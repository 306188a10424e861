"""Work of an N-D permute: one read and one write of the array."""

import math


def work(shape, itemsize, **_) -> dict:
    n = math.prod(shape) * itemsize
    return {"bytes": 2 * n, "flops": 0}
