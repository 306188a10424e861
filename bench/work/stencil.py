"""Work of a linear stencil program of ``repeat`` sweeps over a grid: the
least traffic is one read and one write of the grid (a fused program
keeps the intermediate sweeps on chip); each sweep does one multiply
and one add per tap and cell, less one add."""

import math


def work(shape, itemsize, taps, repeat, **_) -> dict:
    cells = math.prod(shape)
    return {"bytes": 2 * cells * itemsize, "flops": repeat * (2 * taps - 1) * cells}
