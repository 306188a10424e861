"""Work of interlacing ``n`` arrays of ``length`` elements: each input
read once, the interlaced output written once."""


def work(n, length, itemsize, **_) -> dict:
    return {"bytes": 2 * n * length * itemsize, "flops": 0}
