"""Work of splitting an array of ``n * length`` elements into ``n``:
the input read once, the outputs written once."""


def work(n, length, itemsize, **_) -> dict:
    return {"bytes": 2 * n * length * itemsize, "flops": 0}
