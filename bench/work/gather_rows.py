"""Work of a masked row gather: the index table read, every selected
row read once and every output row written once.  A row whose index is
the -1 sentinel needs no read; ``valid`` counts the rows that do."""


def work(rows_out, row_bytes, valid, **_) -> dict:
    return {"bytes": 4 * rows_out + valid * row_bytes + rows_out * row_bytes,
            "flops": 0}
