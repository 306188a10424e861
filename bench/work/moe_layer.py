"""Work of the held-expert MoE layers of one engine call, from the counts
the engine records on its spans, summed over the MoE layers: ``rows``
real (token, held expert) assignments and ``groups`` held experts that
got at least one row.

``gmm``: the grouped SwiGLU matmul.  FLOPs: the three matmuls of each
real row (``2 * 3 * hidden * expert width``).  Bytes: the three weight
matrices of each held expert that got rows, read once, and each real row
read and its output written once, in the served dtype.  Tile padding,
rows past the used tiles and re-reads do not count.

``dispatch``: the masked gather into expert-sorted rows and the fused
gather-combine back to token order.  Bytes: each real row read and
written by the gather, and read and written by the combine.  The
buffer's sentinel rows, the index tables and the gates do not count.
"""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def gmm(cfg: dict, rows: int, groups: int) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = ITEMSIZE[cfg["torch_dtype"]]
    return {"flops": 2 * 3 * d * f * rows,
            "bytes": groups * 3 * d * f * size + 2 * rows * d * size}


def dispatch(cfg: dict, rows: int) -> dict:
    return {"bytes": 4 * rows * cfg["hidden_size"] * ITEMSIZE[cfg["torch_dtype"]], "flops": 0}
