"""Work of one decode step's attention over the slots' KV rings, every layer.

``slots`` query rows (one per decoding slot) attend ``kv_rows`` ring rows
in all (the sum over the slots of the rows each attends).  Bytes: each
attended row's K and V for every KV head read once, each slot's query
read and its output written once, in the served dtype.  FLOPs: ``Q K^T``
and ``P V`` for every query head.  Rows past a slot's length, idle slots,
split partials and re-reads do not count.
"""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def work(cfg: dict, slots: int, kv_rows: int) -> dict:
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // hq
    size, layers = ITEMSIZE[cfg["torch_dtype"]], cfg["num_hidden_layers"]
    kv = kv_rows * 2 * hkv * dh * size
    q_out = slots * 2 * hq * dh * size
    return {"bytes": layers * (kv + q_out), "flops": layers * 4 * hq * dh * kv_rows}
