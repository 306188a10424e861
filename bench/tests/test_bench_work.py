"""Work functions against hand-counted shapes."""

import pytest

from bench import harness, peaks


def work(name, **kw):
    return harness.plugin("work", name).work(**kw)


def test_data_movement_bytes():
    assert work("copy", shape=(8192, 8192), itemsize=4) == {"bytes": 536870912, "flops": 0}
    assert work("permute", shape=(8, 4096, 28, 128), itemsize=2)["bytes"] == 469762048
    assert work("interlace", n=4, length=1 << 26, itemsize=4)["bytes"] == 2147483648
    assert work("deinterlace", n=4, length=1 << 26, itemsize=4)["bytes"] == 2147483648
    g = work("gather_rows", rows_out=65536, row_bytes=7168, valid=65535)
    assert g["bytes"] == 4 * 65536 + 65535 * 7168 + 65536 * 7168


def test_stencil_work():
    w = work("stencil", shape=(65536, 7168), itemsize=4, taps=4, repeat=8)
    assert w == {"bytes": 3758096384, "flops": 8 * 7 * 65536 * 7168}


def test_dense_decoder_flops():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
           "intermediate_size": 16, "vocab_size": 10, "num_hidden_layers": 3}
    flops = harness.plugin("work", "dense_decoder").flops
    per_token = 2 * (8 * 4 * 4 + 8 * 8 + 3 * 8 * 16)  # qkv, o, gate/up/down
    # one token at position 5: 6 keys; logits taken
    assert flops(cfg, [(1, 5, True)]) == 3 * (per_token + 4 * 2 * 4 * 6) + 2 * 8 * 10
    # three prompt tokens from 0: 1 + 2 + 3 keys; no logits
    assert flops(cfg, [(3, 0, False)]) == 3 * (3 * per_token + 4 * 2 * 4 * 6)


def test_least_seconds_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds({"bytes": 819e9, "flops": 0}, p) == pytest.approx(1.0)
    assert peaks.least_seconds({"bytes": 1, "flops": 197e12 * 2}, p) == pytest.approx(2.0)
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")
