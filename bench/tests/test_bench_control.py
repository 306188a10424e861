"""The controls: the reference computed in the next lower precision and
put in the program's place fails the comparison that decides `correct`,
while the program passes it (here on the CPU, at sizes a test holds; on
the chip at the cells' own sizes with ``bench/calibrate.py --seeds``)."""

from bench import harness


def test_library_control_fails_every_op(drive, tiny_lib):
    run = drive("lib-hbm.rearrange", traffic=tiny_lib, seconds=0.2)
    rows = harness.plugin("runners", "library").readings(run, [11, 12, 13], lambda _m: None)
    assert len(rows) == 3 * len(tiny_lib["ops"])
    for row in rows:
        assert row["program"] == 0 and row["program_passes"], row
        assert row["control"] > 0 and not row["control_passes"], row


def test_serving_control_reads_wider_than_the_program(drive, tiny_chat):
    cfg, traffic = tiny_chat
    run = drive("qwen2-7b-8l.chat", config=cfg, traffic=traffic, seconds=1.0)
    rows = harness.plugin("runners", "serve").readings(run, [21, 22, 23], lambda _m: None)
    for row in rows:
        assert row["unfinished"] == 0, row
        assert row["program_passes"] and row["program"] <= cfg["limits"]["served_logit_gap"], row
        assert not row["control_passes"] and row["control"] > row["limit"], row
