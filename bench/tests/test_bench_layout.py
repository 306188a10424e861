"""Every file is found by name, and every name in BENCHMARK.json has its
file: a configuration, a mix, a runner, each op and work function, each
metric's reader."""

import json

from bench import harness


def test_a_dropped_file_is_found_by_name(tmp_path):
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text('{"runner": "library"}')
    (tmp_path / "traffic" / "new-mix.json").write_text('{"ops": []}')
    (tmp_path / "metrics" / "new.metric.py").write_text("def read(run):\n    return 42.0\n")
    assert harness.load_json("configs", "new-model", tmp_path) == {"runner": "library"}
    assert harness.load_json("traffic", "new-mix", tmp_path) == {"ops": []}
    assert harness.plugin("metrics", "new.metric", tmp_path).read(None) == 42.0


def test_every_name_resolves():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = harness.load_json("configs", c["name"])
        assert (harness.ROOT / c["file"]).is_file()
        harness.find("runners", cfg["runner"], ".py")
    for w in spec["workloads"]:
        traffic = harness.load_json("traffic", w["traffic"])
        for op in traffic.get("ops", []):
            harness.find("ops", op["op"], ".py")
            harness.find("work", op["op"], ".py")
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(harness.plugin("metrics", m["name"]), "read")
