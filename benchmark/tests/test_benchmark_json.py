"""BENCHMARK.json against the files the harness actually reads: every cell,
configuration and metric it declares exists under ``benchmark/`` with the
same name, unit, direction, source, layer, ``moves`` and cells."""
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _json(path):
    with open(path) as f:
        return json.load(f)


def test_declared_equals_read():
    declared = _json(os.path.join(ROOT, "BENCHMARK.json"))
    assert declared["command"] == ["python3", "benchmark/run.py"]
    assert declared["paths"] == ["benchmark"]

    cells = {c["name"]: c for c in declared["workloads"]}
    files = {
        os.path.basename(p)[:-5]: _json(p)
        for p in glob.glob(os.path.join(HERE, "workloads", "*.json"))
    }
    assert set(cells) == set(files)
    for name, cell in cells.items():
        on_disk = files[name]
        assert NAME.match(name) and name == f"{cell['config']}.{cell['traffic']}"
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == on_disk[key], (name, key)
        assert len(cell["why"]) <= 200
    four = [c for c in cells.values() if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)

    configs = {c["name"]: c for c in declared["configs"]}
    assert set(configs) == {c["config"] for c in cells.values()}
    for name, config in configs.items():
        on_disk = _json(os.path.join(ROOT, config["file"]))
        assert config["file"] == f"benchmark/configs/{name}.json"
        assert config["source"] == on_disk["source"]
        assert config["reduced"] == on_disk["reduced"]

    metric_files = {
        m["name"]: m for m in
        (_json(p) for p in glob.glob(os.path.join(HERE, "metrics", "*.json")))
    }
    declared_metrics = {
        m["name"]: (kind, m)
        for kind in ("end_to_end", "per_layer") for m in declared[kind]
    }
    assert set(declared_metrics) == set(metric_files)
    end_to_end = {m["name"] for m in declared["end_to_end"]}
    for name, (kind, metric) in declared_metrics.items():
        on_disk = metric_files[name]
        assert NAME.match(name) and on_disk["kind"] == kind
        for key in ("unit", "better", "source"):
            assert metric[key] == on_disk[key], (name, key)
        assert metric.get("workloads") == on_disk.get("workloads"), name
        assert os.path.exists(
            os.path.join(HERE, "reducers", on_disk["reducer"] + ".py")
        )
        if kind == "per_layer":
            assert metric["layer"] == on_disk["layer"]
            assert metric["moves"] == on_disk["moves"] and metric["moves"] in end_to_end
            # reported only where the metric it moves is
            moved = declared_metrics[metric["moves"]][1].get("workloads")
            if moved is not None:
                assert set(metric.get("workloads") or cells) <= set(moved), name
        else:
            assert 0.01 <= metric["bound"] <= 0.1
            assert metric["source"] in ("host_clock", "device_trace")
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for name in cells:
        reported = [
            m for m in declared["end_to_end"]
            if m.get("workloads") is None or name in m["workloads"]
        ]
        assert {"setup_s"} < {m["name"] for m in reported}
