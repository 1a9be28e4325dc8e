"""Every BENCHMARK.json entry resolves its files by name, and the file
keeps to the benchmark's contract."""
import json
import re

import pytest

import _paths
import harness

BM = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BM["workloads"]])
def test_each_workload_resolves_config_traffic_limits_and_metrics(workload):
    cell = harness.resolve(BM, workload)
    drv = harness.kind(cell.traffic["kind"])
    for fn in ("timed", "warm", "window", "check", "end_to_end"):
        assert callable(getattr(drv, fn))
    assert (_paths.BENCH / "designs" / f"{cell.config['design']}.py").exists()
    assert cell.config["name"] == cell.workload["config"]
    assert cell.limits, "every cell compares at least one number"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in names


def test_every_metric_reader_exists_and_names_keep_to_the_contract():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    seen = set()
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert (_paths.BENCH / "metrics" / f"{m['name']}.py").exists()
        ends = {e["name"] for e in BM["end_to_end"]}
        assert m["moves"] in ends
    for c in BM["configs"]:
        assert (_paths.ROOT / c["file"]).exists()
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    assert len({(w["config"], w["traffic"]) for w in BM["workloads"]}) == \
        len(BM["workloads"])


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve(BM, "no_such.cell")
