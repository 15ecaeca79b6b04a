"""The harness on the CPU: files found by name, the result line, refusals."""

import json
import os
import sys

import pytest

from .helpers import BENCH, REPO, run_cell, tiny_checkout

sys.path.insert(0, BENCH)

import run  # noqa: E402

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [c["name"] for c in manifest()["workloads"]])
def test_each_cell_finds_its_files_by_name(cell):
    m = manifest()
    entry, config, traffic = run.find_cell(m, cell)
    declared = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert config["reduced"] == declared["reduced"]
    assert {"file_size", "block_size", "flags", "warmup_flags"} <= set(config)
    assert {"flags", "verify", "trace_seconds"} <= set(traffic)
    for traced in (False, True):
        metrics = run.metrics_for(m, cell, traced)
        assert metrics, f"{cell} reports no {'per-layer' if traced else 'e2e'}"
        for metric in metrics:
            assert callable(run.load_reader(metric["name"]))


def test_every_metric_moves_an_end_to_end_metric_of_its_cells():
    m = manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for metric in m["per_layer"]:
        moved = e2e[metric["moves"]]
        for cell in metric["workloads"]:
            assert cell in moved.get("workloads", [cell])


def test_a_cell_added_as_files_alone_runs(tmp_path):
    co = tiny_checkout(str(tmp_path))
    with open(os.path.join(co, "benchmark", "configs",
                           "seqread-1m.json"), "w") as f:
        json.dump({"source": "https://github.com/breuner/elbencho",
                   "reduced": [], "file_size": 64 << 20,
                   "block_size": 1 << 20, "flags": ["-t", "2", "--tpuids",
                                                    "0"],
                   "warmup_flags": ["-s", str(8 << 20)]}, f)
    with open(os.path.join(co, "benchmark", "traffic",
                           "ingest-deep.json"), "w") as f:
        json.dump({"flags": ["-r", "--iodepth", "16"], "verify": False,
                   "trace_seconds": 1}, f)
    path = os.path.join(co, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "seqread-1m", "source": "x",
                         "file": "benchmark/configs/seqread-1m.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "seqread-1m.deep", "config": "seqread-1m",
                           "traffic": "ingest-deep", "chips": 1,
                           "why": "test"})
    m["end_to_end"][0]["workloads"].append("seqread-1m.deep")
    with open(path, "w") as f:
        json.dump(m, f)
    rc, result, err = run_cell(co, "seqread-1m.deep", seconds=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"hbm_ingest_mib_s", "setup_s"}


def test_a_metric_added_as_a_file_is_found(tmp_path, monkeypatch):
    co = tiny_checkout(str(tmp_path))
    os.makedirs(os.path.join(co, "benchmark", "metrics"), exist_ok=True)
    with open(os.path.join(co, "benchmark", "metrics",
                           "h2d_dispatch_us_per_op.bw.py"), "w") as f:
        f.write("def read(run):\n    return 7.0\n")
    monkeypatch.setattr(run, "BENCH", os.path.join(co, "benchmark"))
    assert run.load_reader("h2d_dispatch_us_per_op.bw")(None) == 7.0
    assert run.load_reader("h2d_dispatch_us_per_op.iops") is \
        run.load_reader("h2d_dispatch_us_per_op")
    with pytest.raises(run.Refused):
        run.load_reader("no_such_metric")


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contract_keys(tmp_path, trace):
    co = tiny_checkout(str(tmp_path))
    rc, result, err = run_cell(co, "seqread-16m.direct", trace=trace)
    assert rc == 0, err[-3000:]
    keys = list(result)
    assert keys[:5] == RESULT_KEYS and keys[-1] == "checks"
    assert set(keys) <= set(RESULT_KEYS) | {"breakdown", "checks"}
    assert result["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(result["device"])
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}
    assert err.rstrip().splitlines()[-1].startswith("bench: check ")


def test_a_run_without_a_tpu_fails(tmp_path):
    co = tiny_checkout(str(tmp_path))
    rc, result, err = run_cell(co, "seqread-16m.direct", cpu=False)
    assert rc != 0 and result is None
    assert "JAX finds no TPU: cpu" in err


def test_a_checkout_of_the_benchmark_alone_fails(tmp_path):
    co = tiny_checkout(str(tmp_path))
    rc, result, err = run_cell(co, "seqread-16m.direct", program=False)
    assert rc != 0 and result is None


def test_manifest_keeps_to_the_contract_shape():
    import re
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and c["file"].startswith("benchmark/")
        assert all(name.match(k) for k in c["reduced"])
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    for metric in m["end_to_end"] + m["per_layer"]:
        assert name.match(metric["name"]) and unit.match(metric["unit"])
        assert set(metric.get("workloads", [])) <= cells
    for metric in m["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
    for cell in cells:
        reported = run.metrics_for(m, cell, False)
        assert "setup_s" in {x["name"] for x in reported}
        assert len(reported) >= 2 and run.metrics_for(m, cell, True)
