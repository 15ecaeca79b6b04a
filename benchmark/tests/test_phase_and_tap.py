"""The window's phase, the check and the tapped calls come from the
traffic file: today's cells select what they always did, a TPUBENCH cell
added as files alone runs, and a landing call that only this file
defines is tapped and checked with no edit to the harness."""

import json
import os
import sys

import numpy as np
import pytest

from .helpers import BENCH, REPO, run_cell, tiny_checkout

sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import tap as tap_mod  # noqa: E402

#: the cells there were before a traffic file could name a phase
READ_CELLS = ["seqread-16m.direct", "seqread-16m.verify",
              "randread-4k.ingest", "seqread-16m-staged.ingest"]


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _old_read_record(path):
    """read_record as it was while every window measured READFILES."""
    with open(path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    recs = [r for r in recs if r.get("Phase") == "READ"]
    if not recs:
        return None
    merged = dict(recs[0])
    for key in run.SUMMED:
        merged[key] = sum(r.get(key, 0) for r in recs)
    merged["Phases"] = len(recs)
    return merged


@pytest.fixture
def records(tmp_path):
    path = tmp_path / "window.json"
    recs = [{"Phase": "WRITE", "TpuHbmBytes": 5},
            {"Phase": "READ", "TpuHbmBytes": 7, "TpuDispatchUSec": 3},
            {"Phase": "TPUBENCH", "TpuHbmBytes": 11},
            {"Phase": "READ", "TpuHbmBytes": 13, "TpuH2dStagedOps": 2},
            {"Phase": "TPUSLICE", "TpuHbmBytes": 17}]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(path)


@pytest.mark.parametrize("cell", READ_CELLS)
def test_each_read_cell_selects_the_same_phase_and_records(cell, records):
    _, _, traffic = run.find_cell(manifest(), cell)
    assert not {"phase", "check", "tap"} & set(traffic)
    measured, name = run.window_phase(traffic)
    assert (measured, name) == ("READFILES", "READ")
    assert run.read_record(records, name) == _old_read_record(records)
    assert run.read_record(records, name)["TpuHbmBytes"] == 20


@pytest.mark.parametrize("phase,name,landed", [
    ("READFILES", "READ", 20), ("TPUBENCH", "TPUBENCH", 11),
    ("TPUSLICE", "TPUSLICE", 17)])
def test_the_window_phase_comes_from_the_traffic(records, phase, name,
                                                 landed):
    assert run.window_phase({"phase": phase}) == (phase, name)
    assert run.read_record(records, name)["TpuHbmBytes"] == landed
    with pytest.raises(run.Refused):
        run.window_phase({"phase": "NO_SUCH_PHASE"})


def test_the_ceiling_cell_measures_tpubench_against_host_bytes():
    entry, config, traffic = run.find_cell(manifest(), "h2d-ceiling.tpubench")
    assert run.window_phase(traffic) == ("TPUBENCH", "TPUBENCH")
    assert traffic["check"] == "host" and "tap" not in traffic
    assert (config["block_size"], config["iodepth"], config["threads"]) \
        == (16 << 20, 8, 1)
    assert "--tpudirect" not in traffic["flags"] + config["flags"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_ceiling_cell_runs_at_cpu_size(tmp_path, trace):
    co = tiny_checkout(str(tmp_path))
    rc, result, err = run_cell(co, "h2d-ceiling.tpubench", trace=trace)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert set(result["checks"]) == {"bad_words", "missed_samples",
                                     "program_errors"}
    if trace:
        # the CPU trace has no TPU device: the device metrics read nothing
        assert set(result["metrics"]) == {"h2d_dispatch_us_per_op.ceiling",
                                          "h2d_wait_us_per_op.ceiling"}
    else:
        assert set(result["metrics"]) == {"hbm_iops", "setup_s"}
    assert "bench: tap: " in err


def test_a_tpubench_cell_added_as_files_alone_runs(tmp_path):
    co = tiny_checkout(str(tmp_path))
    bench = os.path.join(co, "benchmark")
    with open(os.path.join(bench, "configs", "h2d-1m.json"), "w") as f:
        json.dump({"source": "https://github.com/breuner/elbencho",
                   "reduced": [], "file_size": 64 << 20,
                   "block_size": 1 << 20,
                   "flags": ["-t", "2", "--iodepth", "4", "--tpuids", "0"],
                   "warmup_flags": ["-s", str(8 << 20)]}, f)
    with open(os.path.join(bench, "traffic", "h2d-both.json"), "w") as f:
        json.dump({"flags": ["--tpubench", "--tpubenchpat", "both"],
                   "phase": "TPUBENCH", "check": "host", "verify": False,
                   "trace_seconds": 1}, f)
    path = os.path.join(co, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "h2d-1m", "source": "x",
                         "file": "benchmark/configs/h2d-1m.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "h2d-1m.both", "config": "h2d-1m",
                           "traffic": "h2d-both", "chips": 1, "why": "test"})
    {e["name"]: e for e in m["end_to_end"]}["hbm_iops"]["workloads"] \
        .append("h2d-1m.both")
    with open(path, "w") as f:
        json.dump(m, f)
    rc, result, err = run_cell(co, "h2d-1m.both", seconds=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["attempted"] > 0
    assert set(result["metrics"]) == {"hbm_iops", "setup_s"}
    assert "nothing written" in err
    rc, result, err = run_cell(co, "h2d-1m.both", "--fault", "torn",
                               seconds=1)
    assert result["correct"] is False and \
        result["checks"]["bad_words"]["value"] > 0, err[-3000:]


# -- a landing call that only this file defines -----------------------------

class Lander:
    """Lands a host view as the contract in tap.py asks: the landed array
    in ``self.landed``, left as it was where it lands nothing."""

    def __init__(self, stale_after: int = 0):
        self.landed = None
        self.stale_after = stale_after
        self.calls = 0

    def land(self, view, length, file_offset=0):
        import jax.numpy as jnp
        self.calls += 1
        if self.stale_after and self.calls > self.stale_after:
            return
        self.landed = jnp.asarray(np.frombuffer(view, np.uint32,
                                                length // 4).copy())

    def land_twice(self, view, length):
        """A landing call that makes another: tapped once, as itself."""
        self.land(view, length)


def land_returning(buf):
    import jax.numpy as jnp
    return jnp.asarray(np.frombuffer(buf, np.uint32).copy())


HERE = __name__
LAND = {"call": f"{HERE}:Lander.land", "view": "view", "length": "length",
        "offset": "file_offset", "landed": "landed"}


def _tap(check_kind, specs, seed=2 ** 31 + 11, block=4096):
    tap = tap_mod.Tap(seed, block, check_kind, specs)
    tap.install()
    tap.armed = True
    return tap


def _buffers(n, block=4096):
    return [memoryview(bytearray(block)) for _ in range(n)]


def test_a_traffic_file_names_a_tpuslice_phase_and_a_second_landing_call(
        tmp_path, monkeypatch):
    """A slice cell added as files alone: the harness finds its phase in
    its traffic file and taps the landing call that file names."""
    co = tiny_checkout(str(tmp_path))
    with open(os.path.join(co, "benchmark", "traffic", "slice.json"),
              "w") as f:
        json.dump({"flags": ["--tpuslice"], "phase": "TPUSLICE",
                   "check": "host", "verify": False, "trace_seconds": 1,
                   "tap": tap_mod.DEFAULT_TAP + [LAND]}, f)
    m = manifest()
    m["workloads"].append({"name": "seqread-16m.slice",
                           "config": "seqread-16m", "traffic": "slice",
                           "chips": 4, "why": "test"})
    monkeypatch.setattr(run, "ROOT", co)
    monkeypatch.setattr(run, "BENCH", os.path.join(co, "benchmark"))
    _, _, traffic = run.find_cell(m, "seqread-16m.slice")
    assert run.window_phase(traffic) == ("TPUSLICE", "TPUSLICE")
    tap = _tap(traffic["check"], traffic["tap"])
    try:
        lander = Lander()
        bufs = _buffers(8)
        for i in range(64):
            lander.land(bufs[i % 8], 4096)
        assert tap.calls == 64 and len(tap.block_samples()) == 64
        checks, failed = check.compare(tap, {"Phase": "TPUSLICE"}, 1, False,
                                       0)
        assert check.passed(checks) and failed == 0, checks
    finally:
        tap.remove()
    assert Lander.land.__name__ == "land"


RETURN = {"call": f"{HERE}:land_returning", "view": "buf",
          "landed": "return"}


@pytest.mark.parametrize("specs,stale_after,sound", [
    ([LAND], 0, True), ([LAND], 3, False), ([RETURN], 0, True)],
    ids=["attribute", "attribute-stale", "return"])
def test_the_host_check_reads_each_landing(specs, stale_after, sound):
    tap = _tap("host", specs)
    try:
        lander = Lander(stale_after)
        for buf in _buffers(16):
            if specs[0] is LAND:
                lander.land(buf, 4096)
            else:
                land_returning(buf)
        checks, _ = check.compare(tap, {}, 1, False, 0)
    finally:
        tap.remove()
    assert tap.calls == 16
    assert check.passed(checks) is sound, checks
    assert (checks["bad_words"]["value"] == 0) is sound
    assert checks["program_errors"]["value"] == 0


def test_the_host_check_marks_both_halves_and_a_new_value_each_call():
    tap = _tap("host", [LAND])
    try:
        lander = Lander()
        buf = _buffers(1)[0]
        seen = []
        for _ in range(3):
            lander.land(buf, 4096)
            seen.append(np.frombuffer(buf, np.uint64).copy())
    finally:
        tap.remove()
    idx = np.flatnonzero(seen[0])
    assert len(idx) == tap_mod.MARKERS
    assert (idx < 256).sum() == (idx >= 256).sum() == tap_mod.MARKERS // 2
    assert not np.intersect1d(seen[0][idx], seen[1][idx]).size


def test_a_dataset_check_reads_the_offset_and_never_writes():
    salt = 5
    data = reference.pattern_u64(0, 8 * 4096, salt).view(np.uint8)
    tap = _tap("dataset", [LAND])
    try:
        lander = Lander()
        for i in range(8):
            view = memoryview(data[i * 4096:(i + 1) * 4096].copy())
            before = bytes(view)
            lander.land(view, 4096, file_offset=i * 4096)
            assert bytes(view) == before
        checks, _ = check.compare(tap, {}, salt, False, 0)
        assert check.passed(checks), checks
        checks, _ = check.compare(tap, {}, salt + 1, False, 0)
        assert checks["bad_words"]["value"] > 0
    finally:
        tap.remove()


def test_a_dataset_check_refuses_a_call_without_an_offset():
    spec = {**LAND}
    del spec["offset"]
    with pytest.raises(ValueError, match="offset"):
        _tap("dataset", [spec]).remove()


def test_a_landing_call_inside_another_is_tapped_once():
    spec_twice = {"call": f"{HERE}:Lander.land_twice", "view": "view",
                  "length": "length", "landed": "landed"}
    tap = _tap("host", [LAND, spec_twice])
    try:
        lander = Lander()
        for buf in _buffers(4):
            lander.land_twice(buf, 4096)
        checks, _ = check.compare(tap, {}, 1, False, 0)
    finally:
        tap.remove()
    assert tap.calls == 4 and check.passed(checks), checks
