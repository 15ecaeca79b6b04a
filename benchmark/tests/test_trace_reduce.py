"""The reduction from a profiler trace to per-layer numbers."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def synthetic():
    """One TPU over a 100 ns window: ops at 10-30 and 20-40 (overlapping)
    and 70-80, inside programs; the host busy in 40-70 with 'read'."""
    return [
        _plane("/host:CPU", python=[["bench.window", 0, 100],
                                    ["read", 40, 30]]),
        _plane("/device:TPU:0", **{
            "XLA Modules": [["jit_a(1)", 5, 40], ["jit_b(2)", 65, 20]],
            "XLA Ops": [["%copy.1 = u32[4] copy(u32[4] %x)", 10, 20],
                        ["%k.2 = s32[] custom-call(...)", 20, 20],
                        ["%k.2 = s32[] custom-call(...)", 70, 10]]}),
    ]


@pytest.mark.parametrize("intervals,merged", [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(2, 5), (0, 3), (4, 6)], [(0, 6)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
])
def test_union_of_intervals(intervals, merged):
    assert trace_reduce.union(intervals) == merged


def test_busy_is_the_union_and_idle_the_rest():
    s = trace_reduce.reduce(synthetic())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)       # 10-40 and 70-80
    assert sum(s.gap_s.values()) == pytest.approx(60e-9)
    assert s.gap_s["read"] == pytest.approx(30e-9)  # the gap 40-70


def test_kernel_time_by_name_and_program():
    s = trace_reduce.reduce(synthetic())
    assert s.op_s == pytest.approx({"jit_a/copy.1": 20e-9,
                                    "jit_a/k.2": 20e-9, "jit_b/k.2": 10e-9})
    assert s.time_of(r"/k\.") == (pytest.approx(30e-9), 2)
    seconds, runs = s.program_time(r"^k\.")
    assert runs == 2 and seconds == pytest.approx(60e-9)


def test_the_harness_programs_are_not_the_programs_work():
    planes = synthetic()
    device = planes[1]["lines"]
    device[0]["events"].append(["jit_bench_tap_copy(3)", 85, 10])
    device[1]["events"].append(["%copy.9 = u32[4] copy(u32[4] %y)", 88, 5])
    s = trace_reduce.reduce(planes)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.busy_all_s == pytest.approx(45e-9)
    assert "jit_bench_tap_copy/copy.9" not in s.op_s
    assert all(p != "jit_bench_tap_copy" for p, _, _ in s.programs)


def test_window_is_required():
    planes = synthetic()
    planes[0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes)


def test_recorded_verify_trace():
    """A 142 ms cut of a seqread-16m.verify window on a TPU v5 lite: three
    runs of the fingerprint program, waits on np.asarray between them."""
    with open(os.path.join(DATA, "verify_trace.json")) as f:
        s = trace_reduce.reduce(json.load(f))
    assert s.window_s == pytest.approx(0.141693678)
    assert 0 < s.busy_s < 0.01 * s.window_s
    seconds, runs = s.program_time(r"^elbencho_fingerprint")
    assert runs == 3
    kernel_s, calls = s.time_of(r"/elbencho_fingerprint")
    assert calls == 3 and kernel_s < seconds
    top = s.breakdown()
    assert top["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_roofline_of_the_recorded_trace_is_a_share():
    import run
    with open(os.path.join(DATA, "verify_trace.json")) as f:
        trace = trace_reduce.reduce(json.load(f))
    reader = run.load_reader("elbencho_fingerprint_roofline")
    state = run.Run(block_size=16 << 20, trace=trace,
                    peaks=run.load_peaks("TPU v5 lite"))
    assert 0 < reader(state) <= 100


def test_unknown_device_kind_is_an_error():
    import run
    with pytest.raises(run.Refused):
        run.load_peaks("TPU v99 imaginary")
