"""Per-op stage counters and spans of the storage->HBM path: one
op-resident sample, one ready wait and one reap-queue wait per H2D op of
the fused ring (whole and --timelimit-cut phases), one verify op per
--tpuverify block, op spans that enclose their stage spans under
--tracefile, the stage spans by name in a JAX profiler trace, and the
transfer pipeline's stage accounting on its own."""

import json
import os
import sys

import pytest

from elbencho_tpu.utils import native as native_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the profiler sink's leaf stages (reap_queue and op stay in the ring)
PROFILER_STAGES = {"stream_reap", "tpu_dispatch", "h2d_wait",
                   "verify_sync", "verify_expect"}


def _native_stream_or_skip(monkeypatch):
    monkeypatch.delenv("ELBENCHO_TPU_NO_NATIVE", raising=False)
    native_mod.reset_native_engine_cache()
    native = native_mod.get_native_engine()
    if native is None or not native.stream_supported():
        pytest.skip("native stream engine unavailable "
                    "(no io_uring and no kernel AIO)")


def _run(args, jf):
    from elbencho_tpu.cli import main
    open(jf, "w").close()
    rc = main([str(a) for a in args] + ["--jsonfile", str(jf)])
    recs = [json.loads(ln) for ln in open(jf) if ln.strip()]
    return rc, next((r for r in recs if r["Phase"] == "READ"), None)


def _write(target, size, block, salt=5):
    from elbencho_tpu.cli import main
    assert main(["-w", "-t", "1", "-s", size, "-b", block, "--verify",
                 str(salt), "--nolive", str(target)]) == 0


def _h2d_ops(rec):
    return rec["TpuH2dStagedOps"] + rec["TpuH2dDirectOps"]


@pytest.mark.parametrize("cut", [False, True], ids=["whole", "cut"])
def test_fused_read_books_one_stage_sample_per_h2d_op(tmp_path,
                                                      monkeypatch, cut):
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    size = "16M" if cut else "1M"
    _write(target, size, "64K")
    args = ["-r", "-t", "1", "-s", size, "-b", "64K", "--iodepth", "4",
            "--tpuids", "0", "--nolive", target]
    if cut:
        # slow the transfer leg so the 1 s limit fires with the ring
        # loaded (256 ops x 10 ms)
        import time as time_mod
        from elbencho_tpu.tpu.device import TpuWorkerContext
        orig = TpuWorkerContext.host_to_device

        def slow_h2d(self, *a, **kw):
            time_mod.sleep(0.01)
            return orig(self, *a, **kw)

        monkeypatch.setattr(TpuWorkerContext, "host_to_device", slow_h2d)
        args += ["--timelimit", "1"]
    rc, rec = _run(args, tmp_path / "r.json")
    assert rc == 0
    ops = _h2d_ops(rec)
    if cut:
        assert 0 < rec["TpuHbmBytes"] < 16 << 20
    else:
        assert ops == 16
    assert rec["TpuStreamFusedOps"] == ops
    assert rec["TpuReadyWaitOps"] == ops
    assert rec["ReapQueueOps"] == ops
    assert rec["OpResidentLatHisto"]["LatNumValues"] == ops
    # the cut chunk's storage latencies are booked, one per reaped op
    assert rec["IOLatHisto"]["LatNumValues"] == ops
    # the resident tail covers the storage leg it contains
    assert rec["OpResidentLatHisto"]["LatMaxMicroSec"] \
        >= rec["IOLatHisto"]["LatMinMicroSec"]


def test_tpuverify_books_one_verify_op_per_block(tmp_path, monkeypatch):
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    _write(target, "1M", "64K", salt=9)
    rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                    "4", "--tpuids", "0", "--verify", "9", "--tpuverify",
                    "--nolive", target], tmp_path / "r.json")
    assert rc == 0
    assert rec["TpuVerifyOps"] == 16 == rec["TpuFingerprint"]["Blocks"]
    assert rec["TpuVerifySyncUSec"] > 0
    assert rec["TpuVerifyExpectUSec"] > 0
    # the verify's sync is the resident moment: still one sample per op
    assert rec["OpResidentLatHisto"]["LatNumValues"] == 16
    assert rec["TpuReadyWaitOps"] == 16


def _spans_by_op(events):
    ops, children = {}, {}
    for e in events:
        args = e.get("args", {})
        if e["ph"] != "X":
            continue
        if e["name"] == "op":
            ops[tuple(args["op"])] = e
            continue
        ids = [args["op"]] if "op" in args else args.get("ops", [])
        for op in ids:
            children.setdefault(tuple(op), []).append(e)
    return ops, children


@pytest.mark.parametrize("verify", [False, True], ids=["plain", "verify"])
def test_tracefile_op_spans_enclose_their_stage_spans(tmp_path,
                                                      monkeypatch, verify):
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    _write(target, "1M", "64K", salt=3)
    trace = tmp_path / "t.json"
    args = ["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth", "4",
            "--tpuids", "0", "--tracefile", trace, "--nolive", target]
    if verify:
        args += ["--verify", "3", "--tpuverify"]
    rc, rec = _run(args, tmp_path / "r.json")
    assert rc == 0
    assert rec["TpuStreamFusedOps"] == 16  # tracing keeps the fused ring
    with open(trace) as f:
        events = json.load(f)["traceEvents"]
    ops, children = _spans_by_op(events)
    assert len(ops) == 16
    names = set()
    for op, root in ops.items():
        assert root["tid"] == op[0]
        lo, hi = root["ts"], root["ts"] + root["dur"]
        for child in children.get(op, []):
            names.add(child["name"])
            # the trace's microsecond rounding: one unit at each end
            assert lo - 1 <= child["ts"], (child, root)
            assert child["ts"] + child["dur"] <= hi + 1, (child, root)
    want = {"read", "stream_reap", "reap_queue", "tpu_dispatch"}
    want |= {"verify_sync", "verify_expect"} if verify \
        else {"h2d_wait", "tpu_dma"}
    assert want <= names
    # no child names an op that has no root span
    assert set(children) <= set(ops)


def test_profiler_trace_names_the_stage_spans(tmp_path, monkeypatch):
    """A JAX profiler session recording at phase start arms the profiler
    sink: the leaf stages appear in the xplane by name, the ring-only
    spans and the harness's umbrella prefix do not, and the phase keeps
    the fused ring."""
    _native_stream_or_skip(monkeypatch)
    import jax
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import trace_reduce
    target = tmp_path / "f"
    _write(target, "1M", "64K", salt=4)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K",
                        "--iodepth", "4", "--tpuids", "0", "--verify", "4",
                        "--tpuverify", "--nolive", target],
                       tmp_path / "r.json")
    finally:
        jax.profiler.stop_trace()
    assert rc == 0
    assert rec["TpuStreamFusedOps"] == 16
    import glob
    xplane, = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {ev[0] for plane in trace_reduce.load(xplane)
             for line in plane["lines"] for ev in line["events"]}
    assert PROFILER_STAGES <= names
    assert not {"op", "reap_queue", "read"} & names
    assert not any(trace_reduce.UMBRELLA.match(n) for n in PROFILER_STAGES)


def test_tpuprofile_traces_stages_without_the_python_tracer(tmp_path,
                                                           monkeypatch):
    """--tpuprofile starts its session with JAX's Python tracer off and
    gets the program's stage spans."""
    _native_stream_or_skip(monkeypatch)
    import glob
    import jax
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import trace_reduce
    levels = []
    start = jax.profiler.start_trace

    def start_trace(log_dir, **kw):
        levels.append(kw["profiler_options"].python_tracer_level)
        return start(log_dir, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    target = tmp_path / "f"
    _write(target, "1M", "64K")
    rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                    "4", "--tpuids", "0", "--tpuprofile", tmp_path / "prof",
                    "--nolive", target], tmp_path / "r.json")
    assert rc == 0 and levels == [0]
    assert rec["TpuStreamFusedOps"] == 16
    xplane, = glob.glob(str(tmp_path / "prof" / "*" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    names = {ev[0] for plane in trace_reduce.load(xplane)
             for line in plane["lines"] for ev in line["events"]}
    assert {"stream_reap", "tpu_dispatch", "h2d_wait"} <= names


class _Ready:
    def __init__(self, ready=True):
        self.ready = ready

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.ready = True
        return self


def test_pipeline_books_ready_wait_and_residency_per_drain():
    import time
    from elbencho_tpu.tpu.device import TransferPipeline
    pipe = TransferPipeline(depth=2)
    t0 = time.perf_counter_ns()
    pipe.submit(lambda: _Ready(False), op_ns=t0)
    pipe.submit(lambda: _Ready(False), op_ns=0)  # booked elsewhere
    pipe.submit(lambda: _Ready(False), op_ns=t0)
    pipe.flush()
    assert pipe.ready_wait_ops == 3
    assert pipe.ready_wait_usec >= 0
    assert len(pipe.resident) == 2
    pipe.reset_counters()
    assert (pipe.ready_wait_ops, len(pipe.resident)) == (0, 0)
