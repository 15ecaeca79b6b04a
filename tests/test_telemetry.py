"""Telemetry subsystem tests: /metrics scrape-under-load through the real
master path (service harness), fleet aggregation semantics, Chrome
trace-event schema validation (dispatch/DMA sub-spans), and the
zero-overhead guarantee of the telemetry-off path."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tests.cpu_env import cpu_mesh_env  # noqa: E402
from elbencho_tpu.testing.service_harness import (  # noqa: E402
    default_env, free_ports, service_procs)

pytestmark = pytest.mark.obs  # observability gate (`make test-obs`)


def _scrape(url: str, timeout: float = 3.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        assert r.status == 200
        return r.read().decode()


def _metric(body: str, name: str) -> "float | None":
    for line in body.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def _validate_chrome_trace(path: str) -> "list[dict]":
    """Chrome trace-event schema check: complete-event spans ("X") with
    the fields Perfetto needs (args a JSON object), plus the fleet-
    tracing flow events ("s"/"f" RPC arrows, id-bound) and "M" process
    metadata a merged trace carries."""
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["tool"] == "elbencho-tpu"
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "s", "f", "M"), e
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int)
        if e["ph"] == "M":
            assert isinstance(e.get("args", {}), dict)
            continue
        assert isinstance(e["cat"], str) and e["cat"]
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
            assert isinstance(e.get("args", {}), dict)
        else:  # flow event: bound by id, finish side carries bp=e
            assert isinstance(e["id"], int)
            if e["ph"] == "f":
                assert e.get("bp") == "e"
    return doc["traceEvents"]


# ---------------------------------------------------------------------------
# registry / rendering units
# ---------------------------------------------------------------------------

def test_snake_case_wire_keys():
    from elbencho_tpu.telemetry.registry import snake_case
    assert snake_case("TpuH2dDirectOps") == "tpu_h2d_direct_ops"
    assert snake_case("SvcHeartbeatAgeHwmUsec") == "svc_heartbeat_age_hwm_usec"
    assert snake_case("CPUUtil") == "cpu_util"


def test_registry_prometheus_rendering():
    from elbencho_tpu.stats.latency_histogram import LatencyHistogram
    from elbencho_tpu.telemetry.registry import MetricRegistry
    reg = MetricRegistry()
    reg.counter("bytes_done_total", "bytes")
    reg.gauge("cpu", 'has "quotes" and\nnewline')
    reg.histogram("lat_usec", "latency")
    reg.set("bytes_done_total", 123)
    reg.set("cpu", 5.5, (("host", 'h"1"'),))
    h = LatencyHistogram()
    h.add_latency(100)
    reg.set("lat_usec", h)
    text = reg.render()
    assert "# TYPE elbencho_tpu_bytes_done_total counter" in text
    assert "elbencho_tpu_bytes_done_total 123" in text
    assert 'elbencho_tpu_cpu{host="h\\"1\\""} 5.5' in text
    assert 'elbencho_tpu_lat_usec_bucket{le="+Inf"} 1' in text
    assert "elbencho_tpu_lat_usec_count 1" in text
    assert "elbencho_tpu_lat_usec_sum 100" in text
    # HELP newlines are escaped so the line-oriented format stays valid
    help_line = next(ln for ln in text.splitlines()
                     if ln.startswith("# HELP elbencho_tpu_cpu "))
    assert help_line.endswith(r"and\nnewline")
    assert "newline" not in [ln for ln in text.splitlines()]


def test_tracer_ring_bounds_and_sampling(tmp_path):
    from elbencho_tpu.telemetry.tracer import Tracer
    t = Tracer(str(tmp_path / "t.json"), max_events=8)
    for i in range(20):
        t.record("op", "io", t.now_ns(), 1, rank=i)
    assert t.num_recorded == 20
    assert t.num_overwritten == 12
    t.write()
    events = _validate_chrome_trace(t.path)
    assert len(events) == 8
    # ring keeps the newest spans, chronological order
    assert [e["tid"] for e in events] == list(range(12, 20))
    # probabilistic sampling drops op spans, keeps unsampled spans
    s = Tracer(str(tmp_path / "s.json"), sample=0.0)
    s.record_op("write", "WRITE", s.now_ns(), 1, 0, 0, 4096)
    s.record("WRITE", "phase", s.now_ns(), 1)
    assert s.num_recorded == 1
    assert s.snapshot_events()[0]["cat"] == "phase"


def test_config_validation():
    from elbencho_tpu.config.args import ConfigError, parse_cli
    cfg, _ = parse_cli(["--tracesample", "0.5", "/tmp/x"])
    with pytest.raises(ConfigError, match="tracesample"):
        cfg.check()
    cfg2, _ = parse_cli(["--telemetryport", "0", "/tmp/x"])
    with pytest.raises(ConfigError, match="telemetryport"):
        cfg2.check()
    cfg3, _ = parse_cli(["--tracesample", "0.5", "--tracefile", "/tmp/t",
                         "/tmp/x"])
    cfg3.check()  # valid combination


# ---------------------------------------------------------------------------
# overhead guard: telemetry off == no per-op work
# ---------------------------------------------------------------------------

def test_telemetry_off_path_is_noop(tmp_path, monkeypatch):
    """Without --tracefile no Tracer may even be CONSTRUCTED, and no
    instrumentation point may call record() — the off path must resolve
    to a single `is None` attribute test per op."""
    from elbencho_tpu.telemetry.tracer import Tracer

    def boom(*_a, **_k):
        raise AssertionError("tracer touched with telemetry off")

    monkeypatch.setattr(Tracer, "__init__", boom)
    monkeypatch.setattr(Tracer, "record", boom)
    monkeypatch.setattr(Tracer, "record_op", boom)
    from elbencho_tpu.config.args import parse_cli
    from elbencho_tpu.coordinator import Coordinator
    bench = tmp_path / "bench"
    bench.mkdir()
    cfg, _ = parse_cli(["-w", "-d", "-t", "1", "-n", "1", "-N", "2",
                        "-s", "8K", "-b", "4K", "--nolive", str(bench)])
    cfg.derive()
    cfg.check()
    coord = Coordinator(cfg)
    assert coord.manager.shared.tracer is None
    assert coord._run_master_or_local() == 0
    for w in coord.manager.workers:
        assert w._tracer is None
    # exporter/telemetry equally absent without --telemetry
    assert coord._exporter is None
    assert coord.statistics.telemetry is None


def test_stage_spans_off_path_creates_no_annotation(tmp_path, monkeypatch):
    """A TPU read phase without --tracefile and without a recording
    profiler arms no stage-span handle and opens no profiler annotation:
    every stage hook stays one `is None` test, while the always-on stage
    counters still book."""
    import json
    import jax.profiler
    from elbencho_tpu.telemetry.tracer import StageSpans

    def boom(*_a, **_k):
        raise AssertionError("stage spans touched with tracing off")

    class NoAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, *_a, **_k):
            boom()

    monkeypatch.setattr(StageSpans, "__init__", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", NoAnnotation)
    from elbencho_tpu.cli import main
    data = tmp_path / "data.bin"
    data.write_bytes(os.urandom(256 * 1024))
    jf = tmp_path / "r.json"
    assert main(["-r", "-t", "1", "-b", "64K", "--iodepth", "2",
                 "--tpuids", "0", "--nolive", "--jsonfile", str(jf),
                 str(data)]) == 0
    rec = json.loads(jf.read_text().splitlines()[-1])
    assert rec["TpuReadyWaitOps"] == 4
    assert rec["OpResidentLatHisto"]["LatNumValues"] == 4


# ---------------------------------------------------------------------------
# local scrape + trace with the TPU data path (dispatch/DMA sub-spans)
# ---------------------------------------------------------------------------

def test_local_tpu_trace_has_dispatch_dma_subspans(tmp_path):
    from elbencho_tpu.cli import main
    data = tmp_path / "data.bin"
    data.write_bytes(os.urandom(256 * 1024))
    trace = tmp_path / "trace.json"
    rc = main(["-r", "-t", "1", "-b", "64K", "--tpuids", "0",
               "--tracefile", str(trace), "--nolive", str(data)])
    assert rc == 0
    events = _validate_chrome_trace(str(trace))
    cats = {e["cat"] for e in events}
    assert {"io", "tpu", "phase"} <= cats
    names = {e["name"] for e in events if e["cat"] == "tpu"}
    assert {"tpu_dispatch", "tpu_dma"} <= names
    io = [e for e in events if e["cat"] == "io"]
    assert io and all({"phase", "offset", "size"} <= set(e["args"])
                      for e in io)


# ---------------------------------------------------------------------------
# the real master path: scrape under load + fleet aggregation + traces
# ---------------------------------------------------------------------------

@pytest.fixture()
def tpu_services():
    env = cpu_mesh_env(8, base=default_env())
    env["ELBENCHO_TPU_NO_NATIVE"] = "1"
    env["ELBENCHO_TPU_NO_DEFAULT_RESFILES"] = "1"
    with service_procs(free_ports(2), env=env) as _procs:
        yield _procs


def test_master_fleet_metrics_and_trace_under_load(tpu_services, tmp_path):
    """Acceptance: during a running multi-host phase, GET /metrics on the
    master returns fleet-aggregated counters matching the per-host
    sums/MAXes (bracketed by one --svcupint poll interval, the documented
    staleness bound), and the --tracefile files of the same run validate
    against the Chrome trace-event schema with dispatch/DMA sub-spans."""
    ports = [p.args[p.args.index("--port") + 1] for p in tpu_services]
    hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
    bench = tmp_path / "bench"
    bench.mkdir()
    trace = tmp_path / "trace.json"
    jsonfile = tmp_path / "out.json"
    tport = free_ports(1)[0]
    from elbencho_tpu.cli import main
    out = {}

    def run():
        out["rc"] = main([
            "-w", "-d", "-t", "2", "-n", "1", "-N", "250", "-s", "64K",
            "-b", "16K", "--hosts", hosts, "--svcupint", "50",
            "--tpuids", "0", "--telemetry", "--telemetryport", str(tport),
            "--tracefile", str(trace), "--jsonfile", str(jsonfile),
            "--nolive", str(bench)])

    t = threading.Thread(target=run)
    t.start()
    try:
        key = "elbencho_tpu_bytes_done_total"
        master_url = f"http://127.0.0.1:{tport}/metrics"
        svc_urls = [f"http://127.0.0.1:{p}/metrics" for p in ports]
        # wait for a mid-phase fleet view (scrape UNDER LOAD)
        mid_run = False
        for _ in range(1200):
            if not t.is_alive():
                break
            try:
                if (_metric(_scrape(master_url), key) or 0) > 0:
                    mid_run = t.is_alive()
                    break
            except OSError:
                pass
            time.sleep(0.02)
        assert mid_run, "never scraped a running phase through the master"
        # bracketed fleet check: the master's view is the per-host sum as
        # of its last /status poll, so give it one poll interval per side
        s1 = sum(_metric(_scrape(u), key) for u in svc_urls)
        time.sleep(0.25)  # > --svcupint 50ms
        m_body = _scrape(master_url)
        m_val = _metric(m_body, key)
        time.sleep(0.25)
        s2 = sum(_metric(_scrape(u), key) for u in svc_urls)
        assert s1 <= m_val <= s2, (s1, m_val, s2)
        # fleet-labeled per-host gauges on the master
        assert sum(1 for ln in m_body.splitlines()
                   if ln.startswith("elbencho_tpu_host_cpu_util_pct{")) == 2
        # MAX-merged HWM: the master's value equals the max over hosts'
        # phase-end values once the run finishes (checked below via JSON)
    finally:
        t.join()
    assert out["rc"] == 0
    # --tracefile from the same run: per-host files (.r<rankoffset>) with
    # op spans and TPU dispatch/DMA sub-spans; the master file carries
    # the phase markers
    master_events = _validate_chrome_trace(str(trace))
    assert {e["name"] for e in master_events if e["cat"] == "phase"} \
        >= {"MKDIRS", "WRITE"}
    svc_traces = sorted(tmp_path.glob("trace.r*.json"))
    assert len(svc_traces) == 2
    for p in svc_traces:
        events = _validate_chrome_trace(str(p))
        cats = {e["cat"] for e in events}
        assert "io" in cats
        tpu_names = {e["name"] for e in events if e["cat"] == "tpu"}
        assert {"tpu_dispatch", "tpu_dma"} <= tpu_names
    # distinct rank offsets: host 0 -> .r0, host 1 -> .r2 (2 threads/host)
    assert [p.name for p in svc_traces] == ["trace.r0.json", "trace.r2.json"]
    # JSON result carries the telemetry keys (JSON-only)
    recs = [json.loads(ln) for ln in jsonfile.read_text().splitlines()]
    write_rec = next(r for r in recs if r["Phase"] == "WRITE")
    assert set(write_rec["HostCPUUtil"]) == set(
        f"127.0.0.1:{p}" for p in ports)
    assert write_rec["TelemetryScrapes"] > 0
    assert write_rec["TraceEvents"] >= 2


def test_service_record_carries_the_stage_counters(tpu_services, tmp_path):
    """The stage counters travel the service wire like TpuDispatchUSec:
    the master's record sums each host's USec and Ops keys, and merges
    the hosts' op-resident histograms."""
    ports = [p.args[p.args.index("--port") + 1] for p in tpu_services]
    hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
    bench = tmp_path / "bench"
    bench.mkdir()
    jsonfile = tmp_path / "out.json"
    from elbencho_tpu.cli import main
    common = ["-d", "-t", "1", "-n", "1", "-N", "2", "-s", "128K", "-b",
              "32K", "--hosts", hosts, "--tpuids", "0", "--verify", "7",
              "--nolive"]
    assert main(["-w", *common, str(bench)]) == 0
    assert main(["-r", *common, "--tpuverify", "--jsonfile", str(jsonfile),
                 str(bench)]) == 0
    rec = next(json.loads(ln) for ln in jsonfile.read_text().splitlines()
               if json.loads(ln)["Phase"] == "READ")
    ops = rec["TpuH2dStagedOps"] + rec["TpuH2dDirectOps"]
    assert ops == 2 * 2 * 4  # two hosts, two files of four blocks each
    assert rec["TpuReadyWaitOps"] == ops
    assert rec["TpuVerifyOps"] == ops
    assert rec["TpuVerifySyncUSec"] > 0 and rec["TpuReadyWaitUSec"] >= 0
    assert rec["OpResidentLatHisto"]["LatNumValues"] == ops


def test_service_metrics_route_idle(tpu_services):
    """/metrics piggybacks on the service control port and answers even
    before any /preparephase."""
    port = tpu_services[0].args[tpu_services[0].args.index("--port") + 1]
    body = _scrape(f"http://127.0.0.1:{port}/metrics")
    assert 'elbencho_tpu_info{role="service"' in body
    assert _metric(body, "elbencho_tpu_scrapes_total") >= 1


# ---------------------------------------------------------------------------
# tools ride-alongs
# ---------------------------------------------------------------------------

def test_chart_renders_trace_timeline(tmp_path):
    from elbencho_tpu.telemetry.tracer import Tracer
    t = Tracer(str(tmp_path / "t.json"))
    t0 = t.now_ns()
    t.record("WRITE", "phase", t0, 1000)
    t.record_op("write", "WRITE", t0, 500, 0, 0, 4096, slot=1)
    t.record("tpu_dispatch", "tpu", t0, 100)
    t.write()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "elbencho-tpu-chart"),
         "--tracefile", t.path],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "WRITE io" in proc.stdout
    assert "WRITE phase" in proc.stdout
    # tpu sub-spans carry no phase arg: the timeline attributes them to
    # the phase marker covering their timestamp
    assert "WRITE tpu" in proc.stdout


def test_summarize_json_appends_telemetry_columns(tmp_path):
    rec = {"Phase": "WRITE", "EntriesLast": 1, "TpuPipeFullStalls": 3,
           "TpuStreamFusedOps": 7, "SvcRetries": 2, "TelemetryScrapes": 5,
           "TraceEvents": 11}
    f = tmp_path / "r.json"
    f.write_text(json.dumps(rec) + "\n")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "elbencho-tpu-summarize-json"),
         str(f), "--csv"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    header, row = proc.stdout.strip().splitlines()[:2]
    cols = header.split(",")
    # appended, never reordered: the telemetry columns keep their order,
    # with the (later) data-plane fault-tolerance, staging-pool,
    # run-lifecycle, streaming-control-plane, pod-slice,
    # latency-percentile, and master-failover columns after them
    assert cols[-31:] == ["Stalls", "Fused", "SvcRetry", "Scrapes",
                          "TraceEv", "IoRetry", "IoTmo", "ChipFail",
                          "PoolReuse", "RegOps", "SqpollOps",
                          "LeaseExp", "Resumed", "StreamB", "DeltaSave",
                          "AggDepth", "ShardMiB", "IciMiB", "IciGbps",
                          "LatP50", "LatP99", "LatP99.9",
                          "Scenario", "Step", "EpochRate",
                          "TailX", "TailOwner", "Tuned", "Gain%",
                          "Adopt", "Takeover"]
    assert row.split(",")[-31:-26] == ["3", "7", "2", "5", "11"]
