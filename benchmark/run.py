"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything runs in this one process, which holds the chip(s):

1. set-up: the TPU runtime, the dataset (elbencho's verify pattern, salted
   from --seed, written into an anonymous shmem file: no disk, nothing
   left behind; a sparse one, never written, where the traffic's check
   is "host"), then a warm-up phase of the cell's own traffic on a short
   extent of the file, so that the window compiles nothing;
2. the window: the program's normal path (the CLI's parse and derive, then
   ``Coordinator``) runs the cell's phase under ``--timelimit``, looped
   with ``--infloop``;
   a tap (tap.py) samples what lands in HBM;
3. the check (check.py) against the plain reference (reference.py), or
   against the host bytes each sampled call was given, after the window
   and after the device memory peak has been read;
4. with ``--trace 1`` the window is traced by the JAX profiler, and the
   per-layer metrics and the breakdown are reduced from the trace.

A cell is a configuration (configs/<name>.json) under a traffic mix
(traffic/<name>.json); each metric is a reader, metrics/<name>.py or a
function of readers.py named after the metric. All are found by the
names in BENCHMARK.json, so a new cell or metric is new files and
entries.

A traffic file holds the program's flags (``flags``), whether the cell
verifies (``verify``), the traced window's length (``trace_seconds``)
and, optionally:

- ``phase``: the program's phase that the window measures, a name of
  ``elbencho_tpu.phases.BenchPhase`` (default ``READFILES``); its result
  records are those whose ``Phase`` is that phase's name (``READ``);
- ``check``: ``dataset`` (default), the landed blocks against the
  dataset, or ``host``, against the bytes each landing call was given,
  for a phase that reads no file (tap.py);
- ``tap``: the calls the tap wraps (tap.py; default ``tap.DEFAULT_TAP``).
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import reference  # noqa: E402

#: counters of the program's records summed over a window's phases
SUMMED = ("TpuHbmBytes", "TpuDispatchUSec", "TpuH2dStagedOps",
          "TpuH2dDirectOps", "TpuStreamFusedOps")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Refused(Exception):
    """No result: the cell cannot run here (no chip, no program, no cell)."""


# -- the manifest ------------------------------------------------------------

def load_json(*parts) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as err:
        raise Refused(f"cannot read {path}: {err}") from None


def find_cell(manifest: dict, name: str) -> "tuple[dict, dict, dict]":
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"({', '.join(sorted(cells))})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(manifest: dict, cell: str, traced: bool) -> list:
    group = manifest["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def load_reader(name: str):
    """metrics/<name>.py's ``read``, else the function of readers.py
    named after the metric's base (``device_idle_pct.bw`` ->
    ``device_idle_pct``)."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if os.path.exists(path):
        spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
    import readers
    read = getattr(readers, name.split(".", 1)[0], None)
    if not callable(read):
        raise Refused(f"no reader for metric {name!r}: no {path} and no "
                      f"function in readers.py")
    return read


# -- the run -----------------------------------------------------------------

@dataclass
class Run:
    """What a metric reader reads."""
    block_size: int
    record: "dict | None" = None
    results: object = None        # the program's PhaseResults
    window_s: float = 0.0
    setup_s: float = 0.0
    trace: object = None          # trace_reduce.Summary
    peaks: "dict | None" = None


class _Compiles:
    """Counts XLA compiles by JAX's own monitoring events: executables
    built, less those loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.built = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._loaded)

    def _built(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _loaded(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    @property
    def count(self) -> int:
        return self.built - self.loaded


def window_phase(traffic: dict) -> "tuple[str, str]":
    """(the program's phase that the window measures, the ``Phase`` name
    of its result records), from the traffic's ``phase``."""
    from elbencho_tpu.phases import PHASE_NAMES, BenchPhase
    name = traffic.get("phase", "READFILES")
    if name not in BenchPhase.__members__:
        raise Refused(f"no phase {name!r} in the program: "
                      f"{', '.join(BenchPhase.__members__)}")
    return name, PHASE_NAMES[BenchPhase[name]]


def run_program(argv: "list[str]", on_phase) -> "tuple[int, object]":
    """The CLI's local-run path for argv, with on_phase(coord, phase, run)
    wrapped around each phase; returns (rc, the last phase's results)."""
    from elbencho_tpu.config.args import ConfigError, parse_cli
    from elbencho_tpu.coordinator import Coordinator
    from elbencho_tpu.toolkits import logger

    class Timed(Coordinator):
        def run_benchmark_phase(self, phase, bench_uuid=""):
            on_phase(self, phase, lambda: super(Timed, self)
                     .run_benchmark_phase(phase, bench_uuid))

    try:
        cfg, _ = parse_cli(argv)
        cfg.derive(probe_paths=False)
        cfg.probe_local_paths()
        cfg.check()
    except (ConfigError, OSError) as err:
        raise Refused(f"the program refused {argv}: {err}") from None
    logger.set_log_level(cfg.log_level)
    coord = Timed(cfg)
    with contextlib.redirect_stdout(sys.stderr):
        rc = coord.main()
    return rc, coord._last_phase_results


def read_record(path: str, phase_name: str) -> "dict | None":
    """The window's result record: the first record of the measured
    phase (``Phase`` == phase_name), with the counters summed over every
    such record."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    recs = [r for r in recs if r.get("Phase") == phase_name]
    if not recs:
        return None
    merged = dict(recs[0])
    for key in SUMMED:
        merged[key] = sum(r.get(key, 0) for r in recs)
    merged["Phases"] = len(recs)
    return merged


def memory_peak(devices: list) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # rehearsal and control only; the driver never passes these
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on JAX's CPU backend (rehearsals, tests)")
    ap.add_argument("--fault", default="",
                    help="plant a fault of faults.py under the timed path")
    args = ap.parse_args(argv)
    try:
        return _main(args)
    except Refused as err:
        print(f"bench: REFUSED: {err}", file=sys.stderr, flush=True)
        return 2


def _main(args) -> int:
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(manifest, args.workload)
    traced = bool(args.trace)
    readers = {m["name"]: load_reader(m["name"])
               for m in metrics_for(manifest, cell["name"], traced)}
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["ELBENCHO_TPU_NO_DEFAULT_RESFILES"] = "1"
    sys.path.insert(0, ROOT)
    try:
        import elbencho_tpu  # noqa: F401
    except ImportError as err:
        raise Refused(f"no elbencho_tpu package beside {BENCH}: {err}")

    import jax
    from jax.profiler import TraceAnnotation
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    found = f"{platform} / {kind} / {len(devices)} device(s)"
    if platform != "tpu" and not args.allow_cpu:
        raise Refused(f"JAX finds no TPU: {found}")
    if len(devices) < cell["chips"]:
        raise Refused(f"{cell['name']} needs {cell['chips']} chips: {found}")
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", platform)
    # every program goes into the cache, so each run after the first
    # compiles nothing: the program sets its floor of 0.5 s when it first
    # touches JAX, so let it do that first
    from elbencho_tpu.tpu.device import _get_jax
    _get_jax()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _Compiles()
    peaks = load_peaks(kind) if platform == "tpu" else None
    log(f"cell {cell['name']}: {found}; seed {args.seed}; "
        f"{args.seconds} s window; trace {args.trace}")

    from tap import Tap
    size, block = config["file_size"], config["block_size"]
    n_chips = cell["chips"]
    measured, record_phase = window_phase(traffic)
    try:
        tap = Tap(args.seed, block, traffic.get("check", "dataset"),
                  traffic.get("tap"))
        tap.install()
    except (ValueError, ImportError, AttributeError) as err:
        raise Refused(f"the traffic's tap or check: {err}") from None

    salt = reference.salt_for_seed(args.seed)
    work = tempfile.mkdtemp(prefix="bench-")
    fd = -1
    try:
        with TraceAnnotation("bench.setup"):
            t = time.monotonic()
            fd = os.memfd_create(f"bench-{cell['name']}")
            path = f"/proc/{os.getpid()}/fd/{fd}"
            if tap.check == "dataset":
                reference.write_dataset(fd, size, salt)
                log(f"dataset: {size} bytes of the verify pattern in an "
                    f"anonymous shmem file (memfd, no disk write), salt "
                    f"{salt}, written in {time.monotonic() - t:.3f} s")
            else:
                # the program sizes its phase against the path's size;
                # a sparse memfd holds no page until one is written
                os.ftruncate(fd, size)
                log(f"dataset: none (host check); the path is a sparse "
                    f"memfd of {size} bytes, nothing written")
        base = [*traffic["flags"], *config["flags"], "-b", str(block),
                "-s", str(size), "--nolive"]
        if traffic["verify"]:
            base += ["--verify", str(salt)]
        run = Run(block_size=block, peaks=peaks)
        w: dict = {}   # the window: start, end, compiles, trace state

        def on_phase(coord, phase, body):
            if phase.name != measured or "limit" not in w:
                body()
                return
            mgr = coord.manager
            if "wait_for_workers_done" not in vars(mgr):
                wait = mgr.wait_for_workers_done

                def wait_and_stamp(phase_start):
                    wait(phase_start)
                    w["end"] = time.monotonic()
                mgr.wait_for_workers_done = wait_and_stamp
            if "start" not in w:
                if traced:
                    # JAX's Python tracer (on by default) would time every
                    # Python call of the host path it is meant to observe
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(w["trace_dir"],
                                             profiler_options=options)
                w["annotation"] = TraceAnnotation("bench.window")
                w["annotation"].__enter__()
                w["compiles0"] = compiles.count
                w["cpu0"], w["load0"] = os.times(), os.getloadavg()[0]
                tap.armed = True
                w["start"] = time.monotonic()
            try:
                body()
            finally:
                w["end"] = w.get("end") or time.monotonic()

        # warm-up: the cell's traffic on a short extent, same shapes
        warm_json = os.path.join(work, "warmup.json")
        t = time.monotonic()
        with TraceAnnotation("bench.warmup"):
            tap.armed = True
            rc, _ = run_program(base + config["warmup_flags"]
                                + ["--jsonfile", warm_json, path],
                                lambda c, p, body: body())
            tap.armed = False
        log(f"warm-up: rc {rc}, {tap.calls} calls, "
            f"{compiles.count} compiles so far, "
            f"{time.monotonic() - t:.3f} s")
        tap.reset(args.seed)
        if args.fault:
            import faults
            faults.plant(args.fault)
            log(f"FAULT PLANTED under the window: {args.fault}")
        w["limit"] = traffic["trace_seconds"] if traced else args.seconds
        w["trace_dir"] = os.path.join(work, "trace")
        win_json = os.path.join(work, "window.json")
        if rc == 0:
            # one phase, looped by the program until the time limit
            try:
                rc, run.results = run_program(
                    base + ["--infloop", "--timelimit", str(w["limit"]),
                                     "--jsonfile", win_json, path], on_phase)
            finally:
                tap.armed = False
                if "start" in w:
                    w["compiles"] = compiles.count - w["compiles0"]
                    w["cpu1"], w["load1"] = os.times(), os.getloadavg()[0]
                    w["annotation"].__exit__(None, None, None)
                    if traced:
                        jax.profiler.stop_trace()
        run.record = read_record(win_json, record_phase)
        if "start" in w:
            run.setup_s = w["start"] - T0
            run.window_s = w["end"] - w["start"]
        peak = memory_peak(devices[:n_chips])
        log(f"window: rc {rc}, {run.window_s:.6f} s, "
            f"{(run.record or {}).get('Phases', 0)} phase(s), "
            f"{w.get('compiles', 'no')} compiles inside it, setup "
            f"{run.setup_s:.6f} s, native engine ops "
            f"{(run.record or {}).get('TpuStreamFusedOps', 0)}")
        if "start" in w:
            log(f"tap: {tap.spent_s:.6f} s of its own work in the window, "
                f"summed over threads: "
                f"{100 * tap.spent_s / max(run.window_s, 1e-9):.4f}% of it, "
                f"{tap.calls} calls")
        if "cpu1" in w:
            busy = sum(w["cpu1"][:2]) - sum(w["cpu0"][:2])
            log(f"host: this process used {busy:.3f} CPU s in the window "
                f"({busy / max(run.window_s, 1e-9):.3f} cores); load "
                f"average {w['load0']:.2f} -> {w['load1']:.2f}; "
                f"{os.cpu_count()} cores")

        checks, bad = check.compare(tap, run.record, salt, traffic["verify"],
                                    rc)
        tap.drop_samples()
        device = {"platform": platform, "kind": kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        breakdown = None
        if traced and "start" in w:
            import trace_reduce
            run.trace = trace_reduce.reduce(trace_reduce.load(
                _xplane(w["trace_dir"])))
            if run.trace.n_devices:
                # every device op in the window, the tap's copies with it
                # (in a --tpudirect cell they are the only ones: the
                # import is a DMA); the per-layer metrics leave them out
                device["busy_s"] = run.trace.busy_all_s
                device["window_s"] = run.trace.window_s
                breakdown = run.trace.breakdown()
        metrics = {}
        units = {m["name"]: m["unit"]
                 for m in manifest["end_to_end"] + manifest["per_layer"]}
        for name, read in readers.items():
            value = read(run) if "start" in w else None
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
            else:
                log(f"metric {name}: nothing to read")
        correct = check.passed(checks)
        result = {"correct": correct, "attempted": tap.calls, "failed": bad,
                  "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = checks
        for name, c in checks.items():
            print(f"bench: check {name} = {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0 if correct else 1
    finally:
        tap.remove()
        if fd >= 0:
            os.close(fd)
        shutil.rmtree(work, ignore_errors=True)


def _xplane(trace_dir: str) -> str:
    import glob
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


if __name__ == "__main__":
    sys.exit(main())
