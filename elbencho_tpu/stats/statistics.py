"""Statistics: live stats + first-done/last-done phase results + CSV/JSON.

Reference: source/Statistics.{h,cpp} (3.5 kLoC) — live render paths
(fullscreen/single-line/no-console, :182-1249), live CSV/JSON streams
(:3000-3292), and the two-column result model: **first done** (the moment
the fastest worker finished = stonewall snapshots of everyone at that
instant) vs **last done** (all workers finished)
(docs/result-columns-explanation.md; generatePhaseResults :1695).

TPU extension: per-chip HBM ingest bandwidth rows when ``--tpuids`` staging
is active (BASELINE.json north-star metric).
"""

from __future__ import annotations

import json
import os
import sys
import time

from ..phases import BenchMode, BenchPhase, phase_entry_type, phase_name
from ..tpu.device import PATH_AUDIT_COUNTERS, sum_path_audit_counters
from .latency_histogram import LatencyHistogram


def sum_tpu_transfer_totals(workers) -> "tuple[int, int, int]":
    """(bytes, dma_usec, dispatch_usec) summed over a worker list — the
    live-wire and /metrics aggregation of the dispatch-vs-DMA split
    (one definition so the two exports can never diverge)."""
    tpu_bytes = tpu_usec = tpu_dispatch_usec = 0
    for w in workers:
        tpu_bytes += w.tpu_transfer_bytes
        tpu_usec += w.tpu_transfer_usec
        tpu_dispatch_usec += w.tpu_dispatch_usec
    return tpu_bytes, tpu_usec, tpu_dispatch_usec


def fold_fingerprints(workers) -> dict:
    """Phase-wide on-device data fingerprint, folded over the workers
    (and their TPU contexts) like tpu.device.fold_fingerprint."""
    s = x = n = 0
    for w in workers:
        for src in (w, getattr(w, "_tpu", None)):
            if src is not None and getattr(src, "tpu_fp_blocks", 0):
                s = (s + src.tpu_fp_sum) & 0xFFFFFFFF
                x ^= src.tpu_fp_xor
                n += src.tpu_fp_blocks
    return {"Sum": s, "Xor": x, "Blocks": n}


def _device_stamp(workers) -> dict:
    """The TPU device stamp of the first worker whose phase used one."""
    return next((w.tpu_device_stamp for w in workers
                 if getattr(w, "tpu_device_stamp", None)), {})


def merge_live_latency_histos(workers) -> "tuple[LatencyHistogram, ...]":
    """(io, entries) histograms merged over a worker list for the live
    telemetry views (rwmix reads fold into io — a live scrape wants one
    op-latency distribution, not the result table's split)."""
    io_histo = LatencyHistogram()
    ent_histo = LatencyHistogram()
    for w in workers:
        io_histo.merge(w.iops_latency_histo)
        io_histo.merge(w.iops_latency_histo_rwmix)
        ent_histo.merge(w.entries_latency_histo)
    return io_histo, ent_histo


def _fmt_elapsed_usec(usec: int) -> str:
    secs = usec / 1_000_000
    if secs >= 60:
        m, s = divmod(secs, 60)
        return f"{int(m)}m{s:.1f}s"
    if secs >= 1:
        return f"{secs:.3f}s"
    return f"{usec / 1000:.2f}ms"


class PhaseResults:
    """Aggregated first-done/last-done numbers for one finished phase."""

    def __init__(self):
        self.phase: BenchPhase = BenchPhase.IDLE
        self.phase_name = ""
        self.entry_type = "files"
        self.first_done_usec = 0
        self.last_done_usec = 0
        self.stonewall = {}     # first-done totals dict
        self.final = {}         # last-done totals dict
        self.stonewall_rwmix = {}
        self.final_rwmix = {}
        self.iops_histo = LatencyHistogram()
        self.entries_histo = LatencyHistogram()
        self.iops_histo_rwmix = LatencyHistogram()
        # storage submit -> block resident in HBM (JSON OpResidentLatHisto)
        self.op_resident_histo = LatencyHistogram()
        self.cpu_stonewall = 0.0
        self.cpu_last_done = 0.0
        self.elapsed_usec_vec: "list[int]" = []
        self.tpu_bytes = 0
        self.tpu_usec = 0           # DMA wall time (submit -> ready)
        self.tpu_dispatch_usec = 0  # host-side submit cost of the pipeline
        self.tpu_per_chip: "dict[int, tuple[int, int]]" = {}
        # --tpudirect H2D/D2H path audit, keyed by wire/JSON name
        # (schema: tpu.device.PATH_AUDIT_COUNTERS)
        self.tpu_path_counters: "dict[str, int]" = {
            key: 0 for _attr, key, _ingest in PATH_AUDIT_COUNTERS}
        self.tpu_device: dict = {}  # tpu.device.device_stamp of the phase
        self.tpu_fingerprint: dict = {}  # statistics.fold_fingerprints
        self.num_workers = 0
        # per-service-host CPU util at phase end (telemetry satellite;
        # JSON-only result key HostCPUUtil)
        self.host_cpu_util: "dict[str, float]" = {}
        # --svctolerant: hosts lost mid-run (results exclude them)
        self.degraded_hosts: "list[str]" = []
        # control-plane audit (fault_tolerance.CONTROL_AUDIT_COUNTERS)
        self.control_counters: "dict[str, int]" = {}
        # --flightrec: the run doctor's verdict for this phase
        # (telemetry/doctor.py; JSON-only "Analysis" block)
        self.analysis: "dict | None" = None
        # --slowops: the fleet-merged tail forensics block
        # (telemetry/slowops.py; JSON-only "TailAnalysis" block)
        self.tail_analysis: "dict | None" = None


class Statistics:
    def __init__(self, cfg, worker_manager):
        self.cfg = cfg
        self.manager = worker_manager
        self._header_printed = False
        self._live_csv_fh = None
        self._live_json_fh = None
        self._live_rows = 0      # data rows written to the live streams
        self._live_started = 0.0
        self._fullscreen_active = False
        # --telemetry: BenchTelemetry bound by the coordinator; the live
        # loop samples it at its cadence so scrapes between intervals
        # read a warm snapshot
        self.telemetry = None
        # --flightrec: FlightRecorder bound by the coordinator; None =
        # recording off, every hook is a single `is None` test
        self.flightrec = None
        # dedicated CPU meter for /status replies (primed, rate-limited;
        # see SampledCPUUtil for why the shared phase meter is off limits)
        from .cpu_util import SampledCPUUtil
        self._status_cpu = SampledCPUUtil()

    # ------------------------------------------------------------------
    # live statistics (reference: printLiveStats, Statistics.cpp:1337)
    # ------------------------------------------------------------------

    def _sum_live_ops(self) -> "tuple[int, int, int, int]":
        entries = num_bytes = iops = 0
        for w in self.manager.workers:
            entries += (w.live_ops.num_entries_done
                        + w.live_ops_rwmix_read.num_entries_done)
            num_bytes += (w.live_ops.num_bytes_done
                          + w.live_ops_rwmix_read.num_bytes_done)
            iops += (w.live_ops.num_iops_done
                     + w.live_ops_rwmix_read.num_iops_done)
        done = self.manager.shared.num_workers_done \
            + self.manager.shared.num_workers_done_with_error
        return entries, num_bytes, iops, done

    def live_stats_loop(self, phase: BenchPhase,
                        phase_start: "float | None" = None) -> None:
        """Poll worker counters until the phase completes; render according
        to the configured live mode. Runs on the coordinator thread."""
        cfg = self.cfg
        interval = max(cfg.live_stats_interval_ms, 50) / 1000.0
        use_line = not cfg.disable_live_stats
        is_tty = sys.stdout.isatty()
        if self.flightrec is not None:
            self.flightrec.phase_start(
                phase_name(phase, cfg.bench_mode == BenchMode.S3))
        self._live_started = time.monotonic()
        last_bytes = last_iops = 0
        last_t = self._live_started
        next_render = self._live_started + interval
        while not self.manager.all_workers_done():
            time.sleep(0.02)  # fine-grained poll so short phases don't stall
            if phase_start is not None:
                self.manager.check_phase_time_limit(phase_start)
            self.manager.check_fail_fast_interrupt()
            if time.monotonic() < next_render:
                continue
            next_render = time.monotonic() + interval
            entries, num_bytes, iops, done = self._sum_live_ops()
            now = time.monotonic()
            dt = max(now - last_t, 1e-9)
            bps = (num_bytes - last_bytes) / dt
            ops_per_s = (iops - last_iops) / dt
            last_bytes, last_iops, last_t = num_bytes, iops, now
            elapsed = int(now - self._live_started)
            # live CSV/JSON files are written even when console live stats
            # are off (--nolive / service mode)
            self._write_live_files(phase, entries, num_bytes, iops, elapsed)
            if self.telemetry is not None:
                self.telemetry.sample()  # live-stats-cadence sampling
            if self.flightrec is not None:
                self.flightrec.sample(self)  # same cadence, same counters
            if not use_line:
                continue
            unit, div = ("MB", 1000 ** 2) if cfg.use_base10_units \
                else ("MiB", 1 << 20)
            fullscreen = (is_tty and not cfg.use_single_line_live_stats
                          and not cfg.single_line_live_stats_no_erase)
            if fullscreen:
                self._render_fullscreen(phase, elapsed, bps / div,
                                        ops_per_s, unit, div, done)
                continue
            line = (f"{phase_name(phase, cfg.bench_mode == BenchMode.S3)}: "
                    f"{elapsed}s; {bps / div:,.0f} {unit}/s; "
                    f"{ops_per_s:,.0f} IOPS; {entries} entries; "
                    f"{num_bytes / div:,.0f} {unit} total; "
                    f"{done}/{len(self.manager.workers)} done")
            if cfg.show_cpu_util:
                line += f"; CPU {self.manager.shared.cpu_util.update():.0f}%"
            if is_tty and not cfg.single_line_live_stats_no_erase:
                print("\r\x1b[2K" + line, end="", flush=True)
            else:
                print(line, flush=True)
        if use_line and is_tty:
            if self._fullscreen_active:
                print("\x1b[2J\x1b[H", end="", flush=True)
                self._fullscreen_active = False
                self._exit_fullscreen_keys()
            elif not cfg.single_line_live_stats_no_erase:
                print("\r\x1b[2K", end="", flush=True)

    #: fallback worker rows per fullscreen frame when no tty size known
    _FS_ROWS = 40
    #: header/footer lines around the worker table
    _FS_CHROME_LINES = 6
    #: per-frame snapshot of the terminal-derived row count
    _fs_rows = _FS_ROWS

    def _term_fs_rows(self) -> int:
        """Worker rows that fit the current terminal (reference:
        TerminalTk console size; read once per frame to follow resizes)."""
        import shutil
        lines = shutil.get_terminal_size(fallback=(80, 0)).lines
        return max(lines - self._FS_CHROME_LINES, 4) if lines \
            else self._FS_ROWS

    def _render_fullscreen(self, phase, elapsed, rate, ops_per_s, unit,
                           div, done) -> None:
        """Fullscreen per-worker live table (ANSI, dependency-free analogue
        of the reference's ftxui screen, Statistics.cpp:716-1249). Arrow /
        PgUp / PgDn / Home keys scroll the worker rows."""
        cfg = self.cfg
        shared = self.manager.shared
        workers = self.manager.workers
        self._fs_rows = self._term_fs_rows()  # one consistent size/frame
        self._poll_fullscreen_keys(len(workers))
        scroll = getattr(self, "_fs_scroll", 0)
        lines = []
        s3 = cfg.bench_mode == BenchMode.S3
        lines.append(
            f"Phase: {phase_name(phase, s3)}   Elapsed: {elapsed}s   "
            f"Done: {done}/{len(workers)}")
        lines.append(f"Total: {rate:,.0f} {unit}/s  {ops_per_s:,.0f} IOPS"
                     + (f"  CPU: {shared.cpu_util.update():.0f}%"
                        if cfg.show_cpu_util else ""))
        if cfg.show_svc_ping and cfg.hosts:
            # --svcping: control-plane /status RTT per service
            pings = [f"{w.host}={w.last_ping_usec / 1000:.1f}ms"
                     for w in workers if hasattr(w, "last_ping_usec")]
            if pings:
                lines.append("Service ping: " + "  ".join(pings))
        lines.append("")
        lines.append(f"{'Rank':>6} {'Entries':>10} {unit:>10} {'IOPS':>12} "
                     f"{'State':>8}")
        window = workers[scroll:scroll + self._fs_rows]
        for w in window:
            state = "done" if w.phase_finished else "run"
            lines.append(
                f"{w.rank:>6} {w.live_ops.num_entries_done:>10} "
                f"{w.live_ops.num_bytes_done / div:>10,.0f} "
                f"{w.live_ops.num_iops_done:>12,} {state:>8}")
        hidden = len(workers) - len(window)
        if hidden > 0:
            lines.append(f"... showing {scroll}..{scroll + len(window) - 1} "
                         f"of {len(workers)} workers (arrow keys / PgUp / "
                         f"PgDn scroll)")
        # footer: running tail percentiles (bucket-walk over the live
        # histograms the wire already carries — tails are visible
        # MID-RUN, not only post-mortem; slow-op forensics satellite).
        # Entry-granular phases (mkdirs/stat/delete) move no blocks, so
        # their entry latencies ARE the per-op distribution shown.
        # gate on BUCKET content, not num_values: master-mode sum-only
        # mirrors (no --telemetry bucket view on the wire) carry counts
        # and sums with empty buckets — percentile() would answer 0
        io_histo, ent_histo = merge_live_latency_histos(workers)
        tail_histo, tail_label = ((io_histo, "IO")
                                  if any(io_histo.buckets)
                                  else (ent_histo, "Entry"))
        if any(tail_histo.buckets):
            lines.append(
                f"{tail_label} lat us: "
                f"p50={tail_histo.percentile(50):,.0f}  "
                f"p99={tail_histo.percentile(99):,.0f}  "
                f"p99.9={tail_histo.percentile(99.9):,.0f}  "
                f"max={tail_histo.max_micro:,}")
        # per-service-host CPU util sampled from the /status polls
        # (telemetry satellite; RemoteWorker.cpu_util_pct live ingest)
        host_cpus = [(w.host, w.cpu_util_pct) for w in workers
                     if getattr(w, "host", None) is not None
                     and hasattr(w, "cpu_util_pct")]
        if host_cpus:
            lines.append("Host CPU%: " + "  ".join(
                f"{h}={p:.0f}" for h, p in host_cpus))
        frame = "\x1b[H" + "\x1b[2K" + "\n\x1b[2K".join(lines) + "\x1b[J"
        if not self._fullscreen_active:
            print("\x1b[2J", end="")
            self._fullscreen_active = True
            self._enter_fullscreen_keys()
        print(frame, end="", flush=True)

    # -- fullscreen keyboard navigation (reference: ftxui arrow-key rows) ----

    def _enter_fullscreen_keys(self) -> None:
        """Put stdin into cbreak so arrow keys arrive without Enter; restored
        by close()/_exit_fullscreen_keys."""
        self._fs_scroll = 0
        self._fs_old_termios = None
        try:
            import termios
            import tty
            if sys.stdin.isatty():
                fd = sys.stdin.fileno()
                self._fs_old_termios = (fd, termios.tcgetattr(fd))
                tty.setcbreak(fd)
        except (ImportError, OSError):
            pass

    def _exit_fullscreen_keys(self) -> None:
        old = getattr(self, "_fs_old_termios", None)
        if old is not None:
            try:
                import termios
                termios.tcsetattr(old[0], termios.TCSADRAIN, old[1])
            except (ImportError, OSError):
                pass
            self._fs_old_termios = None

    def _poll_fullscreen_keys(self, num_workers: int) -> None:
        """Non-blocking read of pending key escape sequences; updates the
        scroll offset window over the per-worker rows."""
        if getattr(self, "_fs_old_termios", None) is None:
            return
        import select
        scroll = getattr(self, "_fs_scroll", 0)
        max_scroll = max(num_workers - self._fs_rows, 0)
        buf = b""
        try:
            while select.select([sys.stdin], [], [], 0)[0]:
                chunk = os.read(sys.stdin.fileno(), 64)
                if not chunk:
                    break
                buf += chunk
        except OSError:
            pass
        # parse sequence-by-sequence: auto-repeat delivers several escape
        # sequences per read, so the buffer must be consumed incrementally
        i = 0
        while i < len(buf):
            seq, step = self._match_key_seq(buf[i:])
            i += step
            if seq in ("up", "k"):
                scroll -= 1
            elif seq in ("down", "j"):
                scroll += 1
            elif seq in ("pgup", "\x02"):
                scroll -= self._fs_rows
            elif seq in ("pgdn", "\x06"):
                scroll += self._fs_rows
            elif seq in ("home", "g"):
                scroll = 0
            elif seq in ("end", "G"):
                scroll = max_scroll
        self._fs_scroll = min(max(scroll, 0), max_scroll)

    _ESC_SEQS = {b"\x1b[A": "up", b"\x1b[B": "down", b"\x1b[5~": "pgup",
                 b"\x1b[6~": "pgdn", b"\x1b[H": "home", b"\x1b[F": "end"}

    @classmethod
    def _match_key_seq(cls, buf: bytes) -> "tuple[str, int]":
        """Match one key at the front of buf -> (name, bytes_consumed)."""
        if buf[:1] == b"\x1b":
            for seq, name in cls._ESC_SEQS.items():
                if buf.startswith(seq):
                    return name, len(seq)
            return "", 1  # unknown escape: skip the ESC byte
        return chr(buf[0]), 1

    def _write_live_files(self, phase, entries, num_bytes, iops,
                          elapsed) -> None:
        cfg = self.cfg
        if cfg.live_csv_file_path:
            if self._live_csv_fh is None:
                self._live_csv_fh = (sys.stdout
                                     if cfg.live_csv_file_path == "stdout"
                                     else open(cfg.live_csv_file_path, "a"))
                print("ISODate,Label,Phase,Seconds,Entries,Bytes,IOPS",
                      file=self._live_csv_fh, flush=True)
            print(f"{time.strftime('%Y-%m-%dT%H:%M:%S')},"
                  f"{cfg.bench_label},{phase_name(phase)},{elapsed},"
                  f"{entries},{num_bytes},{iops}",
                  file=self._live_csv_fh, flush=True)
        if cfg.live_json_file_path:
            if self._live_json_fh is None:
                self._live_json_fh = (sys.stdout
                                      if cfg.live_json_file_path == "stdout"
                                      else open(cfg.live_json_file_path, "a"))
            rec = {"ISODate": time.strftime("%Y-%m-%dT%H:%M:%S"),
                   "Label": cfg.bench_label, "Phase": phase_name(phase),
                   "Seconds": elapsed, "Entries": entries,
                   "Bytes": num_bytes, "IOPS": iops}
            if cfg.live_json_extended or cfg.live_csv_extended:
                rec["Workers"] = [
                    {"Rank": w.rank, **w.live_ops.as_dict()}
                    for w in self.manager.workers]
            print(json.dumps(rec), file=self._live_json_fh, flush=True)
        if cfg.live_csv_file_path or cfg.live_json_file_path:
            self._live_rows += 1
        self._flush_live_files()

    def _flush_live_files(self) -> None:
        """Push the live streams all the way to stable storage every
        interval: flush() alone leaves rows in the OS page cache, where a
        tailer/scraper on another host (network filesystem) only sees
        them on buffer-boundary writeback — fsync is best effort (stdout
        and pipes have no fsync)."""
        for fh in (self._live_csv_fh, self._live_json_fh):
            if fh is None:
                continue
            try:
                fh.flush()
                os.fsync(fh.fileno())
            except (OSError, ValueError):
                pass

    # ------------------------------------------------------------------
    # phase results (reference: printPhaseResults :1619 /
    # generatePhaseResults :1695)
    # ------------------------------------------------------------------

    def generate_phase_results(self, phase: BenchPhase) -> PhaseResults:
        cfg = self.cfg
        res = PhaseResults()
        res.phase = phase
        s3 = cfg.bench_mode == BenchMode.S3
        res.phase_name = phase_name(phase, s3)
        res.entry_type = phase_entry_type(phase, s3)
        res.cpu_stonewall = self.manager.shared.cpu_util_stonewall
        res.cpu_last_done = self.manager.shared.cpu_util_last_done

        stonewall_totals = {"entries": 0, "bytes": 0, "iops": 0}
        final_totals = {"entries": 0, "bytes": 0, "iops": 0}
        stonewall_rwmix = {"entries": 0, "bytes": 0, "iops": 0}
        final_rwmix = {"entries": 0, "bytes": 0, "iops": 0}
        workers = [w for w in self.manager.workers if w.got_phase_work]
        res.num_workers = len(workers)
        for w in workers:
            res.elapsed_usec_vec.extend(w.elapsed_usec_vec)
            stonewall_totals["entries"] += w.stonewall_ops.num_entries_done
            stonewall_totals["bytes"] += w.stonewall_ops.num_bytes_done
            stonewall_totals["iops"] += w.stonewall_ops.num_iops_done
            final_totals["entries"] += w.live_ops.num_entries_done
            final_totals["bytes"] += w.live_ops.num_bytes_done
            final_totals["iops"] += w.live_ops.num_iops_done
            stonewall_rwmix["entries"] += \
                w.stonewall_ops_rwmix_read.num_entries_done
            stonewall_rwmix["bytes"] += \
                w.stonewall_ops_rwmix_read.num_bytes_done
            stonewall_rwmix["iops"] += \
                w.stonewall_ops_rwmix_read.num_iops_done
            final_rwmix["entries"] += w.live_ops_rwmix_read.num_entries_done
            final_rwmix["bytes"] += w.live_ops_rwmix_read.num_bytes_done
            final_rwmix["iops"] += w.live_ops_rwmix_read.num_iops_done
            res.iops_histo.merge(w.iops_latency_histo)
            res.entries_histo.merge(w.entries_latency_histo)
            res.iops_histo_rwmix.merge(w.iops_latency_histo_rwmix)
            res.op_resident_histo.merge(w.op_resident_histo)
            res.tpu_bytes += w.tpu_transfer_bytes
            res.tpu_usec += w.tpu_transfer_usec
            res.tpu_dispatch_usec += w.tpu_dispatch_usec
            if getattr(w, "_tpu", None) is not None:
                chip = w._tpu.chip_id
                b, u = res.tpu_per_chip.get(chip, (0, 0))
                res.tpu_per_chip[chip] = (b + w.tpu_transfer_bytes,
                                          u + w.tpu_transfer_usec)
            else:  # RemoteWorker: per-chip map ingested from service JSON
                for chip, (b2, u2) in getattr(w, "tpu_per_chip",
                                              {}).items():
                    b, u = res.tpu_per_chip.get(chip, (0, 0))
                    res.tpu_per_chip[chip] = (b + b2, u + u2)
        res.tpu_path_counters = sum_path_audit_counters(workers)
        res.tpu_device = _device_stamp(self.manager.workers)
        res.tpu_fingerprint = fold_fingerprints(workers)
        # fleet straggler attribution (fleet tracing / run doctor): the
        # per-host finish spread behind the phase barrier, computed here
        # — after the barrier, before the control-counter merge — so
        # StragglerSkewUsec (MAX merge = the straggler's lag behind the
        # FIRST finisher) and BarrierWaitUSec (sum = worker-time the
        # fleet idled waiting for the LAST finisher) ride the existing
        # CONTROL_AUDIT_COUNTERS plumbing into JSON//metrics/flightrec
        self._compute_barrier_skew()
        # per-host CPU util (last /status ingest of each RemoteWorker)
        res.host_cpu_util = {
            w.host: round(getattr(w, "cpu_util_pct", 0.0), 1)
            for w in self.manager.workers
            if getattr(w, "host", None) is not None}
        from ..service.fault_tolerance import merge_control_audit_counters
        res.control_counters = merge_control_audit_counters(
            self.manager.workers)
        res.degraded_hosts = list(self.manager.shared.degraded_hosts)
        stonewall_elapsed = [w.stonewall_elapsed_usec for w in workers
                             if w.stonewall_taken]
        res.first_done_usec = min(res.elapsed_usec_vec, default=0)
        if stonewall_elapsed:
            res.first_done_usec = min(stonewall_elapsed)
        res.last_done_usec = max(res.elapsed_usec_vec, default=0)
        res.stonewall = stonewall_totals
        res.final = final_totals
        res.stonewall_rwmix = stonewall_rwmix
        res.final_rwmix = final_rwmix
        if getattr(cfg, "slow_ops_k", 0):
            res.tail_analysis = self._build_tail_analysis(res)
        return res

    def _build_tail_analysis(self, res: PhaseResults) -> "dict | None":
        """Fleet-merge every worker's slow-op capture (local recorders
        directly, RemoteWorkers' shipped snapshots) into the phase's
        TailAnalysis block. The exact percentiles come from the merged
        io histogram (rwmix reads folded in, like the live view); the
        captures add the WHO/WHERE attribution."""
        from ..telemetry.slowops import build_tail_analysis
        parts: "list[tuple[str, dict | None]]" = []
        for w in self.manager.workers:
            if getattr(w, "_slowops", None) is not None:
                parts.append(("", w._slowops.snapshot()))
            elif getattr(w, "host", None) is not None:
                parts.append((w.host, getattr(w, "slowops_shipped",
                                              None)))
        if not any(((snap or {}).get("OpsSeen", 0)
                    or (snap or {}).get("Records"))
                   for _host, snap in parts):
            return None  # nothing captured (e.g. a pure mkdir phase)
        io_histo = LatencyHistogram()
        io_histo.merge(res.iops_histo)
        io_histo.merge(res.iops_histo_rwmix)
        if not io_histo.num_values:
            # entry-granular phase (stat/delete): the entry latencies
            # ARE the per-op distribution the captures attribute
            io_histo.merge(res.entries_histo)
        if not io_histo.num_values:
            return None  # no latencies recorded this phase (e.g. mkdir)
        return build_tail_analysis(
            parts, io_histo, getattr(self.cfg, "slow_ops_k", 0),
            getattr(self.cfg, "op_sample_rate", 1.0))

    def _compute_barrier_skew(self) -> None:
        """Per-host barrier decomposition from the finish stamps each
        RemoteWorker takes when its host's /benchresult lands: skew =
        lag behind the first host to finish, barrier wait = idle wait
        for the last. Meaningful only with >= 2 finishing hosts; local
        runs and single-host fleets keep both counters at zero."""
        finishes = [(w, w.phase_done_monotonic)
                    for w in self.manager.workers
                    if getattr(w, "host", None) is not None
                    and getattr(w, "phase_done_monotonic", 0.0)]
        if len(finishes) < 2:
            return
        first = min(t for _w, t in finishes)
        last = max(t for _w, t in finishes)
        for w, t in finishes:
            w.straggler_skew_usec = int((t - first) * 1e6)
            w.barrier_wait_usec = int((last - t) * 1e6)

    def per_host_barrier_stats(self) -> "dict[str, dict]":
        """{host: {...}} snapshot of the barrier decomposition plus each
        host's clock-offset estimate — the flight recorder stores it in
        phase_end rows and the doctor names the straggler from it."""
        out: "dict[str, dict]" = {}
        for w in self.manager.workers:
            host = getattr(w, "host", None)
            if host is None:
                continue
            entry = {
                "StragglerSkewUsec": getattr(w, "straggler_skew_usec", 0),
                "BarrierWaitUSec": getattr(w, "barrier_wait_usec", 0),
                # how coarse the master's done observation was for this
                # host (poll-interval / stream-tick quantization) — the
                # doctor's straggler floor scales with it so sampling
                # noise can't fabricate a verdict
                "ObsQuantumUsec": getattr(w, "done_obs_quantum_usec", 0),
            }
            estimate = getattr(w, "_host_clock_estimate", None)
            if estimate is not None:
                off, unc, known = estimate()
                if known:
                    entry["ClockOffsetUsec"] = off
                    entry["ClockUncUsec"] = unc
            out[host] = entry
        return out

    # -- rendering ----------------------------------------------------------

    def print_phase_results_table_header(self) -> None:
        line = (f"{'OPERATION':<12}{'RESULT TYPE':<20}"
                f"{'FIRST DONE':>14}{'LAST DONE':>14}")
        print(line)
        print(f"{'=' * 11:<12}{'=' * 18:<20}{'=' * 12:>14}{'=' * 12:>14}")
        self._print_to_res_file(line)

    def print_phase_results(self, phase: BenchPhase) -> PhaseResults:
        res = self.generate_phase_results(phase)
        if self.flightrec is not None:
            # run doctor: final sample + phase_end row + bottleneck
            # verdict — computed AFTER the barrier (RemoteWorkers have
            # ingested their final /benchresult, so totals are exact)
            # and BEFORE rendering so the text/JSON outputs carry it
            res.analysis = self.flightrec.finish_phase(self, res)
        self._render_result_rows(res)
        if self.cfg.csv_file_path:
            self._write_csv(res)
        if self.cfg.json_file_path:
            self._write_json(res)
        return res

    def _row(self, op: str, rtype: str, first, last) -> str:
        return f"{op:<12}{rtype + ' :':<20}{first:>14}{last:>14}"

    def _render_result_rows(self, res: PhaseResults) -> None:
        cfg = self.cfg
        unit, div = ("MB", 1000 ** 2) if cfg.use_base10_units \
            else ("MiB", 1 << 20)
        rows = []
        first_s = res.first_done_usec / 1e6 or 1e-9
        last_s = res.last_done_usec / 1e6 or 1e-9
        op = res.phase_name
        rows.append(self._row(op, "Elapsed time",
                              _fmt_elapsed_usec(res.first_done_usec),
                              _fmt_elapsed_usec(res.last_done_usec)))
        if res.final["entries"]:
            rows.append(self._row(
                "", f"{res.entry_type}/s",
                f"{res.stonewall['entries'] / first_s:,.0f}",
                f"{res.final['entries'] / last_s:,.0f}"))
            rows.append(self._row(
                "", f"{res.entry_type} total",
                f"{res.stonewall['entries']}", f"{res.final['entries']}"))
        if res.final["iops"]:
            rows.append(self._row(
                "", "IOPS", f"{res.stonewall['iops'] / first_s:,.0f}",
                f"{res.final['iops'] / last_s:,.0f}"))
        if res.final["bytes"]:
            rows.append(self._row(
                "", f"Throughput {unit}/s",
                f"{res.stonewall['bytes'] / first_s / div:,.0f}",
                f"{res.final['bytes'] / last_s / div:,.0f}"))
            rows.append(self._row(
                "", f"Total {unit}",
                f"{res.stonewall['bytes'] / div:,.0f}",
                f"{res.final['bytes'] / div:,.0f}"))
        if res.final_rwmix["iops"]:
            rows.append(self._row(
                "", "Read IOPS (rwmix)",
                f"{res.stonewall_rwmix['iops'] / first_s:,.0f}",
                f"{res.final_rwmix['iops'] / last_s:,.0f}"))
            rows.append(self._row(
                "", f"Read {unit}/s (rwmix)",
                f"{res.stonewall_rwmix['bytes'] / first_s / div:,.0f}",
                f"{res.final_rwmix['bytes'] / last_s / div:,.0f}"))
        if res.tpu_bytes:
            # HBM ingest rows: the TPU-native headline metric
            rows.append(self._row(
                "", f"HBM ingest {unit}/s", "-",
                f"{res.tpu_bytes / last_s / div:,.0f}"))
            for chip, (b, u) in sorted(res.tpu_per_chip.items()):
                rows.append(self._row(
                    "", f"  chip {chip} {unit}/s", "-",
                    f"{b / last_s / div:,.0f}"))
            # dispatch-vs-DMA split (TransferPipeline accounting): the
            # host-side submit overhead --tpubudget bounds vs the DMA
            # wall time the pipeline overlaps
            tpu_ops = sum(res.tpu_path_counters.get(k, 0) for k in (
                "TpuH2dDirectOps", "TpuH2dStagedOps",
                "TpuD2hDirectOps", "TpuD2hStagedOps"))
            if tpu_ops and (res.tpu_dispatch_usec or res.tpu_usec):
                rows.append(self._row(
                    "", "HBM dispatch us/op", "-",
                    f"{res.tpu_dispatch_usec / tpu_ops:,.1f}"))
                rows.append(self._row(
                    "", "HBM DMA us/op", "-",
                    f"{res.tpu_usec / tpu_ops:,.1f}"))
        if cfg.show_cpu_util:
            rows.append(self._row("", "CPU util %",
                                  f"{res.cpu_stonewall:.0f}",
                                  f"{res.cpu_last_done:.0f}"))
        if cfg.show_latency and res.iops_histo.num_values:
            h = res.iops_histo
            rows.append(f"{'':12}{'IO latency us :':<20}"
                        f"min={h.min_micro} avg={h.avg_micro:.0f} "
                        f"max={h.max_micro}")
        if cfg.show_latency and res.entries_histo.num_values:
            h = res.entries_histo
            rows.append(f"{'':12}{'Ent latency us :':<20}"
                        f"min={h.min_micro} avg={h.avg_micro:.0f} "
                        f"max={h.max_micro}")
        if cfg.show_latency_percentiles and res.iops_histo.num_values:
            nines = res.iops_histo.percentiles_nines(
                cfg.num_latency_percentile_9s)
            txt = " ".join(f"{k}={v:.0f}" for k, v in nines.items())
            rows.append(f"{'':12}{'IO lat pcts :':<20}{txt}")
        if cfg.show_latency_histogram and res.iops_histo.num_values:
            rows.append(f"{'':12}IO lat histogram : "
                        f"{res.iops_histo.histogram_str()}")
        if cfg.show_all_elapsed:
            txt = ", ".join(_fmt_elapsed_usec(u)
                            for u in sorted(res.elapsed_usec_vec))
            rows.append(f"{'':12}Worker elapsed   : {txt}")
        if cfg.show_svc_elapsed and cfg.hosts:
            # per-service last-done elapsed (--svcelapsed)
            parts = []
            for w in self.manager.workers:
                if getattr(w, "host", None) and w.elapsed_usec_vec:
                    parts.append(f"{w.host}="
                                 f"{_fmt_elapsed_usec(max(w.elapsed_usec_vec))}")
            if parts:
                rows.append(f"{'':12}Service elapsed  : {', '.join(parts)}")
        if res.tail_analysis is not None:
            # --slowops tail forensics: how heavy the tail is and who
            # owns it (full detail in the JSON TailAnalysis block)
            tail = res.tail_analysis
            hosts = tail["Owners"]["ByHost"]
            owner = max(hosts, key=hosts.get) if hosts else ""
            line = (f"p50={tail['P50Usec']} p99={tail['P99Usec']} "
                    f"p99.9={tail['P999Usec']} max={tail['MaxUsec']} "
                    f"({tail['TailRatio']:g}x p50")
            if owner:
                line += (f"; {hosts[owner]:.0%} of captured tail time "
                         f"on {owner}")
            rows.append(f"{'':12}{'Tail lat us :':<20}{line})")
        if res.analysis is not None:
            # --flightrec run doctor: where the wall time went + the
            # named bottleneck, right under the numbers it explains
            ana = res.analysis
            busy = "  ".join(
                f"{name}={pct:g}%" for name, pct in ana["StagePct"].items()
                if pct)
            if busy:
                rows.append(f"{'':12}{'Stage time % :':<20}{busy}")
            first = f" ({ana['Evidence'][0]})" if ana["Evidence"] else ""
            rows.append(f"{'':12}{'Bottleneck :':<20}"
                        f"{ana['Verdict']}{first}")
        if res.degraded_hosts:
            # loud, unmissable: these numbers exclude lost hosts and must
            # never be read as a clean run (--svctolerant)
            rows.append(
                f"{'':12}{'DEGRADED hosts :':<20}"
                f"{', '.join(res.degraded_hosts)} "
                f"(lost mid-run; results cover survivors only)")
        if not cfg.ignore_0usec_errors and res.num_workers \
                and res.first_done_usec == 0:
            # reference semantics (Statistics.cpp:2186): warn when the
            # fastest worker finished in 0 microseconds — the whole phase
            # was too short to measure
            rows.append(
                f"{'':12}WARNING: phase completed in 0 microseconds; "
                f"results may be bogus (too little work?). --no0usecerr "
                f"silences this.")
        for row in rows:
            print(row)
            self._print_to_res_file(row)

    def _print_to_res_file(self, line: str) -> None:
        if self.cfg.res_file_path:
            with open(self.cfg.res_file_path, "a") as f:
                f.write(line + "\n")

    # -- CSV / JSON output (reference: Statistics.cpp:2485-2783 + csv-docs) --

    def _result_record(self, res: PhaseResults) -> dict:
        first_s = res.first_done_usec / 1e6 or 1e-9
        last_s = res.last_done_usec / 1e6 or 1e-9
        rec = {
            "ISODate": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "Label": self.cfg.bench_label,
            "Phase": res.phase_name,
            "EntryType": res.entry_type,
            "NumWorkers": res.num_workers,
            "ElapsedUSecFirst": res.first_done_usec,
            "ElapsedUSecLast": res.last_done_usec,
            "EntriesFirst": res.stonewall["entries"],
            "EntriesLast": res.final["entries"],
            "EntriesPerSecFirst": round(res.stonewall["entries"] / first_s, 2),
            "EntriesPerSecLast": round(res.final["entries"] / last_s, 2),
            "IOPSFirst": round(res.stonewall["iops"] / first_s, 2),
            "IOPSLast": round(res.final["iops"] / last_s, 2),
            "BytesFirst": res.stonewall["bytes"],
            "BytesLast": res.final["bytes"],
            "MiBPerSecFirst": round(
                res.stonewall["bytes"] / first_s / (1 << 20), 2),
            "MiBPerSecLast": round(
                res.final["bytes"] / last_s / (1 << 20), 2),
            "CPUUtilStoneWall": round(res.cpu_stonewall, 1),
            "CPUUtil": round(res.cpu_last_done, 1),
            "IOLatUSecMin": res.iops_histo.min_micro,
            "IOLatUSecAvg": round(res.iops_histo.avg_micro, 1),
            "IOLatUSecMax": res.iops_histo.max_micro,
            "IOLatUSecP99": round(res.iops_histo.percentile(99), 1),
            "EntLatUSecMin": res.entries_histo.min_micro,
            "EntLatUSecAvg": round(res.entries_histo.avg_micro, 1),
            "EntLatUSecMax": res.entries_histo.max_micro,
            "TpuHbmBytes": res.tpu_bytes,
            "TpuHbmMiBPerSec": round(
                res.tpu_bytes / last_s / (1 << 20), 2) if res.tpu_bytes else 0,
            # dispatch-vs-DMA split of the transfer pipeline: host-side
            # submit cost vs per-transfer DMA wall time (overlapping
            # windows — divide bytes by PHASE time for bandwidth)
            "TpuDispatchUSec": res.tpu_dispatch_usec,
            "TpuTransferUSec": res.tpu_usec,
            "TpuPerChip": {str(k): {"Bytes": b, "USec": u}
                           for k, (b, u) in res.tpu_per_chip.items()},
            # H2D/D2H path audit, keyed by PATH_AUDIT_COUNTERS
            **res.tpu_path_counters,
            # --svctolerant: hosts lost mid-run (count in CSV; the host
            # list + control-plane audit counters are JSON-only)
            "NumHostsDegraded": len(res.degraded_hosts),
            "DegradedHosts": list(res.degraded_hosts),
            **res.control_counters,
            # telemetry (JSON-only): per-host CPU view, /metrics scrapes
            # served this run, spans recorded by the --tracefile ring
            "HostCPUUtil": dict(res.host_cpu_util),
            "TelemetryScrapes": (self.telemetry.registry.scrapes
                                 if self.telemetry is not None else 0),
            "TraceEvents": (self.manager.shared.tracer.num_recorded
                            if self.manager.shared.tracer is not None
                            else 0),
            # spans the --tracefile ring LOST (sampled out by
            # --tracesample + overwritten before a write) — so a sampled
            # trace is honest about what it dropped (JSON-only)
            "TraceDropped": (self.manager.shared.tracer.num_dropped
                             if self.manager.shared.tracer is not None
                             else 0),
            # crash-safe run lifecycle (JSON-only): number of finished
            # phases a --resume run skipped per its journal — non-zero
            # marks every record of a resumed run so the summarize tool
            # can banner it (0 = fresh run)
            "Resumed": getattr(self.cfg, "resumed_skipped_phases", 0),
        }
        # unconditional so CSV rows keep a fixed column count
        rec["RWMixReadIOPSLast"] = round(res.final_rwmix["iops"] / last_s, 2)
        rec["RWMixReadMiBPerSecLast"] = round(
            res.final_rwmix["bytes"] / last_s / (1 << 20), 2)
        # scenario identity (--scenario; docs/scenarios.md): every record
        # of a scenario run carries the scenario + step tag so the whole
        # JSON/CSV/summarize/chart pipeline works unchanged; EpochRateMiBs
        # is the per-epoch data rate on epoch-type legs (0 elsewhere) —
        # the coldwarm/epochs comparison column. Appended, never
        # reordered (make check-schema).
        rec["Scenario"] = getattr(self.cfg, "scenario", "")
        rec["ScenarioStep"] = getattr(self.cfg, "scenario_step_label", "")
        rec["EpochRateMiBs"] = rec["MiBPerSecLast"] \
            if getattr(self.cfg, "scenario_epoch", 0) else 0
        # the epoch number itself is JSON-only (popped for CSV)
        rec["ScenarioEpoch"] = getattr(self.cfg, "scenario_epoch", 0)
        # --autotune (JSON-only): whether this phase ran at a tuned
        # point and the search's measured gain over the defaults — the
        # summarize tool's Tuned/Gain% columns; the full Autotune block
        # (trajectory, chosen config, doctor diff) is its own terminal
        # AUTOTUNE record (docs/autotuning.md)
        tuned = getattr(self.cfg, "autotune_applied", None)
        rec["AutotuneTuned"] = bool(tuned)
        rec["AutotuneGainPct"] = tuned["gain_pct"] if tuned else 0
        # the device the TPU path ran on (empty/0 for phases without it),
        # so no TPU number is read without the platform it came from
        rec.update({"TpuPlatform": "", "TpuDeviceKind": "",
                    "TpuDeviceCount": 0, **res.tpu_device})
        return rec

    #: fixed result columns of the CSV schema (docs/result-columns.md);
    #: TpuPerChip and the TpuH2d*/TpuD2h* path-audit counters are JSON-only
    CSV_RESULT_COLUMNS = (
        "ISODate", "Label", "Phase", "EntryType", "NumWorkers",
        "ElapsedUSecFirst", "ElapsedUSecLast", "EntriesFirst", "EntriesLast",
        "EntriesPerSecFirst", "EntriesPerSecLast", "IOPSFirst", "IOPSLast",
        "BytesFirst", "BytesLast", "MiBPerSecFirst", "MiBPerSecLast",
        "CPUUtilStoneWall", "CPUUtil", "IOLatUSecMin", "IOLatUSecAvg",
        "IOLatUSecMax", "IOLatUSecP99", "EntLatUSecMin", "EntLatUSecAvg",
        "EntLatUSecMax", "TpuHbmBytes", "TpuHbmMiBPerSec",
        "TpuDispatchUSec", "TpuTransferUSec", "NumHostsDegraded",
        "RWMixReadIOPSLast", "RWMixReadMiBPerSecLast",
        "Scenario", "ScenarioStep", "EpochRateMiBs",
        "TpuPlatform", "TpuDeviceKind", "TpuDeviceCount")

    @classmethod
    def check_csv_file_compatibility(cls, cfg) -> None:
        """Appending to an existing CSV requires a matching column count
        (reference: checkCSVFileCompatibility, ProgArgs.cpp:4303 — catches
        files written by a different version/config before any phase
        runs). Raises ValueError on mismatch."""
        path = cfg.csv_file_path
        if not path or not os.path.exists(path) \
                or os.path.getsize(path) == 0:
            return
        with open(path) as f:
            first_line = f.readline().rstrip("\n")
        found = first_line.count(",")
        labels = 0 if cfg.no_csv_labels else len(cfg.config_labels())
        expected = len(cls.CSV_RESULT_COLUMNS) + labels - 1
        if found == expected:
            return
        if getattr(cfg, "_defaulted_csv", False):
            # implicit default file (user never asked for CSV): rotate to
            # a fresh suffixed name instead of failing the run — a new
            # release adding flags would otherwise break every run until
            # the stale default file is deleted by hand
            base, ext = os.path.splitext(path)
            for n in range(2, 1000):
                candidate = f"{base}_{n}{ext}"
                if not os.path.exists(candidate) \
                        or os.path.getsize(candidate) == 0 \
                        or cls._csv_columns_match(candidate, expected):
                    from ..toolkits.logger import log
                    log(0, f"NOTE: default CSV result file {path} has an "
                           f"incompatible column count (old version?); "
                           f"writing to {candidate} instead")
                    cfg.csv_file_path = candidate
                    return
        raise ValueError(
            f"CSV output file exists and the column compatibility "
            f"check failed (was it written by a different version or "
            f"with different label settings?). Found commas: {found}; "
            f"expected: {expected}; file: {path}")

    @staticmethod
    def _csv_columns_match(path: str, expected: int) -> bool:
        with open(path) as f:
            return f.readline().rstrip("\n").count(",") == expected

    def _write_csv(self, res: PhaseResults) -> None:
        from ..service.fault_tolerance import CONTROL_AUDIT_COUNTERS
        rec = self._result_record(res)
        rec.pop("TpuPerChip")
        for _attr, key, _ingest in PATH_AUDIT_COUNTERS:  # JSON-only keys
            rec.pop(key)
        rec.pop("DegradedHosts")  # list is JSON-only; the count stays CSV
        for _attr, key, _mode in CONTROL_AUDIT_COUNTERS:  # JSON-only keys
            rec.pop(key)
        for key in ("HostCPUUtil", "TelemetryScrapes", "TraceEvents",
                    "TraceDropped", "Resumed", "ScenarioEpoch",
                    "AutotuneTuned", "AutotuneGainPct"):
            rec.pop(key)  # telemetry + lifecycle keys are JSON-only
        assert tuple(rec) == self.CSV_RESULT_COLUMNS, "CSV schema drift"
        labels = {} if self.cfg.no_csv_labels else self.cfg.config_labels()
        path = self.cfg.csv_file_path
        new_file = not os.path.exists(path) or os.path.getsize(path) == 0
        with open(path, "a") as f:
            if new_file:
                f.write(",".join(list(rec) + list(labels)) + "\n")
            # comma-escape EVERY value (Label is user-supplied) so the
            # fixed column count the compatibility check relies on holds
            vals = [str(v).replace(",", ";")
                    for v in list(rec.values()) + list(labels.values())]
            f.write(",".join(vals) + "\n")

    def _write_json(self, res: PhaseResults) -> None:
        """JSONL: one JSON object per phase result (consumed by
        tools/elbencho-tpu-summarize-json)."""
        rec = self._result_record(res)
        rec["Config"] = self.cfg.config_labels()
        rec["ElapsedUSecList"] = res.elapsed_usec_vec
        rec["IOLatHisto"] = res.iops_histo.to_dict()
        rec["EntLatHisto"] = res.entries_histo.to_dict()
        if res.op_resident_histo.num_values:
            # per-op storage submit -> resident in HBM (TPU block loops);
            # absent when no op of the phase landed in HBM
            rec["OpResidentLatHisto"] = res.op_resident_histo.to_dict()
        if res.analysis is not None:
            # --flightrec run doctor: stage decomposition + bottleneck
            # verdict (docs/result-columns.md Analysis block); absent
            # without --flightrec so the off path stays byte-identical
            rec["Analysis"] = res.analysis
        if res.tpu_fingerprint.get("Blocks"):
            # on-device verify fingerprint (--tpuverify, --tpuslice):
            # absent when no block was fingerprinted on the device
            rec["TpuFingerprint"] = res.tpu_fingerprint
        if res.tail_analysis is not None:
            # --slowops tail forensics (docs/result-columns.md
            # TailAnalysis block); absent without --slowops so the off
            # path stays byte-identical
            rec["TailAnalysis"] = res.tail_analysis
        with open(self.cfg.json_file_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    # -- service protocol views (used by HTTP /status & /benchresult) --------

    def get_live_stats_dict(self) -> dict:
        entries, num_bytes, iops, done = self._sum_live_ops()
        shared = self.manager.shared
        workers = self.manager.workers
        lat_sums = {"NumIOLatUSec": 0, "SumIOLatUSec": 0,
                    "NumEntLatUSec": 0, "SumEntLatUSec": 0}
        for w in workers:
            # rwmix reads fold into the io sums, matching the live
            # bucket view (merge_live_latency_histos) — the master's
            # flight-recorder IoBusyUSec must not undercount rwmix runs
            lat_sums["NumIOLatUSec"] += w.iops_latency_histo.num_values \
                + w.iops_latency_histo_rwmix.num_values
            lat_sums["SumIOLatUSec"] += w.iops_latency_histo.sum_micro \
                + w.iops_latency_histo_rwmix.sum_micro
            lat_sums["NumEntLatUSec"] += w.entries_latency_histo.num_values
            lat_sums["SumEntLatUSec"] += w.entries_latency_histo.sum_micro
        tpu_bytes, tpu_usec, tpu_dispatch_usec = \
            sum_tpu_transfer_totals(workers)
        stats = {
            "BenchID": shared.bench_uuid,
            "PhaseCode": int(shared.current_phase),
            "PhaseName": phase_name(shared.current_phase),
            "NumWorkersDone": shared.num_workers_done,
            "NumWorkersDoneWithError": shared.num_workers_done_with_error,
            "NumEntriesDone": entries,
            "NumBytesDone": num_bytes,
            "NumIOPSDone": iops,
            "CPUUtil": round(self._status_cpu.sample(), 1),
            **lat_sums,
            # live telemetry harvest: the master mirrors these into its
            # RemoteWorker's ingest attributes on every /status poll so
            # its /metrics fleet view aggregates mid-run (same wire keys
            # and merge rules as the phase-end /benchresult payload)
            "TpuHbmBytes": tpu_bytes,
            "TpuHbmUSec": tpu_usec,
            "TpuHbmDispatchUSec": tpu_dispatch_usec,
            **sum_path_audit_counters(workers),
        }
        if getattr(self.cfg, "telemetry", False):
            # bucket-level latency for the master's /metrics histogram;
            # only shipped when the master asked for telemetry (the flag
            # travels the config wire) to keep the common poll lean
            io_histo, ent_histo = merge_live_latency_histos(workers)
            stats["IOLatHisto"] = io_histo.to_dict()
            stats["EntLatHisto"] = ent_histo.to_dict()
        return stats

    def get_bench_result_dict(self) -> dict:
        """Final per-phase result for the master (per-worker elapsed vec +
        mergeable histograms, reference: getBenchResultAsPropertyTreeForService
        Statistics.cpp:2784)."""
        shared = self.manager.shared
        elapsed_vec = []
        tpu_bytes = tpu_usec = tpu_dispatch_usec = 0
        tpu_per_chip = {}
        for w in self.manager.workers:
            if w.got_phase_work:
                elapsed_vec.extend(w.elapsed_usec_vec)
            tpu_bytes += w.tpu_transfer_bytes
            tpu_usec += w.tpu_transfer_usec
            tpu_dispatch_usec += w.tpu_dispatch_usec
            if getattr(w, "_tpu", None) is not None:
                chip = w._tpu.chip_id
                b, u = tpu_per_chip.get(chip, (0, 0))
                tpu_per_chip[chip] = (b + w.tpu_transfer_bytes,
                                      u + w.tpu_transfer_usec)
            else:  # RemoteWorker: per-chip map ingested from service JSON
                for chip, (b2, u2) in getattr(w, "tpu_per_chip",
                                              {}).items():
                    b, u = tpu_per_chip.get(chip, (0, 0))
                    tpu_per_chip[chip] = (b + b2, u + u2)
        iops_histo = LatencyHistogram()
        entries_histo = LatencyHistogram()
        iops_histo_rwmix = LatencyHistogram()
        op_resident_histo = LatencyHistogram()
        final = {"entries": 0, "bytes": 0, "iops": 0}
        stonewall = {"entries": 0, "bytes": 0, "iops": 0}
        final_rwmix = {"entries": 0, "bytes": 0, "iops": 0}
        stonewall_rwmix = {"entries": 0, "bytes": 0, "iops": 0}
        for w in self.manager.workers:
            if not w.got_phase_work:
                continue
            iops_histo.merge(w.iops_latency_histo)
            entries_histo.merge(w.entries_latency_histo)
            iops_histo_rwmix.merge(w.iops_latency_histo_rwmix)
            op_resident_histo.merge(w.op_resident_histo)
            final["entries"] += w.live_ops.num_entries_done
            final["bytes"] += w.live_ops.num_bytes_done
            final["iops"] += w.live_ops.num_iops_done
            stonewall["entries"] += w.stonewall_ops.num_entries_done
            stonewall["bytes"] += w.stonewall_ops.num_bytes_done
            stonewall["iops"] += w.stonewall_ops.num_iops_done
            final_rwmix["entries"] += w.live_ops_rwmix_read.num_entries_done
            final_rwmix["bytes"] += w.live_ops_rwmix_read.num_bytes_done
            final_rwmix["iops"] += w.live_ops_rwmix_read.num_iops_done
            stonewall_rwmix["bytes"] += \
                w.stonewall_ops_rwmix_read.num_bytes_done
            stonewall_rwmix["iops"] += \
                w.stonewall_ops_rwmix_read.num_iops_done
            stonewall_rwmix["entries"] += \
                w.stonewall_ops_rwmix_read.num_entries_done
        stonewall_elapsed = [w.stonewall_elapsed_usec
                             for w in self.manager.workers
                             if w.got_phase_work and w.stonewall_taken]
        return {
            "BenchID": shared.bench_uuid,
            "PhaseCode": int(shared.current_phase),
            "NumWorkersDone": shared.num_workers_done,
            "NumWorkersDoneWithError": shared.num_workers_done_with_error,
            "ElapsedUSecList": elapsed_vec,
            "StoneWallUSec": min(stonewall_elapsed, default=0),
            "Final": final,
            "StoneWall": stonewall,
            "FinalRWMixRead": final_rwmix,
            "StoneWallRWMixRead": stonewall_rwmix,
            "IOLatHisto": iops_histo.to_dict(),
            "EntLatHisto": entries_histo.to_dict(),
            "IOLatHistoRWMixRead": iops_histo_rwmix.to_dict(),
            "OpResidentLatHisto": op_resident_histo.to_dict(),
            "CPUUtilStoneWall": round(shared.cpu_util_stonewall, 1),
            "CPUUtil": round(shared.cpu_util_last_done, 1),
            "TpuHbmBytes": tpu_bytes,
            "TpuHbmUSec": tpu_usec,
            # host-side submit cost of the transfer pipeline, shipped
            # separately so the master's dispatch-vs-DMA split survives
            # distribution (RemoteWorker ingests it as tpu_dispatch_usec)
            "TpuHbmDispatchUSec": tpu_dispatch_usec,
            # per-chip breakdown travels the wire so the master's merged
            # record can attribute bytes to chips across services
            "TpuPerChip": {str(k): {"Bytes": b, "USec": u}
                           for k, (b, u) in tpu_per_chip.items()},
            # the device the service's TPU path ran on, and the phase's
            # on-device data fingerprint
            "TpuDevice": _device_stamp(self.manager.workers),
            "TpuFingerprint": fold_fingerprints(self.manager.workers),
            # H2D/D2H path audit, keyed by PATH_AUDIT_COUNTERS
            **sum_path_audit_counters(self.manager.workers),
        }

    def close(self) -> None:
        self._exit_fullscreen_keys()
        for fh in (self._live_csv_fh, self._live_json_fh):
            if fh is not None and fh is not sys.stdout:
                fh.close()
        self._live_csv_fh = self._live_json_fh = None

    def abort_cleanup(self) -> None:
        """Master-side abort hygiene: close the live streams and remove
        live-stats files this run opened but never wrote a data row to —
        a back-to-back run must not inherit a stale header-only artifact
        (run lifecycle satellite, docs/fault-tolerance.md)."""
        self.close()
        if self._live_rows:
            return  # real data: keep the files
        for path in (self.cfg.live_csv_file_path,
                     self.cfg.live_json_file_path):
            if not path or path == "stdout":
                continue
            try:
                # the streams open in append mode: an earlier run's rows
                # may live in the same file — remove only empty or
                # header-only leftovers
                with open(path) as f:
                    head = f.readline()
                    more = f.readline()
                if more or (head and not head.startswith("ISODate")):
                    continue
                os.unlink(path)
            except OSError:
                pass
