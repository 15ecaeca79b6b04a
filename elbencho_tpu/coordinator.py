"""Coordinator: role dispatch and benchmark phase ordering.

Reference: source/Coordinator.{h,cpp} — main() :32 (service vs master vs
local role), runBenchmarks() :299 with the ordered phase table :311-334
(creates before deletes), sync/dropcaches interleave after every phase,
host rotation :384, SIGINT graceful shutdown :420-442, synchronized start
time :150-159, service-ready wait :165.
"""

from __future__ import annotations

import os
import signal
import sys
import time

from .phases import BenchPhase
from .stats.statistics import Statistics
from .toolkits import logger
from .workers.manager import WorkerManager
from .workers.shared import WorkerException


class Coordinator:
    def __init__(self, cfg):
        self.cfg = cfg
        self.manager = WorkerManager(cfg)
        self.statistics = Statistics(cfg, self.manager)
        self._interrupted = False
        self._profile_seq = 0
        self._profile_warned_hosts = False
        self._old_handlers = []  # (signum, previous handler) pairs
        self._telemetry = None   # BenchTelemetry when --telemetry
        self._exporter = None    # its /metrics HTTP server
        self._flightrec = None   # FlightRecorder when --flightrec
        self._journal = None     # RunJournal when --journal
        self._resume_plan = None  # ResumePlan when --resume
        self._scenario_plan = None  # ScenarioPlan when --scenario
        self._last_phase_results = None  # PhaseResults of the last phase

    # ------------------------------------------------------------------

    def main(self) -> int:
        from .toolkits.signals import register_fault_handlers
        register_fault_handlers()  # reference: SignalTk fault trace
        cfg = self.cfg
        if cfg.run_as_service:
            from .service.http_service import HTTPService
            return HTTPService(cfg).start()
        if cfg.quit_services or cfg.interrupt_services:
            from .service.remote_worker import send_interrupt_to_hosts
            # --svcfanout: interrupt/quit walk the aggregation tree, so
            # tearing down a large fleet is O(fanout) requests here too
            send_interrupt_to_hosts(cfg.hosts, cfg.service_port,
                                    quit=cfg.quit_services,
                                    fanout=cfg.svc_fanout)
            return 0
        if cfg.standby_str:
            return self._run_standby()
        return self._run_master_or_local()

    def _run_standby(self) -> int:
        """--standby HOST:PORT warm standby (docs/fault-tolerance.md
        "Master failover"): observe one sentinel service's /status — an
        observer poll carries no bench UUID, so it can never renew the
        primary's lease — and auto-take-over (--resume --adopt) the
        moment the sentinel reports AwaitingAdoption. The watch ends
        cleanly when the shared journal gains its run_complete record:
        the primary finished without needing us."""
        from .journal import REC_RUN_COMPLETE, read_journal
        from .service import protocol as proto
        from .service.remote_worker import ServiceClient
        from .workers.shared import WorkerRemoteException
        cfg = self.cfg
        client = ServiceClient(cfg.standby_str, cfg.service_port)
        logger.log(0, f"STANDBY: watching {client.hostname}:{client.port} "
                      f"for a master lease expiry (--standby); journal: "
                      f"{cfg.journal_file_path}")
        poll_secs = max(cfg.svc_update_interval_ms, 500) / 1000.0
        try:
            while True:
                try:
                    if os.path.exists(cfg.journal_file_path) and any(
                            r.get("rec") == REC_RUN_COMPLETE
                            for r in read_journal(cfg.journal_file_path)):
                        logger.log(0, "STANDBY: journal shows "
                                      "run_complete — primary finished; "
                                      "standing down")
                        return 0
                except Exception:  # noqa: BLE001 - torn journal mid-append
                    pass
                try:
                    status, stats = client.get_json(proto.PATH_STATUS,
                                                    timeout=5)
                except WorkerRemoteException:
                    status, stats = 0, {}
                if status == 200 \
                        and stats.get(proto.KEY_AWAITING_ADOPTION):
                    logger.log(0, "STANDBY: sentinel host is awaiting "
                                  "adoption — taking over the fleet "
                                  "(--resume --adopt)")
                    client.close()
                    # shed the standby role BEFORE assuming mastership:
                    # later config re-checks (the manager's path-type
                    # pass) must see a plain --resume --adopt master,
                    # not the standby+resume combination check() forbids
                    cfg.standby_str = ""
                    cfg.resume_run = True
                    cfg.adopt_run = True
                    return self._run_master_or_local()
                time.sleep(poll_secs)
        except KeyboardInterrupt:
            logger.log(0, "STANDBY: interrupted; standing down")
            return 3
        finally:
            client.close()

    def _run_master_or_local(self) -> int:
        from .config.args import ConfigError
        cfg = self.cfg
        self._install_signal_handler()
        try:
            try:
                if cfg.scenario:
                    # expand ONCE; the same plan object drives the
                    # journal's run_start, the resume filter and the
                    # step loop (journal.config_fingerprint re-expands
                    # deterministically for the hash)
                    from .scenarios import expand_scenario
                    self._scenario_plan = expand_scenario(cfg)
                if self._setup_journal():
                    return 0  # --resume against a complete journal
            except (ConfigError, OSError) as err:
                # OSError: unwritable/unreadable --journal path — fail
                # before any phase runs, not mid-run
                logger.log_error(str(err))
                return 1
            self._start_telemetry()
            self._start_flightrec()
            if cfg.hosts:
                from .service.remote_worker import wait_for_services_ready
                wait_for_services_ready(cfg.hosts, cfg.service_port,
                                        cfg.svc_wait_secs)
            self._wait_for_sync_start()
            self._arm_takeover_credentials()
            self.manager.prepare_threads()
            self._note_takeover()
            if cfg.autotune_secs:
                # closed-loop autotuning (docs/autotuning.md): probe ->
                # doctor verdict -> hill-climb, then apply the tuned
                # point (fleet rebuilt) so the REAL phases below run it
                self._run_autotune()
                if cfg.journal_file_path:
                    # journal the run NOW, against the TUNED effective
                    # config (see _setup_journal's autotune deferral);
                    # the unjournaled probes above left no records. The
                    # tuned profile is already on disk, so a refused/
                    # unwritable journal aborts without wasting the
                    # spent tune budget.
                    try:
                        from .journal import RunJournal
                        self._journal = RunJournal(
                            cfg.journal_file_path, cfg)
                        self._journal.start_fresh(cfg.enabled_phases(),
                                                  cfg.iterations)
                    except (ConfigError, OSError) as err:
                        logger.log_error(str(err))
                        return 1
            self.run_benchmarks()
            if self._journal is not None:
                self._journal_write(self._journal.run_complete)
            return 0
        except WorkerException as err:
            logger.log_error(f"Aborting due to worker error: {err}")
            self.manager.interrupt_and_notify_workers()
            self._abort_hygiene()
            return 1
        except KeyboardInterrupt:
            logger.log_error("Interrupted. Shutting down workers...")
            self.manager.interrupt_and_notify_workers()
            self._abort_hygiene()
            return 3
        finally:
            # exporter first: the abort path must free --telemetryport
            # before the (up to 30s/thread) worker join, so back-to-back
            # runs on the same port bind cleanly (stop() is idempotent)
            if self._exporter is not None:
                self._exporter.stop()
                self._exporter = None
            try:
                self.manager.join_all_threads()
            except Exception:  # noqa: BLE001 - teardown must not mask errors
                pass
            # fleet tracing: merge master + collected per-host traces
            # into the one clock-aligned timeline (after the join wrote
            # the master's final span ring); an aborted run merges
            # whatever was collected before the abort
            self._merge_fleet_trace()
            if self._flightrec is not None:
                # flush the ring so even an aborted run leaves a
                # loadable (torn-tail-tolerated) recording
                self._flightrec.close()
            self.statistics.close()
            if self._journal is not None:
                self._journal.close()
            self._restore_signal_handler()

    def _setup_journal(self) -> bool:
        """--journal/--resume wiring. Returns True when --resume finds a
        terminal run_complete record (nothing left to run). Raises
        ConfigError on a missing journal or a config-fingerprint
        mismatch — resuming a different workload would silently mix
        incompatible datasets."""
        cfg = self.cfg
        if not cfg.journal_file_path:
            return False
        if cfg.autotune_secs:
            # a fresh tuned run journals AFTER the tuner applied its
            # knobs (--resume next to --autotune is rejected at config
            # time), so the fingerprint describes the config the phases
            # actually ran — which makes `--resume -c PROFILE` the
            # working recovery path instead of a guaranteed mismatch
            return False
        from .journal import RunJournal, load_resume_plan
        if cfg.resume_run:
            plan = load_resume_plan(cfg.journal_file_path, cfg)
            if plan.run_complete:
                logger.log(0, "RESUME: journal already has run_complete — "
                              "nothing left to resume")
                return True
            self._resume_plan = plan
            # surfaced in the JSON result records ("Resumed") and the
            # summarize tool's RESUMED banner
            cfg.resumed_skipped_phases = plan.num_finished
            if plan.partial_dataset:
                # the interrupted run died inside a write/delete phase:
                # the re-run's delete/overwrite work must tolerate the
                # partial dataset it left on disk (PR 5 latch)
                self.manager.shared.mark_partial_dataset()
            logger.log(0, f"RESUME: {plan.num_finished} finished phase(s) "
                          f"will be skipped per {cfg.journal_file_path}; "
                          f"the first incomplete phase re-runs from "
                          f"scratch")
        self._journal = RunJournal(cfg.journal_file_path, cfg)
        if cfg.resume_run:
            self._journal.resume(self._resume_plan.num_finished)
            plan = self._resume_plan
            if plan.takeover_token:
                # the run was armed for failover: every resume (plain or
                # --adopt) keeps presenting the journaled token so the
                # fleet's adoption grace re-arms for the continuation
                cfg.takeover_token = plan.takeover_token
                cfg.journal_fingerprint = self._journal.fingerprint
            if cfg.adopt_run:
                if not plan.takeover_token:
                    logger.log_error(
                        "ADOPT: journal has no fleet record (the run was "
                        "not armed with --svcadoptsecs) — falling back "
                        "to a plain --resume; the fleet is re-prepared")
                    cfg.adopt_run = False
                else:
                    inf = plan.inflight
                    cfg.adopt_bench_uuid = \
                        inf.get("bench_uuid", "") if inf else ""
                    what = (f"in-flight phase {inf.get('name', '?')} "
                            f"(iteration {inf.get('iteration', 0)}) is "
                            f"adopted mid-run" if inf
                            else "no phase was in flight")
                    logger.log(0, f"ADOPT: taking over "
                                  f"{len(plan.fleet_hosts) or len(cfg.hosts)}"
                                  f" host(s) from the journaled fleet; "
                                  f"{what}")
        else:
            # a fresh run refuses to append after an incomplete journal
            # (that restart point is someone's resume) and truncates a
            # complete one — mixing runs in one file would poison every
            # later --resume replay
            if self._scenario_plan is not None:
                self._journal.start_fresh(
                    self._scenario_plan.phases(), cfg.iterations,
                    scenario=self._scenario_plan.describe())
            else:
                self._journal.start_fresh(cfg.enabled_phases(),
                                          cfg.iterations)
        return False

    def _arm_takeover_credentials(self) -> None:
        """Master failover arming (docs/fault-tolerance.md): a journaled
        fleet run with --svcadoptsecs > 0 mints a takeover token, ships
        it (plus the journal fingerprint) on /preparephase, and journals
        the fleet topology — the three things a successor master needs
        to /adopt the hosts after this process dies."""
        cfg = self.cfg
        if getattr(cfg, "takeover_token", ""):
            return  # --resume: credentials already came from the journal
        if self._journal is None or not cfg.hosts \
                or cfg.svc_adopt_secs <= 0:
            return
        cfg.takeover_token = os.urandom(16).hex()
        cfg.journal_fingerprint = self._journal.fingerprint
        self._journal_write(self._journal.fleet, cfg.hosts,
                            cfg.takeover_token)

    def _adopt_inflight(self) -> "dict | None":
        """The dead master's in-flight phase record, when this run is a
        --resume --adopt takeover (None otherwise)."""
        if getattr(self.cfg, "adopt_run", False) \
                and self._resume_plan is not None:
            return self._resume_plan.inflight
        return None

    def _note_takeover(self) -> None:
        """Post-handshake bookkeeping of a --resume --adopt takeover:
        prepare_threads ran the /adopt handshake per host instead of
        /preparephase; journal the takeover record and mark the event as
        a trace span. The MasterTakeovers counter itself lands via the
        adopted phase's audit counters (RemoteWorker)."""
        if not getattr(self.cfg, "adopt_run", False):
            return
        adopted = sum(1 for w in self.manager.workers
                      if getattr(w, "_took_over", False))
        if not adopted:
            return
        inf = self._adopt_inflight()
        if self._journal is not None:
            self._journal_write(self._journal.takeover, adopted, inf)
        tracer = self.manager.shared.tracer
        if tracer is not None:
            t0 = tracer.now_ns()
            tracer.record("takeover", "phase", t0, 1,
                          AdoptedHosts=adopted)
        logger.log(0, f"TAKEOVER: adopted {adopted} host(s); "
                      + (f"the in-flight phase continues under the "
                         f"journaled bench UUID "
                         f"{inf.get('bench_uuid', '')[:8]}..." if inf
                         else "no phase was in flight"))

    def _merge_fleet_trace(self) -> None:
        """--tracefleet: fold the master trace + the per-host rings
        collected at /benchresult into ONE clock-aligned Chrome trace
        (<tracefile base>.fleet<ext>) with a skew report. Best effort:
        a failed merge is LOUD but never fails the run — the per-host
        inputs stay on disk for tools/elbencho-tpu-trace."""
        from .telemetry.tracefleet import (FleetTraceError,
                                           fleet_trace_enabled,
                                           merge_fleet_trace,
                                           skew_report_text)
        cfg = self.cfg
        if not fleet_trace_enabled(cfg) \
                or self.manager.shared.tracer is None \
                or not os.path.exists(cfg.trace_file_path):
            return
        try:
            doc = merge_fleet_trace(cfg.trace_file_path)
        except (OSError, FleetTraceError) as err:
            logger.log_error(f"fleet trace merge failed: {err} "
                             f"(per-host inputs kept; retry with "
                             f"tools/elbencho-tpu-trace)")
            return
        logger.log(0, f"fleet trace: {doc['outPath']}")
        for line in skew_report_text(doc):
            logger.log(1, line)

    def _abort_hygiene(self) -> None:
        """Master-side abort: close the telemetry exporter socket NOW and
        drop live-stats files that never saw a data row, so back-to-back
        runs on the same port/paths never inherit stale state."""
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None
        self.statistics.abort_cleanup()

    def _start_telemetry(self) -> None:
        """--telemetry: standalone Prometheus /metrics endpoint for
        local/master runs (service mode piggybacks onto the control
        server's route table instead, service/http_service.py). The
        provider indirection follows manager/statistics across
        --rotatehosts rebuilds."""
        cfg = self.cfg
        if not cfg.telemetry:
            return
        from .telemetry.exporter import TelemetryExporter
        from .telemetry.registry import BenchTelemetry
        telemetry = BenchTelemetry(
            cfg, lambda: (self.statistics, self.manager),
            role="master" if cfg.hosts else "local")
        self._telemetry = telemetry
        self.statistics.telemetry = telemetry
        exporter = TelemetryExporter(telemetry, cfg.telemetry_port)
        try:
            exporter.start()
        except OSError as err:
            raise WorkerException(
                f"--telemetry: cannot bind --telemetryport "
                f"{cfg.telemetry_port}: {err}") from err
        self._exporter = exporter

    def _start_flightrec(self) -> None:
        """--flightrec: arm the flight recorder (telemetry/flightrec.py).
        An unwritable recording path fails BEFORE any phase runs, like
        the journal — a run asked to explain itself must not silently
        lose its recording."""
        from .telemetry.flightrec import make_flightrec
        try:
            self._flightrec = make_flightrec(self.cfg)
        except OSError as err:
            raise WorkerException(
                f"--flightrec: cannot open "
                f"{self.cfg.flightrec_file_path}: {err}") from err
        self.statistics.flightrec = self._flightrec

    def _wait_for_sync_start(self) -> None:
        """--start: cross-host synchronized start (reference: :150-159;
        accepts "HH:MM[:SS]" UTC or a unix timestamp)."""
        spec = self.cfg.start_time_utc
        if not spec:
            return
        if ":" in spec:
            parts = [int(x) for x in spec.split(":")]
            now = time.gmtime()
            target_secs = parts[0] * 3600 + parts[1] * 60 + \
                (parts[2] if len(parts) > 2 else 0)
            now_secs = now.tm_hour * 3600 + now.tm_min * 60 + now.tm_sec
            delay = target_secs - now_secs
            if delay < 0:
                raise WorkerException("--start time is in the past")
        else:
            delay = float(spec) - time.time()
            if delay < 0:
                raise WorkerException("--start time is in the past")
        logger.log(0, f"Waiting {delay:.0f}s for synchronized start...")
        time.sleep(delay)

    # ------------------------------------------------------------------

    def run_benchmarks(self) -> None:
        """Iterations x ordered phases with sync/dropcaches interleave
        (reference: runBenchmarks, Coordinator.cpp:299-376). With
        --journal every table phase is bracketed by start/finish records;
        with --resume, phases the journal proves finished are skipped —
        host rotation still applies to skipped slots so the re-run phases
        see the same rank assignments the original run would have."""
        cfg = self.cfg
        if self._scenario_plan is not None:
            self._run_scenario()
            return
        phases = cfg.enabled_phases()
        from .phases import phase_name
        for iteration in range(cfg.iterations):
            if cfg.iterations > 1:
                logger.log(0, f"[Starting iteration {iteration + 1} of "
                              f"{cfg.iterations}...]")
            self.statistics.print_phase_results_table_header()
            self._run_sync_and_drop_caches()
            for idx, phase in enumerate(phases):
                skipped = self._resume_plan is not None \
                    and (iteration, idx) in self._resume_plan.finished
                if skipped:
                    logger.log(0, f"RESUME: skipping finished phase "
                                  f"{phase_name(phase)} "
                                  f"(iteration {iteration + 1})")
                else:
                    self._run_journaled_phase(iteration, idx, phase)
                    self._run_sync_and_drop_caches()
                if idx < len(phases) - 1:
                    if cfg.next_phase_delay_secs and not skipped:
                        time.sleep(cfg.next_phase_delay_secs)
                    self._rotate_hosts()

    # ------------------------------------------------------------------
    # training-ingest scenarios (--scenario; docs/scenarios.md)
    # ------------------------------------------------------------------

    def _run_scenario(self) -> None:
        """Drive the expanded scenario plan through the unchanged phase
        machinery: per step, apply the config overlay (re-shipping it to
        the services when the wire-relevant effective config changed),
        run the phase journaled under the step's plan index, and collect
        a per-step summary for the scenario-level verdict."""
        cfg = self.cfg
        plan = self._scenario_plan
        from .phases import phase_name
        from .scenarios.verdict import analyze_scenario
        logger.log(0, f"Scenario {plan.name}: {len(plan.steps)} step(s) — "
                      + ", ".join(s.label for s in plan.steps))
        self.statistics.print_phase_results_table_header()
        finished = self._resume_plan.finished \
            if self._resume_plan is not None else set()
        runs = plan.resume_runs(finished)
        # every attribute any step overlays, snapshotted once so each
        # step starts from the BASE config, not the previous overlay
        base = {}
        for step in plan.steps:
            for key in step.overlay:
                base.setdefault(key, getattr(cfg, key))
        base.setdefault("scenario_step_label", cfg.scenario_step_label)
        base.setdefault("scenario_epoch", cfg.scenario_epoch)
        # what the initial prepare_threads shipped to the services; the
        # step label is log-only and never worth a fleet re-prepare
        wire_keys = sorted(set(base) - {"scenario_step_label"})

        def wire_relevant(overlay: dict) -> dict:
            """The overlay keys a service actually consumes. The only
            service-side reader of scenario_epoch is the shuffle seed,
            so without a shuffle window in effect an epoch-only change
            (coldwarm's measured legs) must not bounce the fleet — the
            epoch tag on the records is stamped master-side."""
            eff = {k: overlay[k] for k in wire_keys}
            if not eff.get("shuffle_window", cfg.shuffle_window):
                eff.pop("scenario_epoch", None)
            return eff

        shipped = wire_relevant(base)
        inf = self._adopt_inflight()
        if inf is not None and 0 <= inf.get("index", -1) < len(plan.steps):
            # --resume --adopt skipped the fleet /preparephase: the
            # services still run the dead master's LAST shipped config —
            # the in-flight step's effective overlay, not the base.
            # Seeding `shipped` with it keeps the adopted step from
            # bouncing the fleet mid-flight; later differing steps still
            # re-prepare as usual.
            step0 = plan.steps[inf["index"]]
            shipped = wire_relevant({**base, **step0.overlay,
                                     "scenario_step_label": step0.label,
                                     "scenario_epoch": step0.epoch})
        summaries: "list[dict]" = []
        ran_any = False
        try:
            for idx, step in enumerate(plan.steps):
                if not runs[idx]:
                    logger.log(0, f"RESUME: skipping finished scenario "
                                  f"step {step.label} "
                                  f"({phase_name(step.phase)})")
                    continue
                if self._skip_mkdirs_leg(step):
                    continue
                if step.delay_secs:
                    time.sleep(step.delay_secs)
                elif ran_any and cfg.next_phase_delay_secs:
                    # --phasedelay idles between scenario steps exactly
                    # like between plain phases; a step's own interval
                    # knob (ckpt-burst) wins over it
                    time.sleep(cfg.next_phase_delay_secs)
                overlay = {**base, **step.overlay,
                           "scenario_step_label": step.label,
                           "scenario_epoch": step.epoch}
                for key, val in overlay.items():
                    setattr(cfg, key, val)
                from .phases import UNJOURNALED_PHASES
                if step.phase not in UNJOURNALED_PHASES:
                    # master mode ships the full config once per prepare
                    # (/preparephase): an overlay that changes the wire
                    # config needs a fleet re-prepare — the rotate-hosts
                    # rebuild, reused (identical-overlay steps share
                    # one). Sync/dropcaches legs never read the overlay,
                    # so they must not bounce the fleet just because the
                    # epoch tag reverted between two measured steps.
                    effective = wire_relevant(overlay)
                    if cfg.hosts and effective != shipped:
                        self._rebuild_manager()
                    shipped = effective
                self._last_phase_results = None
                ran_any = True
                try:
                    self._run_journaled_phase(0, idx, step.phase,
                                              step_label=step.label)
                except WorkerException as err:
                    if not step.best_effort:
                        raise
                    # sync/dropcaches legs degrade LOUDLY, never fatally:
                    # an unprivileged run still measures, but its "cold"
                    # epochs are flagged in the verdict evidence
                    logger.log_error(
                        f"scenario step {step.label} failed ({err}); "
                        f"continuing — best-effort leg, later cold "
                        f"epochs may not be cold")
                    summaries.append({"Label": step.label,
                                      "Role": step.role,
                                      "Phase": phase_name(step.phase),
                                      "Failed": True})
                    self._mark_cold_degraded(plan, idx, summaries)
                    if cfg.hosts:
                        # a failed phase leaves the RemoteWorkers in
                        # their terminal error state (unlike local
                        # workers, which respawn per phase) — the next
                        # measured leg needs a fresh fleet prepare
                        self._rebuild_manager()
                        shipped = wire_relevant(overlay)
                    continue
                summaries.append(self._scenario_step_summary(step))
        finally:
            for key, val in base.items():  # never leak the last overlay
                setattr(cfg, key, val)
        self._finish_scenario(plan, summaries, analyze_scenario)

    def _skip_mkdirs_leg(self, step) -> bool:
        """The expansion emits the setup.mkdirs leg whenever the bench
        path type is DIR **or unknown** (master mode cannot probe the
        remote path at expansion time) — but by the time the step loop
        runs, prepare_threads has exchanged the services' probed path
        type into cfg.bench_path_type. A file/blockdev fleet must skip
        the leg instead of hammering CREATEDIRS against a file."""
        from .phases import BenchPathType, BenchPhase
        if step.phase != BenchPhase.CREATEDIRS or step.role != "setup":
            return False
        if self.cfg.bench_path_type == BenchPathType.DIR:
            return False
        logger.log(0, f"Skipping scenario step {step.label}: bench path "
                      f"is not a directory")
        return True

    @staticmethod
    def _mark_cold_degraded(plan, failed_idx: int,
                            summaries: "list[dict]") -> None:
        """A failed cache-drop leg taints the cold labels that depend on
        it — record the degradation on the summary side so the verdict
        can say so instead of publishing a fake cold/warm ratio."""
        if plan.steps[failed_idx].role != "cachedrop":
            return
        for step in plan.steps[failed_idx + 1:]:
            if step.cold:
                summaries.append({"__cold_degraded__": step.label})
                return

    def _scenario_step_summary(self, step) -> dict:
        """Per-step result summary feeding scenarios/verdict.py — the
        cross-leg numbers only (full records live in the JSON file)."""
        res = self._last_phase_results
        cfg = self.cfg
        if res is None:  # phase ran without a result (should not happen)
            return {"Label": step.label, "Role": step.role,
                    "Epoch": step.epoch, "Failed": True}
        last_s = res.last_done_usec / 1e6 or 1e-9
        mibs = round(res.final["bytes"] / last_s / (1 << 20), 2)
        read_mibs = round(res.final_rwmix["bytes"] / last_s / (1 << 20), 2)
        out = {
            "Label": step.label,
            "Role": step.role,
            "Epoch": step.epoch,
            "Cold": step.cold,
            "Phase": res.phase_name,
            "ElapsedUSec": res.last_done_usec,
            "Bytes": res.final["bytes"],
            "Entries": res.final["entries"],
            "MiBPerSec": mibs,
            "ReadMiBPerSec": read_mibs,
            "EpochRate": mibs if step.epoch else 0,
            "NumWorkers": res.num_workers,
            # fleet-wide thread counts: NumWorkers counts RemoteWorkers
            # (= hosts) in master mode, so per-thread normalization in
            # the verdicts needs the real totals
            "TotalThreads": cfg.num_threads * max(1, len(cfg.hosts) or 1),
            "ReadThreads": step.overlay.get("num_rwmix_read_threads", 0)
            * max(1, len(cfg.hosts) or 1),
            "BlockSize": cfg.block_size,
        }
        for knob, key in (("scenario_step_usec", "LoaderStepUSec"),
                          ("scenario_batch_blocks", "LoaderBatchBlocks"),
                          ("scenario_prefetch", "LoaderPrefetch"),
                          ("scenario_decode_usec", "LoaderDecodeUSec")):
            if step.overlay.get(knob):
                out[key] = step.overlay[knob]
        if res.analysis is not None:
            # the per-phase doctor's stage decomposition (--flightrec):
            # what the scenario verdict compares ACROSS legs
            out["Analysis"] = {k: res.analysis[k] for k in
                               ("Verdict", "BottleneckStage", "StagePct")}
        return out

    def _finish_scenario(self, plan, summaries: "list[dict]",
                         analyze_scenario) -> None:
        """Compute + print the scenario-level verdicts and append the
        terminal SCENARIO record to the JSON results, so summarize/chart
        and the artifact pipeline see the analysis without new files."""
        degraded = {s["__cold_degraded__"] for s in summaries
                    if "__cold_degraded__" in s}
        steps = [s for s in summaries if "__cold_degraded__" not in s]
        for s in steps:
            if s.get("Label") in degraded:
                s["ColdDegraded"] = True
        analysis = analyze_scenario(plan.name, steps)
        for v in analysis["Verdicts"]:
            logger.log(0, f"Scenario verdict [{v['Kind']}]: "
                          f"{v['Verdict']}")
            for ev in v["Evidence"]:
                logger.log(1, f"  - {ev}")
        if not analysis["Verdicts"]:
            logger.log(0, "Scenario verdict: inconclusive (not enough "
                          "finished legs to compare)")
        cfg = self.cfg
        if cfg.json_file_path:
            import json as json_mod
            rec = {"ISODate": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                   "Label": cfg.bench_label,
                   "Phase": "SCENARIO",
                   "Scenario": plan.name,
                   "ScenarioStep": "summary",
                   "ScenarioAnalysis": analysis}
            with open(cfg.json_file_path, "a") as f:
                f.write(json_mod.dumps(rec) + "\n")

    def _run_autotune(self) -> None:
        """--autotune: verdict-guided knob search BEFORE the measured
        phases (elbencho_tpu/autotune/). The Autotune block lands in
        the run JSON as its own terminal-style record immediately, so
        even an aborted main run keeps the search's trajectory and the
        emitted profile path."""
        from .autotune import run_autotune
        block = run_autotune(self)
        if block is None:
            return
        cfg = self.cfg
        if cfg.json_file_path:
            import json as json_mod
            rec = {"ISODate": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                   "Label": cfg.bench_label,
                   "Phase": "AUTOTUNE",
                   "Autotune": block}
            with open(cfg.json_file_path, "a") as f:
                f.write(json_mod.dumps(rec) + "\n")

    def _rebuild_manager(self) -> None:
        """Tear down the worker fleet and re-prepare it against the
        CURRENT cfg — the mechanism behind --rotatehosts re-ranking and
        scenario overlay re-shipping (master mode posts the full config
        at /preparephase, so a changed step config needs a fresh
        prepare). Keeps tracer/telemetry/flightrec across the rebuild."""
        old_tracer = self.manager.shared.tracer
        self.manager.join_all_threads()
        from .workers.manager import WorkerManager
        self.manager = WorkerManager(self.cfg)
        if old_tracer is not None:
            # keep the run's span ring across the rebuild: a fresh tracer
            # at the same path would overwrite the file and silently drop
            # every earlier span at the next phase-end write()
            self.manager.shared.tracer = old_tracer
        self.statistics = Statistics(self.cfg, self.manager)
        self.statistics.telemetry = self._telemetry  # follow the rebuild
        self.statistics.flightrec = self._flightrec  # keep recording
        self.manager.prepare_threads()

    def _run_journaled_phase(self, iteration: int, idx: int,
                             phase: BenchPhase,
                             step_label: str = "") -> None:
        """One table phase, bracketed by journal records: the fsync'd
        phase_start makes a later crash provable (no finish record = the
        phase did not complete), phase_interrupted marks signal/error
        aborts, phase_finish carries per-host result summaries. Scenario
        steps pass their label so the records stay human-readable;
        sync/dropcaches legs stay out of the journal here exactly like
        the interleave (UNJOURNALED_PHASES) — a resume must never treat
        a cache drop as finished work."""
        from .phases import UNJOURNALED_PHASES
        if self._journal is None or phase in UNJOURNALED_PHASES:
            self.run_benchmark_phase(phase)
            return
        bench_uuid = ""
        if self.cfg.hosts and getattr(self.cfg, "takeover_token", ""):
            # failover-armed fleet run: pre-mint the phase's bench UUID
            # so it is journaled BEFORE /startphase — an adopting master
            # then re-presents it and the fleet's duplicate-start
            # idempotency keeps the in-flight phase running
            import uuid as uuid_mod
            bench_uuid = str(uuid_mod.uuid4())
            inf = self._adopt_inflight()
            if inf is not None and inf.get("bench_uuid") \
                    and (inf.get("iteration"), inf.get("index")) \
                    == (iteration, idx):
                bench_uuid = inf["bench_uuid"]
        self._journal_write(self._journal.phase_start, iteration, idx,
                            phase, step_label, bench_uuid)
        try:
            self.run_benchmark_phase(phase, bench_uuid=bench_uuid)
        except BaseException as err:
            reason = f"{type(err).__name__}: {err}" if str(err) \
                else type(err).__name__
            try:  # best effort: never mask the original abort cause
                self._journal.phase_interrupted(iteration, idx, phase,
                                                reason, step_label)
            except OSError:
                pass
            raise
        self._journal_write(self._journal.phase_finish, iteration, idx,
                            phase, self._phase_host_summaries(),
                            step_label)

    def _journal_write(self, method, *args) -> None:
        """A mid-run journal append failure (disk full, lost mount) must
        abort like any worker error — cleanly, with interrupt + hygiene —
        not escape as a raw OSError traceback: a run whose restart point
        can no longer be recorded must not keep running as if it could."""
        try:
            method(*args)
        except OSError as err:
            raise WorkerException(
                f"--journal write failed ({self.cfg.journal_file_path}): "
                f"{err}") from err

    def _phase_host_summaries(self) -> "dict[str, dict]":
        """Per-host finish summary for the journal: local workers fold
        into one "local" entry, RemoteWorkers report per host."""
        out: "dict[str, dict]" = {}
        for w in self.manager.workers:
            key = getattr(w, "host", None) or "local"
            s = out.setdefault(key, {"entries": 0, "bytes": 0, "iops": 0,
                                     "elapsed_usec": 0})
            s["entries"] += w.live_ops.num_entries_done
            s["bytes"] += w.live_ops.num_bytes_done
            s["iops"] += w.live_ops.num_iops_done
            s["elapsed_usec"] = max(s["elapsed_usec"],
                                    max(w.elapsed_usec_vec, default=0))
        return out

    def _run_sync_and_drop_caches(self) -> None:
        if self.cfg.run_sync_phase:
            self.run_benchmark_phase(BenchPhase.SYNC)
        if self.cfg.run_drop_caches_phase:
            self.run_benchmark_phase(BenchPhase.DROPCACHES)

    def run_benchmark_phase(self, phase: BenchPhase,
                            bench_uuid: str = "") -> None:
        """Start phase -> live stats -> wait done -> print results
        (reference: runBenchmarkPhase, Coordinator.cpp:249). A nonempty
        bench_uuid forces the phase's UUID (journal pre-mint / adoption,
        see _run_journaled_phase)."""
        from .phases import phase_name
        phase_start = time.monotonic()
        tracer = self.manager.shared.tracer
        trace_t0 = tracer.now_ns() if tracer is not None else 0
        profiling = self._start_tpu_profile(phase)
        try:
            self.manager.start_next_phase(phase, bench_uuid=bench_uuid)
            self.statistics.live_stats_loop(phase, phase_start)
            self.manager.wait_for_workers_done(phase_start)
        finally:
            if profiling:
                self._stop_tpu_profile()
            if tracer is not None:
                # phase marker span + persist the ring, so the trace file
                # is loadable after every phase (and after an abort). The
                # marker carries the phase's non-zero path-audit totals
                # (TPU path, retry, staging-pool counters) as span args —
                # the whole PATH_AUDIT_COUNTERS schema is inspectable in
                # Perfetto without cross-referencing the JSON record.
                from .service.fault_tolerance import \
                    merge_control_audit_counters
                from .tpu.device import sum_path_audit_counters
                # barrier decomposition BEFORE the marker is built, so
                # StragglerSkewUsec/BarrierWaitUSec ride the marker like
                # every control counter (recomputed harmlessly by
                # generate_phase_results right after)
                self.statistics._compute_barrier_skew()
                audit = {k: v for k, v in sum_path_audit_counters(
                    self.manager.workers).items() if v}
                # control-plane audit (retries, lease expiries/age) rides
                # the same phase marker so Perfetto shows both planes
                audit.update({k: v for k, v in merge_control_audit_counters(
                    self.manager.workers).items() if v})
                tracer.record(phase_name(phase), "phase", trace_t0,
                              (tracer.now_ns() - trace_t0) // 1000,
                              **audit)
                try:
                    tracer.write()
                except OSError as err:
                    logger.log_error(f"--tracefile write failed: {err}")
        self._last_phase_results = self.statistics.print_phase_results(phase)
        if self._interrupted:
            # user Ctrl-C: print what we have for this phase, then abort the
            # remaining phases (reference: handleInterruptSignal semantics)
            raise KeyboardInterrupt

    #: phases whose workers drive the TPU data path (H2D staging on
    #: reads, HBM-originated fills on writes, the fabric bench itself) —
    #: metadata phases (mkdir/stat/delete) never touch the device
    _TPU_PROFILE_PHASES = (BenchPhase.CREATEFILES, BenchPhase.READFILES,
                           BenchPhase.TPUBENCH, BenchPhase.TPUSLICE)

    def _start_tpu_profile(self, phase: BenchPhase) -> bool:
        """--tpuprofile DIR: bracket each TPU-touching measured phase with
        a jax profiler trace (XLA device timeline, viewable in
        TensorBoard/Perfetto — the TPU-native per-op observability the
        reference's --opslog gives for syscalls). One trace subdirectory
        per phase run."""
        cfg = self.cfg
        if not cfg.tpu_profile_dir:
            return False
        if not (cfg.tpu_ids or cfg.run_tpu_bench or cfg.run_tpu_slice):
            return False
        if phase not in self._TPU_PROFILE_PHASES:
            return False
        if cfg.hosts:
            # master mode: the TPU work happens in the remote service
            # processes; tracing this process would record an idle
            # timeline. Warn once instead of writing meaningless traces.
            if not self._profile_warned_hosts:
                self._profile_warned_hosts = True
                logger.log_error(
                    "--tpuprofile is ignored in master mode (the TPU "
                    "work runs in the remote service processes); run the "
                    "benchmark locally on each host to capture traces")
            return False
        self._profile_seq += 1
        trace_dir = os.path.join(
            cfg.tpu_profile_dir,
            f"{self._profile_seq:03d}_{phase.name.lower()}")
        try:
            import jax
            # JAX's Python tracer (on by default) would time every Python
            # call of the host path the trace is meant to observe; the
            # program's own stage spans name the host's time instead
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except Exception as err:  # noqa: BLE001 - any failure fails the run
            raise WorkerException(
                f"--tpuprofile: cannot start jax trace "
                f"({type(err).__name__}: {err})") from err
        logger.log(1, f"TPU profile trace: {trace_dir}")
        return True

    @staticmethod
    def _stop_tpu_profile() -> None:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as err:  # noqa: BLE001 - any failure fails the run
            raise WorkerException(
                f"--tpuprofile: stop_trace failed "
                f"({type(err).__name__}: {err})") from err

    def _rotate_hosts(self) -> None:
        """--rotatehosts: shift the hosts list between phases, which
        re-ranks all remote workers (reference: rotateHosts :384-408 —
        requires a fresh prep phase)."""
        cfg = self.cfg
        if not cfg.rotate_hosts_num or not cfg.hosts:
            return
        k = cfg.rotate_hosts_num % len(cfg.hosts)
        if not k:
            return
        cfg.hosts = cfg.hosts[k:] + cfg.hosts[:k]
        self._rebuild_manager()

    # ------------------------------------------------------------------

    def _install_signal_handler(self) -> None:
        """Two-stage graceful shutdown (reference: Coordinator.cpp:23,
        :420-442, tightened for unattended runs): the FIRST SIGINT or
        SIGTERM interrupts local workers and remote services and lets the
        run unwind normally — the journal's phase_interrupted record is
        written on the way out, services get /interruptphase. A SECOND
        signal is immediate: the default disposition is restored and the
        signal re-delivered to this process."""

        def handler(signum, frame):
            if self._interrupted:
                # second signal: immediate — no more graceful anything
                for sig in (signal.SIGINT, signal.SIGTERM):
                    try:
                        signal.signal(sig, signal.SIG_DFL)
                    except (ValueError, OSError):
                        pass
                os.kill(os.getpid(), signum)
                return
            self._interrupted = True
            print("Interrupt received. Finishing up... "
                  "(send the signal again to force quit)", file=sys.stderr)
            self.manager.shared.request_interrupt()
            self.manager.interrupt_and_notify_workers()

        self._old_handlers = []
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                self._old_handlers.append((sig, signal.signal(sig, handler)))
            except ValueError:
                pass  # not on main thread (tests)

    def _restore_signal_handler(self) -> None:
        for sig, old in self._old_handlers:
            try:
                signal.signal(sig, old)
            except (ValueError, OSError, TypeError):
                pass
        self._old_handlers = []
