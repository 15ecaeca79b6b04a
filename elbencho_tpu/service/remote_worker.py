"""RemoteWorker: the master's per-host proxy thread.

Reference: source/workers/RemoteWorker.{h,cpp} — one per --hosts entry;
uploads prep files (:288-345), POSTs the serialized config
(preparePhase :354-407), GETs /startphase (:412), polls /status at an
adaptive cadence accumulating remote live ops into its own counters
(:447-560), fetches /benchresult and ingests per-thread elapsed vectors +
mergeable histograms (finishPhase :172-280), sends /interruptphase on
error/quit. Bench-UUID hijack detection: a /status reply with an unexpected
BenchID aborts the run (RemoteWorker.cpp:199-202).

Fault tolerance (service/fault_tolerance.py + docs/fault-tolerance.md):
transient control-plane failures retry with jittered backoff
(--svcretries/--svcretrybudget), a stalled-progress watchdog bounds how
long a silent host can hold a phase (--svcstalledsecs), and with
--svctolerant N the run completes degraded when up to N hosts are lost
mid-run instead of aborting.
"""

from __future__ import annotations

import http.client
import json
import os
import random
import threading
import time
import urllib.parse

from .. import HTTP_PROTOCOL_VERSION
from ..phases import BenchPhase
from ..stats.latency_histogram import LatencyHistogram
from ..toolkits import logger
from ..workers.base import Worker
from ..workers.shared import (WorkerHijackedException,
                              WorkerInterruptedException,
                              WorkerRemoteException,
                              WorkerStalledException)
from . import protocol as proto
from .fault_tolerance import (ConnectFailedError, GarbageReplyError,
                              RetryBudget, RetryPolicy,
                              TRANSIENT_EXCEPTIONS, TRANSIENT_HTTP_STATUSES,
                              is_connect_level_error, is_transient_error)
from .stream import StreamDetachedError, plan_tree

DEFAULT_PORT = 1611
CONNECT_TIMEOUT_SECS = 10
# best-effort /interruptphase sends (teardown path): short and retry-free
# so a dead host can't stall the shutdown of the survivors
INTERRUPT_TIMEOUT_SECS = 3
# adaptive /status cadence: start fast for short phases, back off to the
# configured --svcupint (reference: 25ms -> 500ms, RemoteWorker.cpp:447+)
POLL_MIN_SECS = 0.025
# done-observation granularity of the streaming plane: completion pushes
# ride the change-detection tick (stream.TICK_SECS, 25ms) plus frame
# transit — two ticks bounds it honestly
STREAM_DONE_OBS_QUANTUM_USEC = 50_000


def split_host_port(host: str, default_port: int = DEFAULT_PORT
                    ) -> "tuple[str, int]":
    if ":" in host:
        name, _, port = host.rpartition(":")
        return (name, int(port))
    return (host, default_port)


class ServiceClient:
    """HTTP/JSON client for one service host with transient-failure
    retries (shared idiom with the S3 data plane's retry strategy,
    s3_tk.S3Client.request).

    Streaming-control-plane core (docs/control-plane.md): ONE persistent
    keep-alive connection per host, reused across requests with a
    transparent one-shot reconnect when a parked connection turns out
    stale (the service times idle connections out) — per-request
    connection churn used to cost a TCP handshake per /status tick per
    host. `open_stream` opens the separate long-lived /livestream
    connection. The class-level `open_connections` gauge counts every
    control-plane socket this process believes open; the master samples
    it into the SvcConnHwm audit counter (the O(fanout) proof)."""

    #: open MASTER-side control-plane sockets process-wide (requests +
    #: streams). Interior-node clients (a service's child aggregators,
    #: interrupt forwarding) opt out via gauge=False: their sockets live
    #: on the service hosts and must not pollute the master's SvcConnHwm
    #: — which also keeps the in-process test fleet honest.
    open_connections = 0
    _conn_gauge_lock = threading.Lock()

    def __init__(self, host: str, default_port: int, pw_hash: str = "",
                 retry_policy: "RetryPolicy | None" = None,
                 interrupt_check=None, gauge: bool = True):
        self.hostname, self.port = split_host_port(host, default_port)
        self.pw_hash = pw_hash
        self.retry_policy = retry_policy or RetryPolicy(num_retries=0,
                                                        budget_secs=0.0)
        self.retry_budget = RetryBudget(self.retry_policy.budget_secs)
        self.interrupt_check = interrupt_check
        # deterministic per-host jitter stream (reproducible chaos runs)
        self._rng = random.Random(f"{self.hostname}:{self.port}")
        # the persistent keep-alive connection
        self._conn: "http.client.HTTPConnection | None" = None
        self._gauge = gauge
        # control-plane audit counters (fault_tolerance.py schema)
        self.total_retries = 0
        self.consec_retries = 0
        self.consec_retries_hwm = 0
        self.total_requests = 0  # SvcRequests: HTTP requests actually sent
        self.total_rx_bytes = 0  # SvcCtlBytes: response payload bytes

    def reset_phase_accounting(self) -> None:
        """New phase: fresh retry budget + per-phase counters."""
        self.retry_budget.reset()
        self.total_retries = 0
        self.consec_retries = 0
        self.consec_retries_hwm = 0
        self.total_requests = 0
        self.total_rx_bytes = 0

    def rebind(self, pw_hash: str, retry_policy: "RetryPolicy",
               interrupt_check) -> None:
        """Re-home an adopted client (e.g. one kept warm by the
        wait_for_services_ready probe) onto its RemoteWorker's policy."""
        self.pw_hash = pw_hash
        self.retry_policy = retry_policy
        self.retry_budget = RetryBudget(retry_policy.budget_secs)
        self.interrupt_check = interrupt_check
        self.reset_phase_accounting()

    def _host_label(self) -> str:
        return f"{self.hostname}:{self.port}"

    # -- connection lifecycle ----------------------------------------------

    def _conn_opened(self) -> None:
        if self._gauge:
            with ServiceClient._conn_gauge_lock:
                ServiceClient.open_connections += 1

    def _conn_closed(self) -> None:
        if self._gauge:
            with ServiceClient._conn_gauge_lock:
                ServiceClient.open_connections -= 1

    def _connect(self, timeout: float) -> "http.client.HTTPConnection":
        conn = http.client.HTTPConnection(self.hostname, self.port,
                                          timeout=timeout)
        try:
            conn.connect()
        except OSError as err:
            raise ConnectFailedError(
                f"connect to {self._host_label()} failed: {err}") from err
        self._conn_opened()
        return conn

    def drop_connection(self) -> None:
        """Close the persistent request connection (stream mode parks the
        master between phase-control bursts; holding an idle socket per
        host would defeat the O(fanout) steady state)."""
        conn, self._conn = self._conn, None
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass
            self._conn_closed()

    def close(self) -> None:
        self.drop_connection()

    def _request(self, method: str, path: str, params: "dict | None" = None,
                 body: "bytes | None" = None,
                 timeout: float = CONNECT_TIMEOUT_SECS,
                 allow_reuse: bool = True):
        """One raw exchange over the persistent connection. A failure to
        even reach the service raises ConnectFailedError so the retry
        layer knows the request was never sent (safe to retry
        non-idempotent requests). A failure on a REUSED connection is
        transparently retried once on a fresh one — the service closes
        idle keep-alive connections, and that stale-socket case must not
        surface as a spurious transient error. Non-idempotent callers
        pass allow_reuse=False: their request always rides a provably
        fresh connection, so the stale-retry ambiguity (was it
        processed?) cannot arise for them."""
        params = dict(params or {})
        if self.pw_hash:
            params[proto.KEY_AUTHORIZATION] = self.pw_hash
        if params:
            path = path + "?" + urllib.parse.urlencode(params)
        if not allow_reuse:
            self.drop_connection()
        for _attempt in (0, 1):
            conn = self._conn
            reused = conn is not None
            if conn is None:
                conn = self._connect(timeout)
                self._conn = conn
            try:
                if reused and conn.sock is not None:
                    # per-request timeout on the reused socket; EBADF
                    # here means the parked socket died — the stale-
                    # retry below handles it like any reuse failure
                    conn.sock.settimeout(timeout)
                conn.request(method, path, body=body)
                resp = conn.getresponse()
                data = resp.read()
            except TRANSIENT_EXCEPTIONS:
                self.drop_connection()
                if reused:
                    continue  # stale keep-alive socket: one fresh retry
                raise
            self.total_requests += 1
            self.total_rx_bytes += len(data)
            if resp.will_close:
                self.drop_connection()
            return resp.status, data
        raise AssertionError("unreachable")  # pragma: no cover

    def open_stream(self, bench_id: str, interval_ms: int, fanout: int = 0,
                    subtree: "list[str] | tuple" = (),
                    read_timeout: float = 10.0, resync: bool = False,
                    trace_params: "dict | None" = None):
        """Open the /livestream server-push connection (--svcstream);
        returns a stream.StreamHandle whose rtt_usec is the open round
        trip (the streaming --svcping source) and whose clock_* fields
        carry the fleet-tracing skew sample (the open ping bracketed in
        local wall clock + the service's X-Svc-Clock-Usec stamp). The
        stream rides its OWN connection — a chunked response would
        monopolize the request one."""
        from .stream import StreamHandle
        params = {proto.KEY_STREAM_INTERVAL_MS: int(interval_ms)}
        if bench_id:
            params[proto.KEY_BENCH_ID] = bench_id
        if fanout:
            params[proto.KEY_STREAM_FANOUT] = int(fanout)
        if subtree:
            params[proto.KEY_STREAM_SUBTREE] = ",".join(subtree)
        if resync:
            params[proto.KEY_STREAM_RESYNC] = 1
        if trace_params:
            params.update(trace_params)
        if self.pw_hash:
            params[proto.KEY_AUTHORIZATION] = self.pw_hash
        path = proto.PATH_LIVE_STREAM + "?" + urllib.parse.urlencode(params)
        t0 = time.monotonic()
        t0_wall = time.time_ns() // 1000
        conn = self._connect(CONNECT_TIMEOUT_SECS)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
        except TRANSIENT_EXCEPTIONS as err:
            conn.close()
            self._conn_closed()
            raise WorkerRemoteException(
                f"live stream open on {self._host_label()} failed: "
                f"{type(err).__name__}: {err}") from err
        rtt_usec = int((time.monotonic() - t0) * 1e6)
        t1_wall = time.time_ns() // 1000
        self.total_requests += 1
        if resp.status != 200:
            try:
                detail = resp.read(512).decode(errors="replace")
            except TRANSIENT_EXCEPTIONS:
                detail = ""
            conn.close()
            self._conn_closed()
            raise WorkerRemoteException(
                f"live stream open on {self._host_label()} failed "
                f"(HTTP {resp.status}): {detail}")
        try:
            svc_clock = int(resp.getheader(proto.HDR_SVC_CLOCK, "") or 0)
        except ValueError:
            svc_clock = 0
        if conn.sock is not None:
            conn.sock.settimeout(read_timeout)
        return StreamHandle(conn, resp, rtt_usec, self._host_label(),
                            on_close=self._conn_closed,
                            clock_t0_usec=t0_wall, clock_t1_usec=t1_wall,
                            svc_clock_usec=svc_clock)

    # -- retrying core ------------------------------------------------------

    def _exchange_retry(self, method: str, path: str,
                        params: "dict | None" = None,
                        body: "bytes | None" = None,
                        timeout: float = CONNECT_TIMEOUT_SECS,
                        idempotent: bool = True,
                        deadline: "float | None" = None,
                        parse_json: bool = True):
        """(status, payload) with transient-error retries.

        Idempotent requests retry on any transient failure including
        retryable HTTP statuses and garbage 200-replies; non-idempotent
        ones only on connect-level failures. Each retry sleeps a jittered
        exponential backoff drawn from the per-phase budget; an optional
        deadline (the stall watchdog) caps the whole exchange. On
        exhaustion the last transient status is returned for the caller's
        contextual error message, while transport errors raise
        WorkerRemoteException with host context.
        """
        policy = self.retry_policy
        attempt = 0
        while True:
            if self.interrupt_check is not None:
                self.interrupt_check()
            err: "BaseException | None" = None
            status, payload = 0, {}
            try:
                # non-idempotent requests always ride a provably fresh
                # connection (no stale-keep-alive ambiguity about whether
                # the service processed them)
                status, data = self._request(method, path, params, body,
                                             timeout=timeout,
                                             allow_reuse=idempotent)
                if parse_json:
                    try:
                        payload = json.loads(data) if data else {}
                    except json.JSONDecodeError:
                        payload = {"raw": data.decode(errors="replace")}
                        if status == 200:
                            # a mangled OK reply is indistinguishable from
                            # line noise — retryable, never trustable
                            err = GarbageReplyError(
                                f"undecodable JSON reply from "
                                f"{self._host_label()}")
                else:
                    payload = data
            except TRANSIENT_EXCEPTIONS as req_err:
                err = req_err
            if err is None and status in TRANSIENT_HTTP_STATUSES \
                    and idempotent:
                err = http.client.HTTPException(
                    f"transient HTTP {status} from {self._host_label()}")
                # keep last payload/status: returned on retry exhaustion
            if err is None:
                self.consec_retries = 0
                return status, payload
            retryable = is_transient_error(err) and (
                idempotent or is_connect_level_error(err))
            delay = policy.backoff_delay(attempt, self._rng)
            if (not retryable) or attempt >= policy.num_retries \
                    or (deadline is not None
                        and time.monotonic() + delay >= deadline) \
                    or not self.retry_budget.try_spend(delay):
                if status in TRANSIENT_HTTP_STATUSES:
                    # the service DID answer; hand the status back so the
                    # caller raises its own contextual error
                    return status, payload
                raise WorkerRemoteException(
                    f"service {self._host_label()}: {method} {path} "
                    f"failed: {type(err).__name__}: {err}") from err
            attempt += 1
            self.total_retries += 1
            self.consec_retries += 1
            self.consec_retries_hwm = max(self.consec_retries_hwm,
                                          self.consec_retries)
            logger.log(logger.LOG_VERBOSE,
                       f"retrying {method} {path} on "
                       f"{self._host_label()} in {delay * 1000:.0f}ms "
                       f"(attempt {attempt}/{policy.num_retries}: "
                       f"{type(err).__name__}: {err})")
            time.sleep(delay)

    # -- public request surface --------------------------------------------

    def get_json(self, path: str, params: "dict | None" = None,
                 timeout: float = CONNECT_TIMEOUT_SECS,
                 idempotent: bool = True,
                 deadline: "float | None" = None) -> "tuple[int, dict]":
        return self._exchange_retry("GET", path, params, timeout=timeout,
                                    idempotent=idempotent,
                                    deadline=deadline)

    def post_json(self, path: str, obj, params: "dict | None" = None,
                  timeout: float = 60.0,
                  idempotent: bool = False) -> "tuple[int, dict]":
        body = json.dumps(obj).encode()
        return self._exchange_retry("POST", path, params, body,
                                    timeout=timeout, idempotent=idempotent)

    def get_raw(self, path: str, params: "dict | None" = None,
                timeout: float = CONNECT_TIMEOUT_SECS
                ) -> "tuple[int, bytes]":
        return self._exchange_retry("GET", path, params, timeout=timeout,
                                    idempotent=True, parse_json=False)

    def post_raw(self, path: str, params: "dict | None", body: bytes,
                 timeout: float = 60.0, idempotent: bool = True
                 ) -> "tuple[int, bytes]":
        return self._exchange_retry("POST", path, params, body,
                                    timeout=timeout, idempotent=idempotent,
                                    parse_json=False)


class RemoteWorker(Worker):
    def __init__(self, shared, host_idx: int, host: str):
        super().__init__(shared, rank=host_idx)
        self.cfg = shared.config
        self.host = host
        self.host_idx = host_idx
        self.last_ping_usec = 0  # --svcping: last /status RTT
        self.cpu_util_pct = 0.0  # last /status CPUUtil (telemetry gauge)
        self.degraded = False    # --svctolerant: host lost mid-run
        # control-plane audit counters (CONTROL_AUDIT_COUNTERS schema);
        # the lease pair mirrors SERVICE-observed values (--svcleasesecs,
        # service-lifetime) ingested from /status + /benchresult
        self.svc_retries = 0
        self.svc_consec_retries_hwm = 0
        self.svc_heartbeat_age_hwm_usec = 0
        self.svc_lease_expiries = 0
        self.svc_lease_age_hwm_usec = 0
        # master failover (--resume --adopt; CONTROL_AUDIT_COUNTERS):
        # MasterTakeovers is master-observed (1 on the phase this worker
        # claimed its host via /adopt); the SvcAdopt pair mirrors
        # SERVICE-observed lifetime values ingested like the lease pair
        self.master_takeovers = 0
        self.svc_adoptions = 0
        self.svc_adopt_wait_usec = 0
        self._took_over = False       # this worker claimed its host
        self._takeover_counted = False
        # streaming control plane audit (--svcstream; master-observed,
        # CONTROL_AUDIT_COUNTERS schema — docs/control-plane.md)
        self.svc_requests = 0
        self.svc_ctl_bytes = 0
        self.svc_stream_frames = 0
        self.svc_stream_bytes = 0
        self.svc_delta_saved_bytes = 0
        self.svc_agg_depth_hwm = 0
        self.svc_conn_hwm = 0
        # fleet straggler attribution (CONTROL_AUDIT_COUNTERS schema):
        # computed by Statistics after the phase barrier from each
        # host's phase_done_monotonic finish stamp
        self.straggler_skew_usec = 0
        self.barrier_wait_usec = 0
        self.phase_done_monotonic = 0.0
        # how coarse the done observation was (usec): poll mode = the
        # poll interval at detection time (ramped, up to --svcupint),
        # stream mode = the push-on-change tick — the doctor scales its
        # straggler-bound floor by it so sampling noise can't fabricate
        # a verdict
        self.done_obs_quantum_usec = 0
        # fleet tracing: per-host clock-offset estimator fed by the
        # exchanges this worker performs anyway (/status polls, the
        # stream open, /benchresult)
        from ..telemetry.tracefleet import (ClockSyncEstimator,
                                            fleet_trace_enabled)
        self.clock_sync = ClockSyncEstimator()
        self._fleet_trace = fleet_trace_enabled(self.cfg)
        # slow-op forensics (--slowops): this proxy never records ops
        # itself — it ingests the snapshot its service ships at
        # /benchresult (the counters arrive via the PATH_AUDIT ingest)
        self._slowops = None
        self.slowops_shipped: "dict | None" = None
        pw_hash = ""
        if self.cfg.svc_password_file:
            pw_hash = proto.read_pw_file(self.cfg.svc_password_file)
        # adopt the persistent client the wait_for_services_ready probe
        # already holds an open connection on, instead of building a
        # throwaway one (duplicated --hosts entries: only the first
        # worker adopts; the rest get fresh clients)
        client = adopt_probed_client(*split_host_port(
            host, self.cfg.service_port))
        if client is not None:
            client.rebind(pw_hash, RetryPolicy.from_config(self.cfg),
                          self.check_interruption_flag_only)
        else:
            client = ServiceClient(
                host, self.cfg.service_port, pw_hash,
                retry_policy=RetryPolicy.from_config(self.cfg),
                interrupt_check=self.check_interruption_flag_only)
        self.client = client
        self.num_remote_threads = self.cfg.num_threads
        self._expected_bench_id = ""

    # ------------------------------------------------------------------

    def reset_stats(self) -> None:
        super().reset_stats()
        # zero EVERY live-ingest mirror, incl. the TPU-context path-audit
        # attrs _ingest_live_telemetry setattr'd last phase (base reset
        # only covers the worker-owned ones): a stale mirror would leak
        # the previous phase's totals into the next phase's first
        # /metrics view and flight-recorder tick
        from ..tpu.device import PATH_AUDIT_COUNTERS
        for _attr, _key, ingest_attr in PATH_AUDIT_COUNTERS:
            setattr(self, ingest_attr, 0)
        self.client.reset_phase_accounting()
        self.svc_retries = 0
        self.svc_consec_retries_hwm = 0
        self.svc_heartbeat_age_hwm_usec = 0
        self.svc_lease_expiries = 0
        self.svc_lease_age_hwm_usec = 0
        self.master_takeovers = 0
        self.svc_adoptions = 0
        self.svc_adopt_wait_usec = 0
        self.svc_requests = 0
        self.svc_ctl_bytes = 0
        self.svc_stream_frames = 0
        self.svc_stream_bytes = 0
        self.svc_delta_saved_bytes = 0
        self.svc_agg_depth_hwm = 0
        self.svc_conn_hwm = 0
        self.straggler_skew_usec = 0
        self.barrier_wait_usec = 0
        self.phase_done_monotonic = 0.0
        self.done_obs_quantum_usec = 0
        self.slowops_shipped = None
        if self.degraded:
            # a lost host stays excluded from all later phase results
            self.got_phase_work = False

    def _sync_control_counters(self) -> None:
        self.svc_retries = self.client.total_retries
        self.svc_consec_retries_hwm = self.client.consec_retries_hwm
        self.svc_requests = self.client.total_requests
        # SvcCtlBytes = every control-plane payload byte this phase:
        # request/poll replies plus live-stream frames
        self.svc_ctl_bytes = self.client.total_rx_bytes \
            + self.svc_stream_bytes

    def run(self) -> None:
        try:
            self._run_phases()
        finally:
            self.client.close()  # drop the persistent connection

    def _run_phases(self) -> None:
        self._check_protocol_version()
        if getattr(self.cfg, "adopt_run", False) \
                and getattr(self.cfg, "takeover_token", ""):
            # --resume --adopt: claim the dead master's live service via
            # /adopt — the pool-rebuilding /preparephase would kill the
            # very in-flight work the takeover exists to preserve
            self._adopt_remote_phase()
        else:
            self._prepare_remote_files()
            self._prepare_phase_remote()
        last_uuid = self.shared.bench_uuid
        self.shared.inc_num_workers_done()  # prep barrier
        while True:
            phase, last_uuid = self.shared.wait_for_phase_change(last_uuid)
            if phase == BenchPhase.TERMINATE:
                self._interrupt_remote(quit_service=False)
                return
            if phase == BenchPhase.IDLE:
                continue
            try:
                self._start_remote_phase(phase, last_uuid)
                self._live_until_done(phase)
                # straggler attribution: stamp when the live wait SAW
                # this host's workers done — before the /benchresult
                # fetch, whose duration (and, with fleet tracing, whose
                # shipped span ring) must not fabricate skew
                self.phase_done_monotonic = time.monotonic()
                self._finish_phase_remote()
                self._sync_control_counters()
                self.shared.inc_num_workers_done()
            except WorkerInterruptedException:
                self._interrupt_remote(quit_service=False)
                self._sync_control_counters()
                self.shared.inc_num_workers_done()
            except WorkerHijackedException as err:
                # bench-UUID hijack stays a hard abort: two masters on one
                # service corrupt BOTH runs, no degraded completion
                logger.log_error(f"Remote worker for {self.host} failed: "
                                 f"{err}")
                self._interrupt_remote(quit_service=False)
                self.shared.inc_num_workers_done_with_error(err)
            except Exception as err:  # noqa: BLE001
                logger.log_error(f"Remote worker for {self.host} failed: "
                                 f"{err}")
                self._interrupt_remote(quit_service=False)
                self._sync_control_counters()
                if self.shared.try_degrade_worker(self, err):
                    logger.log_error(
                        f"service {self.host} lost mid-run; completing "
                        f"phase with survivors (--svctolerant, results "
                        f"marked degraded)")
                    return  # host dropped for the rest of the run
                self.shared.inc_num_workers_done_with_error(err)

    # ------------------------------------------------------------------

    # -- fleet tracing: span-context propagation + clock-skew sampling ------

    def _trace_params(self) -> "tuple[dict | None, int]":
        """(extra request params, flow id) for one traced control-plane
        request: a fleet-unique flow id as ParentSpan plus the run's
        trace id. (None, 0) when fleet tracing is off — the wire stays
        byte-identical then."""
        tracer = self.shared.tracer
        if tracer is None or not self._fleet_trace:
            return None, 0
        from ..telemetry.tracer import next_flow_id
        flow_id = next_flow_id()
        params = {proto.KEY_PARENT_SPAN: flow_id}
        trace_id = tracer.extra_other_data.get("traceId", "")
        if trace_id:
            params[proto.KEY_TRACE_ID] = trace_id
        return params, flow_id

    def _record_rpc_span(self, path: str, flow_id: int, t0_ns: int) -> None:
        """Master half of an RPC edge: the rpc:<path> span (tid = this
        host's index, so each host's control traffic gets its own lane)
        plus the Chrome flow-start event the service's handling span
        finishes."""
        tracer = self.shared.tracer
        if tracer is None or not flow_id:
            return
        dur = max((tracer.now_ns() - t0_ns) // 1000, 1)
        tracer.record_rpc(f"rpc:{path}", t0_ns, dur, rank=self.host_idx,
                          flow_id=flow_id, side="out")

    def _feed_clock_sample(self, t0_wall_usec: int, reply: dict) -> None:
        """NTP-style offset sample from any reply carrying the service's
        SvcClockUsec stamp, bracketed by local wall-clock reads. Always
        fed when the key is present (the stamp is always on the wire) so
        the estimate is warm before anything needs it."""
        peer = reply.get(proto.KEY_SVC_CLOCK, 0) if isinstance(
            reply, dict) else 0
        if peer:
            self.clock_sync.add_sample(t0_wall_usec,
                                       time.time_ns() // 1000, peer)

    def _host_clock_estimate(self) -> "tuple[int, int, bool]":
        """(offset_usec, uncertainty_usec, known) of this host's clock
        relative to the master. Two candidate estimates — the direct
        estimator (for a fanout non-root host its only direct samples
        are /benchresult exchanges, whose RTT the shipped span ring
        inflates) and the aggregation-tree chain (master->root measured
        here, root->host carried in stream frames, built from tight
        stream-open pings) — and the one with the SMALLER uncertainty
        wins: uncertainty ~ rtt/2, so a ring-inflated sample can never
        displace a tight chained one."""
        best: "tuple[int, int] | None" = None
        if self.clock_sync.has_estimate:
            best = (self.clock_sync.offset_usec,
                    self.clock_sync.uncertainty_usec)
        sc = getattr(self.shared, "stream_control", None)
        if sc is not None:
            st = sc.states.get(self.host)
            root_worker = sc.workers_by_host.get(
                sc.root_of.get(self.host, self.host))
            if st is not None and st.has_clock \
                    and root_worker is not None \
                    and root_worker.clock_sync.has_estimate:
                from ..telemetry.tracefleet import chain_offsets
                chained = chain_offsets(
                    root_worker.clock_sync.offset_usec,
                    root_worker.clock_sync.uncertainty_usec,
                    st.clock_off, st.clock_unc)
                if best is None or chained[1] < best[1]:
                    best = chained
        if best is None:
            return 0, 0, False
        return best[0], best[1], True

    def _ingest_slowops(self, result: dict) -> None:
        """Slow-op forensics: keep the snapshot this host's /benchresult
        shipped for the master's TailAnalysis merge. A refusal (capture
        over --traceshipcap) is LOUD, never fatal — the merged block
        then names the missing host in its Refusals list."""
        refused = result.get(proto.KEY_SLOWOPS_REFUSED)
        if refused:
            logger.log_error(
                f"slow-op forensics: {self.host} refused to ship its "
                f"capture ({refused.get('Records', 0)} records, "
                f"{refused.get('Bytes', 0)} bytes > --traceshipcap "
                f"{refused.get('CapMiB', 0)} MiB) — TailAnalysis will "
                f"miss this host")
            self.slowops_shipped = None
            return
        shipped = result.get(proto.KEY_SLOWOPS)
        self.slowops_shipped = shipped if isinstance(shipped, dict) \
            else None

    def _collect_trace_ring(self, result: dict) -> None:
        """Fleet tracing: persist the span ring a /benchresult reply
        shipped as this host's per-host trace file next to the master's
        --tracefile, stamped with the estimated clock offset. A refusal
        (ring over --traceshipcap) and a write failure are LOUD, never
        fatal."""
        refused = result.get(proto.KEY_TRACE_RING_REFUSED)
        if refused:
            logger.log_error(
                f"fleet trace: {self.host} refused to ship its span "
                f"ring ({refused.get('Events', 0)} events, "
                f"{refused.get('Bytes', 0)} bytes > --traceshipcap "
                f"{refused.get('CapMiB', 0)} MiB) — its lane will be "
                f"missing from the merged fleet trace")
            return
        ring = result.get(proto.KEY_TRACE_RING)
        if not isinstance(ring, dict):
            return
        from ..telemetry.tracefleet import write_collected_ring
        tracer = self.shared.tracer
        trace_id = tracer.extra_other_data.get("traceId", "") \
            if tracer is not None else ""
        off, unc, _known = self._host_clock_estimate()
        rank_offset = ring.get("otherData", {}).get(
            "rankOffset",
            self.cfg.rank_offset + self.host_idx * self.cfg.num_threads)
        try:
            write_collected_ring(self.cfg.trace_file_path, rank_offset,
                                 ring, self.host, off, unc, trace_id)
        except OSError as err:
            logger.log_error(
                f"fleet trace: cannot write collected trace for "
                f"{self.host}: {err}")

    def _check_protocol_version(self) -> None:
        status, data = self.client.get_raw(proto.PATH_PROTOCOL_VERSION)
        remote = data.decode(errors="replace").strip().strip('"')
        if status != 200 or remote != HTTP_PROTOCOL_VERSION:
            raise WorkerRemoteException(
                f"service {self.host} protocol version mismatch: "
                f"{remote!r} != {HTTP_PROTOCOL_VERSION!r}")

    def _prepare_remote_files(self) -> None:
        """Upload treefile to the service (reference: :288-345).
        Idempotent: re-uploading simply overwrites the stored file."""
        if not self.cfg.tree_file_path:
            return
        with open(self.cfg.tree_file_path, "rb") as f:
            body = f.read()
        status, data = self.client.post_raw(
            proto.PATH_PREPARE_FILE, {
                proto.KEY_FILE_NAME:
                    os.path.basename(self.cfg.tree_file_path)}, body)
        if status != 200:
            raise WorkerRemoteException(
                f"file upload to {self.host} failed: {data!r}")

    def _prepare_phase_remote(self) -> None:
        """POST the full effective config with this host's rank offset
        (reference: preparePhase :354-407; rank offset = hostIdx * threads,
        ProgArgs.cpp:3921). Non-idempotent (rebuilds the remote worker
        pool): retried on connect-level failures only."""
        cfg_dict = self.cfg.to_service_dict(
            service_rank_offset=self.host_idx * self.cfg.num_threads)
        token = getattr(self.cfg, "takeover_token", "")
        if token:
            # master failover: the takeover credentials ride the config
            # wire as protocol extras, present ONLY when the coordinator
            # armed them (--svcadoptsecs > 0 with a journal) — without
            # them the POST body stays byte-identical
            cfg_dict[proto.KEY_TAKEOVER_TOKEN] = token
            cfg_dict[proto.KEY_JOURNAL_FINGERPRINT] = getattr(
                self.cfg, "journal_fingerprint", "")
        trace_params, flow_id = self._trace_params()
        tracer = self.shared.tracer
        t0_ns = tracer.now_ns() if tracer is not None else 0
        status, reply = self.client.post_json(proto.PATH_PREPARE_PHASE,
                                              cfg_dict,
                                              params=trace_params,
                                              timeout=300.0,
                                              idempotent=False)
        self._record_rpc_span(proto.PATH_PREPARE_PHASE, flow_id, t0_ns)
        self._replay_error_history(reply)
        if status != 200:
            raise WorkerRemoteException(
                f"preparation on {self.host} failed: "
                f"{reply.get('Error', reply)}")
        self.bench_path_info = reply

    def _adopt_remote_phase(self) -> None:
        """Takeover handshake (--resume --adopt): GET /adopt with the
        dead master's journaled credentials — bench UUID, takeover
        token, journal fingerprint — so the service re-arms its lease
        for THIS master and keeps its in-flight phase running. The reply
        doubles as the bench path info a /preparephase would have
        returned. Non-idempotent retry shape: a lost reply must not
        double-count the service's SvcAdoptions."""
        params = {
            proto.KEY_BENCH_ID: getattr(self.cfg, "adopt_bench_uuid", ""),
            proto.KEY_TAKEOVER_TOKEN: self.cfg.takeover_token,
            proto.KEY_JOURNAL_FINGERPRINT:
                getattr(self.cfg, "journal_fingerprint", ""),
        }
        status, reply = self.client.get_json(proto.PATH_ADOPT, params,
                                             idempotent=False)
        self._replay_error_history(reply)
        if status != 200:
            raise WorkerRemoteException(
                f"takeover of {self.host} failed ({status}): "
                f"{reply.get('Error', reply)}")
        self.bench_path_info = reply
        self._ingest_lease_counters(reply)
        self._took_over = True
        logger.log(0, f"adopted {self.host}: service accepted takeover "
                      f"(phase code {reply.get(proto.KEY_PHASE_CODE, 0)}, "
                      f"{reply.get(proto.KEY_NUM_WORKERS_DONE, 0)} "
                      f"worker(s) already done)")

    def _start_remote_phase(self, phase: BenchPhase, bench_id: str) -> None:
        self._expected_bench_id = bench_id
        params = {proto.KEY_PHASE_CODE: int(phase),
                  proto.KEY_BENCH_ID: bench_id}
        trace_params, flow_id = self._trace_params()
        if trace_params:
            params.update(trace_params)
        tracer = self.shared.tracer
        t0_ns = tracer.now_ns() if tracer is not None else 0
        status, reply = self.client.get_json(proto.PATH_START_PHASE,
                                             params, idempotent=False)
        self._record_rpc_span(proto.PATH_START_PHASE, flow_id, t0_ns)
        if status != 200:
            raise WorkerRemoteException(
                f"phase start on {self.host} failed: "
                f"{reply.get('Message', reply)}")
        if self._took_over and not self._takeover_counted:
            # lands exactly once, on the adopted phase: reset_stats
            # zeroed the counter before this worker woke for the phase
            self._takeover_counted = True
            self.master_takeovers = 1
        if getattr(self.shared, "stream_control", None) is not None:
            # streaming mode: live stats ride the stream connection; an
            # idle parked request socket per host would defeat the
            # O(fanout) steady state the tree buys
            self.client.drop_connection()

    # -- live-stats ingestion: streaming plane with polling fallback --------

    def _live_until_done(self, phase: BenchPhase) -> None:
        """Dispatch the phase's live-stats wait onto the streaming
        control plane (--svcstream) when it is active for this run,
        falling back LOUDLY one rung (stream -> poll) when the stream
        cannot serve this host — the control-plane analogue of the
        uring -> AIO -> Python ladder of the data path."""
        sc = getattr(self.shared, "stream_control", None)
        if sc is None:
            self._poll_until_done(phase)
            return
        sc.ensure_phase(self._expected_bench_id)
        sc.note_entered()
        try:
            subtree = sc.subtree_of(self.host)
            if subtree is not None:
                self._run_root_stream(phase, sc, subtree)
            else:
                self._wait_stream_host(phase, sc)
            return
        except StreamDetachedError as err:
            sc.detach_host(self.host)
            logger.log_error(
                f"SVCSTREAM FALLBACK: {self.host}: {err}; falling back "
                f"to /status polling for this phase (stream -> poll)")
        self._poll_until_done(phase)

    def _account_stream_frame(self, nbytes: int, state: dict,
                              is_full: bool, now: float,
                              last_frame: float) -> None:
        """Per-frame audit: frames/bytes received, bytes delta encoding
        kept off the wire, the deepest aggregation tree seen, and the
        inter-frame heartbeat gap.

        SvcDeltaSavedBytes is an estimate priced against the size of the
        most recent FULL frame on this stream (every stream starts with
        one) — re-serializing the merged state per frame just to price
        the delta would re-create a slice of the very per-tick cost the
        stream removes."""
        from .stream import KEY_AGG_DEPTH
        self.svc_stream_frames += 1
        self.svc_stream_bytes += nbytes
        if is_full:
            self._stream_full_frame_bytes = nbytes
        else:
            self.svc_delta_saved_bytes += max(
                getattr(self, "_stream_full_frame_bytes", 0) - nbytes, 0)
        self.svc_agg_depth_hwm = max(self.svc_agg_depth_hwm,
                                     int(state.get(KEY_AGG_DEPTH, 1)))
        self.svc_heartbeat_age_hwm_usec = max(
            self.svc_heartbeat_age_hwm_usec,
            int((now - last_frame) * 1e6))

    #: how long a root stream may deliver only non-matching frames after
    #: /startphase succeeded before the master stops waiting (persistent
    #: foreign UUID = hijack; persistent idle = fall to the polling rung)
    NO_MATCH_GRACE_SECS = 5.0

    def _run_root_stream(self, phase: BenchPhase, sc,
                         subtree: "list[str]") -> None:
        """Attached-root duty: own the subtree's /livestream, distribute
        per-host frame entries into the fleet's host states and worker
        mirrors, ingest the subtree-aggregated telemetry into THIS
        worker (the detach logic guarantees no host contributes twice),
        and stay on the wire until every subtree host is resolved."""
        from .stream import KEY_FULL, StreamProtocolError, apply_delta, \
            check_seq, stream_read_timeout
        interval_ms = max(self.cfg.svc_update_interval_ms, 25)
        read_timeout = stream_read_timeout(interval_ms)
        stalled_secs = max(self.cfg.svc_stalled_secs, 0)

        def reopen(resync: bool):
            trace_params, flow_id = self._trace_params()
            tracer = self.shared.tracer
            t0_ns = tracer.now_ns() if tracer is not None else 0
            try:
                handle = self.client.open_stream(
                    self._expected_bench_id, interval_ms,
                    fanout=sc.fanout, subtree=subtree,
                    read_timeout=read_timeout, resync=resync,
                    trace_params=trace_params)
            except (WorkerRemoteException, *TRANSIENT_EXCEPTIONS) as err:
                raise StreamDetachedError(
                    f"cannot open live stream: {err}") from err
            self._record_rpc_span(proto.PATH_LIVE_STREAM, flow_id, t0_ns)
            if handle.svc_clock_usec:
                # the stream-open ping doubles as a clock-offset sample
                self.clock_sync.add_sample(handle.clock_t0_usec,
                                           handle.clock_t1_usec,
                                           handle.svc_clock_usec)
            return handle

        handle = None
        state: dict = {}
        last_seq = 0
        matched = False
        resyncs = 0
        agg_zeroed = False
        no_match_since = time.monotonic()
        last_frame = time.monotonic()
        normal_exit = False
        try:
            handle = reopen(resync=False)
            self.last_ping_usec = handle.rtt_usec
            while True:
                self.check_interruption_request(force=True)
                try:
                    frame = handle.read_frame()
                    last_seq = check_seq(last_seq, frame)
                except (StreamProtocolError,
                        *TRANSIENT_EXCEPTIONS) as err:
                    # missed/garbled frame or a dead socket: ONE resync
                    # reconnect (the new stream's first frame is a full
                    # snapshot), then give the poll rung the phase
                    if resyncs >= 1:
                        raise StreamDetachedError(
                            f"live stream failed twice: {err}") from err
                    resyncs += 1
                    handle.close()
                    handle = reopen(resync=True)
                    last_seq = 0
                    state = {}
                    continue
                state = apply_delta(
                    {} if frame.get(KEY_FULL) else state, frame)
                frame_id = state.get(proto.KEY_BENCH_ID, "")
                if frame_id == self._expected_bench_id:
                    matched = True
                elif matched and frame_id:
                    self._raise_host_failure("hijacked")
                if not matched:
                    # stale pre-/startphase frames get a short grace; a
                    # stream that NEVER matches must not hang the phase
                    # on heartbeats — a persistent foreign UUID is a
                    # hijack (polling would raise on its first reply),
                    # persistent idle/empty falls to the polling rung
                    if time.monotonic() - no_match_since \
                            <= self.NO_MATCH_GRACE_SECS:
                        continue
                    if frame_id:
                        self._raise_host_failure("hijacked")
                    raise StreamDetachedError(
                        f"no frame matched this run's bench UUID within "
                        f"{self.NO_MATCH_GRACE_SECS:.0f}s")
                now = time.monotonic()
                self._account_stream_frame(handle.last_frame_bytes, state,
                                           bool(frame.get(KEY_FULL)),
                                           now, last_frame)
                last_frame = now
                # SvcConnHwm censuses STEADY-STATE connections: after
                # every worker is past its /startphase burst and before
                # the first finisher reopens for /benchresult — the
                # window where "master holds O(fanout) connections" is
                # the claim being audited
                if sc.all_entered() \
                        and not state.get(proto.KEY_NUM_WORKERS_DONE, 0):
                    self.svc_conn_hwm = max(self.svc_conn_hwm,
                                            ServiceClient.open_connections)
                sc.ingest_frame(self.host, state)
                # subtree-aggregated TPU/path-audit/lease telemetry lands
                # on the ROOT worker; the fleet sum/MAX over workers then
                # equals the flat merge (satellite: /metrics harvests
                # from stream frames — zero extra service requests).
                # Gated on the whole subtree still riding the tree: a
                # detached-then-recovered host would otherwise appear in
                # the aggregate AND in its own polling ingest. On the
                # first detach the already-ingested aggregate (which
                # baked in the lost host's pre-detach share) is zeroed —
                # mid-run /metrics under-counts the subtree rather than
                # double-counting; finals are exact either way
                # (/benchresult overwrites)
                if sc.subtree_fully_attached(self.host):
                    self._ingest_live_telemetry(state)
                elif not agg_zeroed:
                    agg_zeroed = True
                    self._reset_live_telemetry()
                st = sc.state_of(self.host)
                if st.hijacked:
                    self._raise_host_failure("hijacked")
                if st.err:
                    self._raise_host_failure("err")
                if stalled_secs and not self.shared.stonewall_triggered \
                        and now - st.last_change >= stalled_secs:
                    self._raise_host_failure("stalled", stalled_secs)
                if sc.subtree_satisfied(self.host,
                                        self.num_remote_threads):
                    self.done_obs_quantum_usec = \
                        STREAM_DONE_OBS_QUANTUM_USEC
                    normal_exit = True
                    return
        finally:
            if handle is not None:
                handle.close()
            if not normal_exit:
                # abnormal root exit: the subtree loses its aggregator;
                # still-waiting hosts detach and fall back to polling
                sc.detach_subtree(self.host)

    def _wait_stream_host(self, phase: BenchPhase, sc) -> None:
        """Non-root duty: wait on this host's stream-fed state until it
        is done — or raise the exact exception the polling loop would
        (error/hijack/stall), or detach when the tree stops covering this
        host (root died / subtree reported unreachable)."""
        st = sc.state_of(self.host)
        stalled_secs = max(self.cfg.svc_stalled_secs, 0)
        while True:
            self.check_interruption_request(force=True)
            action = None
            with sc.cond:
                if st.hijacked:
                    action = "hijacked"
                elif st.err:
                    action = "err"
                elif st.done >= self.num_remote_threads:
                    self.done_obs_quantum_usec = \
                        STREAM_DONE_OBS_QUANTUM_USEC
                    return
                elif st.unreachable or not st.attached \
                        or sc.root_worker_lost(self.host):
                    action = "detached"
                elif stalled_secs \
                        and not self.shared.stonewall_triggered \
                        and time.monotonic() - st.last_change \
                        >= stalled_secs:
                    action = "stalled"
                else:
                    sc.cond.wait(0.1)
                    continue
            if action in ("hijacked", "err", "stalled"):
                self._raise_host_failure(action, stalled_secs)
            raise StreamDetachedError(
                "aggregation tree no longer covers this host")

    def _poll_until_done(self, phase: BenchPhase) -> None:
        """Poll /status, mirroring remote live totals into this worker's
        counters so the master's live stats aggregate naturally
        (reference: waitForBenchPhaseCompletion :447-560).

        Stall watchdog (--svcstalledsecs): when the service's live
        counters stop advancing — or the service stops answering — for
        longer than the window, the host is declared stalled instead of
        holding the phase barrier forever."""
        interval = POLL_MIN_SECS
        max_interval = max(self.cfg.svc_update_interval_ms, 25) / 1000.0
        stalled_secs = max(self.cfg.svc_stalled_secs, 0)
        # bound the per-poll read block so a hung socket can't blow
        # through the stall window before the watchdog gets to look
        poll_timeout = min(CONNECT_TIMEOUT_SECS, stalled_secs) \
            if stalled_secs else CONNECT_TIMEOUT_SECS
        # two separate baselines: last_success (last answered /status)
        # drives the unreachable trip and the retry deadline, so a
        # legitimately idle host — e.g. a post-stonewall straggler whose
        # counters sit still — keeps its full retry window; last_progress
        # (last counter advance) drives only the static-counter trip
        last_progress = last_success = time.monotonic()
        last_counters = None
        while True:
            self.check_interruption_request(force=True)
            deadline = (last_success + stalled_secs) if stalled_secs \
                else None
            t0 = time.monotonic()
            t0_wall = time.time_ns() // 1000
            try:
                # the bench UUID marks this poll as the owning master's
                # heartbeat: the service's --svcleasesecs lease renews on
                # it, while observer /status polls (dashboards, probes)
                # deliberately cannot keep an orphaned service alive
                status, stats = self.client.get_json(
                    proto.PATH_STATUS,
                    {proto.KEY_BENCH_ID: self._expected_bench_id}
                    if self._expected_bench_id else None,
                    timeout=poll_timeout,
                    deadline=deadline)
            except WorkerRemoteException as err:
                if stalled_secs \
                        and time.monotonic() - last_success >= stalled_secs:
                    raise WorkerStalledException(
                        f"service {self.host} stalled: no reachable "
                        f"status for {stalled_secs}s "
                        f"(--svcstalledsecs)") from err
                raise
            now = time.monotonic()
            # --svcping: the /status round-trip IS the service ping
            # (reference fullscreen shows per-service latency, --svcping)
            self.last_ping_usec = int((now - t0) * 1e6)
            # fleet tracing: the same round trip is a clock-offset
            # sample (lease-renewal piggyback — zero extra requests)
            self._feed_clock_sample(t0_wall, stats)
            self.svc_conn_hwm = max(self.svc_conn_hwm,
                                    ServiceClient.open_connections)
            # heartbeat age: gap between successive successful polls
            self.svc_heartbeat_age_hwm_usec = max(
                self.svc_heartbeat_age_hwm_usec,
                int((now - last_success) * 1e6))
            last_success = now
            if status != 200:
                raise WorkerRemoteException(
                    f"status poll on {self.host} failed ({status})")
            got_id = stats.get(proto.KEY_BENCH_ID, "")
            if got_id and self._expected_bench_id \
                    and got_id != self._expected_bench_id:
                self._raise_host_failure("hijacked")  # reference: :199-202
            self.live_ops.num_entries_done = \
                stats.get(proto.KEY_NUM_ENTRIES_DONE, 0)
            self.live_ops.num_bytes_done = \
                stats.get(proto.KEY_NUM_BYTES_DONE, 0)
            self.live_ops.num_iops_done = \
                stats.get(proto.KEY_NUM_IOPS_DONE, 0)
            self._ingest_live_telemetry(stats)
            if stats.get(proto.KEY_NUM_WORKERS_DONE_WITH_ERROR, 0):
                self._raise_host_failure("err")
            done = stats.get(proto.KEY_NUM_WORKERS_DONE, 0)
            if done >= self.num_remote_threads:
                # the done observation is quantized by the CURRENT poll
                # interval (the host may have finished any time since
                # the previous poll)
                self.done_obs_quantum_usec = int(interval * 1e6)
                return
            counters = (self.live_ops.num_entries_done,
                        self.live_ops.num_bytes_done,
                        self.live_ops.num_iops_done, done)
            if counters != last_counters:
                last_counters = counters
                last_progress = now
            elif stalled_secs and not self.shared.stonewall_triggered \
                    and now - last_progress >= stalled_secs:
                # counters froze while the service still answers; with a
                # stonewall in effect straggler counters may legitimately
                # idle, so the static-counter trip is gated on it
                self._raise_host_failure("stalled", stalled_secs)
            time.sleep(interval)
            interval = min(interval * 2, max_interval)

    def _ingest_live_telemetry(self, stats: dict) -> None:
        """Mirror the per-host telemetry harvest of a /status reply into
        this worker's ingest attributes, so the master's /metrics fleet
        aggregation (sum_path_audit_counters + the MAX-merge rules) works
        MID-RUN exactly like the phase-end /benchresult ingest does. The
        final /benchresult ingest overwrites all of these."""
        from ..tpu.device import PATH_AUDIT_COUNTERS
        self.cpu_util_pct = stats.get("CPUUtil", 0.0)
        self._ingest_lease_counters(stats)
        if "TpuHbmBytes" not in stats:
            return  # pre-telemetry service replied (tests with old stubs)
        self.tpu_transfer_bytes = stats.get("TpuHbmBytes", 0)
        self.tpu_transfer_usec = stats.get("TpuHbmUSec", 0)
        self.tpu_dispatch_usec = stats.get("TpuHbmDispatchUSec", 0)
        for _attr, key, ingest_attr in PATH_AUDIT_COUNTERS:
            setattr(self, ingest_attr, stats.get(key, 0))
        if "IOLatHisto" in stats:  # --telemetry: bucket-level live view
            self.iops_latency_histo = LatencyHistogram.from_dict(
                stats["IOLatHisto"])
            self.entries_latency_histo = LatencyHistogram.from_dict(
                stats.get("EntLatHisto", {}))
        elif "SumIOLatUSec" in stats:
            # no bucket view on the wire, but every live reply/frame
            # carries the latency SUMS — mirror them so the flight
            # recorder's per-host IoBusyUSec (storage busy time) is live
            # mid-run; the final /benchresult ingest overwrites with the
            # full histograms
            io_histo = LatencyHistogram()
            io_histo.num_values = stats.get("NumIOLatUSec", 0)
            io_histo.sum_micro = stats.get("SumIOLatUSec", 0)
            self.iops_latency_histo = io_histo
            ent_histo = LatencyHistogram()
            ent_histo.num_values = stats.get("NumEntLatUSec", 0)
            ent_histo.sum_micro = stats.get("SumEntLatUSec", 0)
            self.entries_latency_histo = ent_histo

    def _reset_live_telemetry(self) -> None:
        """Zero every mirror _ingest_live_telemetry can set — incl. the
        conditionally-ingested histograms and lease counters. Lives next
        to the ingest so a new conditional key added there is visibly a
        key to reset here too (the stream plane zeroes a root's stale
        subtree aggregate when a member detaches to polling)."""
        self._ingest_live_telemetry({
            "TpuHbmBytes": 0, "IOLatHisto": {}, "EntLatHisto": {},
            proto.KEY_SVC_LEASE_EXPIRIES: 0,
            proto.KEY_SVC_LEASE_AGE_HWM: 0,
            proto.KEY_SVC_ADOPTIONS: 0,
            proto.KEY_SVC_ADOPT_WAIT: 0})

    def _raise_host_failure(self, kind: str, stalled_secs: int = 0):
        """The per-host failure exceptions, shared by the polling loop
        and both streaming waiters so the two planes can never drift in
        semantics or wording."""
        if kind == "hijacked":
            raise WorkerHijackedException(
                f"service {self.host} was hijacked by another master "
                f"(bench UUID mismatch)")  # reference: :199-202
        if kind == "err":
            raise WorkerRemoteException(
                f"worker error on service {self.host}"
                + self._fetch_remote_error_detail())
        raise WorkerStalledException(
            f"service {self.host} stalled: live counters static "
            f"for {stalled_secs}s (--svcstalledsecs)")

    def _ingest_lease_counters(self, reply: dict) -> None:
        """Mirror the service-observed lease counters (--svcleasesecs;
        service-lifetime values) so the fleet merge — SvcLeaseExpiries
        sums, SvcLeaseAgeHwmUsec MAXes across hosts — and the /metrics
        view pick them up like every CONTROL_AUDIT_COUNTERS entry."""
        if proto.KEY_SVC_LEASE_EXPIRIES in reply:
            self.svc_lease_expiries = reply[proto.KEY_SVC_LEASE_EXPIRIES]
            self.svc_lease_age_hwm_usec = reply.get(
                proto.KEY_SVC_LEASE_AGE_HWM, 0)
        # adoption counters are on the wire only when nonzero (master
        # failover); absent keys leave the mirrors untouched
        if proto.KEY_SVC_ADOPTIONS in reply:
            self.svc_adoptions = reply[proto.KEY_SVC_ADOPTIONS]
        if proto.KEY_SVC_ADOPT_WAIT in reply:
            self.svc_adopt_wait_usec = reply[proto.KEY_SVC_ADOPT_WAIT]

    def _replay_error_history(self, reply: dict) -> "list[str]":
        """Log the service's error-history lines under this host's prefix
        (reference: XFER_PREP_ERRORHISTORY replay)."""
        lines = reply.get(proto.KEY_ERROR_HISTORY, [])
        for line in lines:
            logger.log_error(f"[{self.host}] {line}")
        return lines

    @staticmethod
    def _strip_log_prefix(line: str) -> str:
        """'2026-.. ERROR: Worker 0 ...' -> 'Worker 0 ...' so an embedded
        root cause doesn't nest timestamps."""
        return line.split("ERROR: ", 1)[-1]

    def _fetch_remote_error_detail(self) -> str:
        """Pull the service's error history so the master shows the REAL
        failure, not just 'worker error' (reference: error history replay,
        Common.h XFER_PREP_ERRORHISTORY + finishPhase ingestion)."""
        try:
            status, result = self.client.get_json(proto.PATH_BENCH_RESULT,
                                                  timeout=15.0)
        except Exception:  # noqa: BLE001 - detail fetch must not mask
            return ""
        lines = self._replay_error_history(result) if status == 200 else []
        return f": {self._strip_log_prefix(lines[-1])}" if lines else ""

    def _finish_phase_remote(self) -> None:
        """GET /benchresult and ingest per-thread elapsed + histograms
        (reference: finishPhase :172-280). With fleet tracing armed the
        same request also asks the service to ship its span ring
        (ShipTrace) — collection piggybacks, zero extra requests."""
        params, flow_id = self._trace_params()
        if params is not None:
            params[proto.KEY_SHIP_TRACE] = 1
        if getattr(self.cfg, "slow_ops_k", 0):
            # slow-op forensics rides the SAME request (zero extra
            # requests; SvcRequests stays byte-identical)
            params = params or {}
            params[proto.KEY_SHIP_SLOWOPS] = 1
        tracer = self.shared.tracer
        t0_ns = tracer.now_ns() if tracer is not None else 0
        t0_wall = time.time_ns() // 1000
        status, result = self.client.get_json(proto.PATH_BENCH_RESULT,
                                              params=params, timeout=60.0)
        self._record_rpc_span(proto.PATH_BENCH_RESULT, flow_id, t0_ns)
        self._feed_clock_sample(t0_wall, result)
        if status != 200:
            raise WorkerRemoteException(
                f"result fetch from {self.host} failed ({status})")
        lines = self._replay_error_history(result)
        self._ingest_lease_counters(result)
        if result.get(proto.KEY_NUM_WORKERS_DONE_WITH_ERROR, 0):
            detail = f": {self._strip_log_prefix(lines[-1])}" if lines \
                else ""
            raise WorkerRemoteException(
                f"service {self.host} reported worker errors{detail}")
        final = result.get("Final", {})
        stonewall = result.get("StoneWall", {})
        self.live_ops.num_entries_done = final.get("entries", 0)
        self.live_ops.num_bytes_done = final.get("bytes", 0)
        self.live_ops.num_iops_done = final.get("iops", 0)
        self.stonewall_ops.num_entries_done = stonewall.get("entries", 0)
        self.stonewall_ops.num_bytes_done = stonewall.get("bytes", 0)
        self.stonewall_ops.num_iops_done = stonewall.get("iops", 0)
        final_rw = result.get("FinalRWMixRead", {})
        stone_rw = result.get("StoneWallRWMixRead", {})
        self.live_ops_rwmix_read.num_entries_done = final_rw.get("entries", 0)
        self.live_ops_rwmix_read.num_bytes_done = final_rw.get("bytes", 0)
        self.live_ops_rwmix_read.num_iops_done = final_rw.get("iops", 0)
        self.stonewall_ops_rwmix_read.num_entries_done = \
            stone_rw.get("entries", 0)
        self.stonewall_ops_rwmix_read.num_bytes_done = \
            stone_rw.get("bytes", 0)
        self.stonewall_ops_rwmix_read.num_iops_done = stone_rw.get("iops", 0)
        self.elapsed_usec_vec = list(
            result.get(proto.KEY_ELAPSED_USEC_LIST, []))
        self.stonewall_elapsed_usec = result.get("StoneWallUSec", 0)
        self.stonewall_taken = True
        self.phase_finished = True
        self.iops_latency_histo = LatencyHistogram.from_dict(
            result.get("IOLatHisto", {}))
        self.entries_latency_histo = LatencyHistogram.from_dict(
            result.get("EntLatHisto", {}))
        self.iops_latency_histo_rwmix = LatencyHistogram.from_dict(
            result.get("IOLatHistoRWMixRead", {}))
        self.op_resident_histo = LatencyHistogram.from_dict(
            result.get("OpResidentLatHisto", {}))
        self.tpu_transfer_bytes = result.get("TpuHbmBytes", 0)
        self.tpu_transfer_usec = result.get("TpuHbmUSec", 0)
        self.tpu_dispatch_usec = result.get("TpuHbmDispatchUSec", 0)
        # H2D/D2H path-audit counters, schema-driven so a counter added
        # to PATH_AUDIT_COUNTERS is ingested without touching this file
        from ..tpu.device import PATH_AUDIT_COUNTERS
        for _attr, key, ingest_attr in PATH_AUDIT_COUNTERS:
            setattr(self, ingest_attr, result.get(key, 0))
        # chip ids arrive as JSON string keys; normalize back to int so
        # the master's merge can't split one chip into "0" and 0 buckets
        self.tpu_per_chip = {
            int(chip): (v.get("Bytes", 0), v.get("USec", 0))
            for chip, v in result.get("TpuPerChip", {}).items()}
        self.tpu_device_stamp = result.get("TpuDevice", {})
        fp = result.get("TpuFingerprint", {})
        self.tpu_fp_sum = fp.get("Sum", 0)
        self.tpu_fp_xor = fp.get("Xor", 0)
        self.tpu_fp_blocks = fp.get("Blocks", 0)
        self.got_phase_work = bool(self.elapsed_usec_vec)
        if getattr(self.cfg, "slow_ops_k", 0):
            self._ingest_slowops(result)
        if self._fleet_trace:
            self._collect_trace_ring(result)
        if getattr(self.shared, "stream_control", None) is not None:
            self.client.drop_connection()  # back to the parked steady state

    def _interrupt_remote(self, quit_service: bool) -> None:
        """Best effort, deliberately BELOW the retry layer: the service may
        already be gone, and burning --svcretries x timeout here serializes
        into teardown (error handler + TERMINATE both interrupt), stalling
        the whole run on a dead host. TRANSIENT_EXCEPTIONS is the shared
        classifier: a half-closed socket's malformed status line
        (HTTPException) must not escape and mask the original failure."""
        params = {proto.KEY_INTERRUPT_QUIT: "1"} if quit_service else {}
        try:
            self.client._request("GET", proto.PATH_INTERRUPT_PHASE, params,
                                 timeout=INTERRUPT_TIMEOUT_SECS)
        except TRANSIENT_EXCEPTIONS:
            pass  # service may already be gone (best effort)


# ---------------------------------------------------------------------------
# master-side helpers (reference: Coordinator::waitForServicesReady :165-227)
# ---------------------------------------------------------------------------

#: clients the ready-probe left with a warm persistent connection, keyed
#: by (hostname, port) for adoption by the host's RemoteWorker — the
#: probe used to build throwaway clients whose sockets were wasted
_probed_clients: "dict[tuple[str, int], ServiceClient]" = {}
_probed_clients_lock = threading.Lock()


def _register_probed_client(client: ServiceClient) -> None:
    key = (client.hostname, client.port)
    with _probed_clients_lock:
        old = _probed_clients.pop(key, None)
        _probed_clients[key] = client
    if old is not None:
        old.close()


def adopt_probed_client(hostname: str, port: int) -> "ServiceClient | None":
    with _probed_clients_lock:
        return _probed_clients.pop((hostname, port), None)


def wait_for_services_ready(hosts: "list[str]", default_port: int,
                            wait_secs: int) -> None:
    """Probe all hosts CONCURRENTLY against the shared --svcwait deadline
    (a slow first host used to eat the whole budget of the hosts after
    it) and report every unreachable host at once. Each successful
    probe's client — connection still open — is parked for adoption by
    that host's RemoteWorker (persistent-connection reuse instead of
    throwaway probe clients)."""
    deadline = time.monotonic() + max(wait_secs, 0)
    unreachable: "dict[str, str]" = {}
    lock = threading.Lock()

    def probe(host: str) -> None:
        client = ServiceClient(host, default_port)
        last_err = "no reply"
        while True:
            try:
                status, _ = client.get_json(proto.PATH_STATUS, timeout=3)
                if status in (200, 401):
                    _register_probed_client(client)
                    return
                last_err = f"HTTP {status}"
            except WorkerRemoteException as err:
                last_err = str(err)
            if time.monotonic() >= deadline:
                with lock:
                    unreachable[host] = last_err
                client.close()
                return
            time.sleep(1)

    threads = [threading.Thread(target=probe, args=(h,), daemon=True,
                                name=f"svc-probe-{h}") for h in hosts]
    for t in threads:
        t.start()
    for t in threads:
        # margin over the shared deadline: a probe returns right after its
        # own deadline check, so this only guards against pathological hangs
        t.join(timeout=max(deadline - time.monotonic(), 0) + 10)
    if unreachable:
        details = "; ".join(f"{h}: {e}" for h, e in unreachable.items())
        raise WorkerRemoteException(
            f"service(s) not reachable (--svcwait to extend the wait): "
            f"{details}")


def send_interrupt_to_hosts(hosts: "list[str]", default_port: int,
                            quit: bool = False, fanout: int = 0) -> None:
    """--interrupt / --quit handling (reference: Coordinator service
    control paths). With --svcfanout the interrupt walks the same
    aggregation tree the live stats ride: the master contacts only the
    roots, each root forwards to its children with their sub-subtrees
    (stream.forward_interrupt), so teardown is O(fanout) too."""
    verb = "quit" if quit else "interrupt"

    def send_one(host: str, subtree: "list[str]") -> None:
        client = ServiceClient(host, default_port)
        params = {proto.KEY_INTERRUPT_QUIT: "1"} if quit else {}
        if subtree:
            params[proto.KEY_STREAM_SUBTREE] = ",".join(subtree)
            params[proto.KEY_STREAM_FANOUT] = fanout
        try:
            client.get_json(proto.PATH_INTERRUPT_PHASE, params)
            via = f" (+{len(subtree)} host(s) via tree)" if subtree else ""
            logger.log(0, f"sent {verb} to {host}{via}")
        except (WorkerRemoteException, *TRANSIENT_EXCEPTIONS) as err:
            # OSError alone used to let a half-closed socket's malformed
            # status line (HTTPException) escape and mask the real failure
            logger.log_error(f"could not reach {host}: {err}")
            if subtree:
                # the same direct-attachment fallback the live-stats
                # plane has: a dead root must not strand its subtree
                # with workers still running
                logger.log_error(
                    f"{verb} fan-out: root {host} unreachable — sending "
                    f"directly to its {len(subtree)} subtree host(s)")
                for sub_host in subtree:
                    send_one(sub_host, [])
        finally:
            client.close()

    for host, subtree in plan_tree(hosts, max(fanout, 0)):
        send_one(host, subtree)
