"""Bounded ring-buffer per-op span recorder with Chrome trace output.

``--tracefile PATH`` arms the recorder; every instrumentation point in the
workers (storage ops), the TPU transfer pipeline and the fused stream
loop (the per-op stage spans, ``StageSpans``) records one complete span
("ph": "X") per event. ``--tracesample R`` keeps only a probabilistic R
fraction of op spans so long phases fit the ring. The stage spans also
go to the JAX profiler while a profiler session records
(docs/telemetry.md "Stage spans").

When tracing is OFF the recorder does not exist: workers hold
``self._tracer is None`` / ``self._stages is None`` and every
instrumentation point is a single attribute test — no allocation, no
call, no formatting (the overhead guards in tests/test_telemetry.py pin
this).

The output is Chrome trace-event JSON (the ``traceEvents`` array format),
loadable in Perfetto / chrome://tracing; ``pid`` is the service's rank
offset (host slot in a distributed run), ``tid`` the worker rank.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import threading
import time

#: default ring capacity (events); old spans are overwritten when a phase
#: outgrows it — num_overwritten says how many were lost
DEFAULT_RING_EVENTS = 1 << 18

#: fleet-wide flow ids (master-generated, echoed by services): one shared
#: counter per process so concurrent RemoteWorkers can never mint the
#: same id — uniqueness across hosts holds because ONLY the master mints
_flow_ids = itertools.count(1)


def next_flow_id() -> int:
    """A process-unique Chrome flow-event id (master side of an RPC edge
    mints one; the service side echoes it back — docs/telemetry.md
    "Fleet tracing")."""
    return next(_flow_ids)


def atomic_write_json(path: str, doc) -> None:
    """Write a JSON document via temp-then-rename so a concurrent
    reader (Perfetto, a scraper, the merge) never sees a torn file —
    the one crash-safe write path shared by the trace ring, the
    collected per-host rings, and the merged fleet trace."""
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class Tracer:
    """Thread-safe bounded span ring. ``record`` is only ever called from
    instrumentation points that already checked the tracer exists, so the
    off path costs nothing; the on path takes one short lock per span."""

    def __init__(self, path: str, sample: float = 1.0,
                 max_events: int = DEFAULT_RING_EVENTS,
                 rank_offset: int = 0):
        self.path = path
        self.sample = min(max(sample, 0.0), 1.0)
        self.rank_offset = rank_offset
        self._cap = max(int(max_events), 1)
        self._ring: "list" = [None] * self._cap
        self._idx = 0
        self.num_recorded = 0
        self.num_overwritten = 0
        # spans rejected by --tracesample (never entered the ring);
        # num_dropped = these + overwritten, so a sampled trace is
        # honest about everything it lost (TraceDropped in JSON,
        # trace_events_dropped_total on /metrics)
        self.num_sampled_out = 0
        self._lock = threading.Lock()
        self._rng = random.Random(0xe1be0 + rank_offset)
        self._t0_ns = time.perf_counter_ns()
        # wall-clock anchor captured at the SAME instant as the
        # perf-counter epoch: an event at trace-ts T usec happened at
        # wall time wall_anchor_usec + T on THIS host's clock — the
        # hook the fleet merge (telemetry/tracefleet.py) aligns
        # per-host files through after subtracting the estimated
        # per-host clock offset
        self.wall_anchor_usec = time.time_ns() // 1000
        # fleet-tracing metadata merged into write()'s otherData: the
        # run trace id (master-minted, echoed by services) and — on
        # collected per-host files — the master-estimated clock offset
        self.extra_other_data: "dict" = {}
        # per-op span ids: (rank, seq), unique over the ring's run
        self.op_seqs = itertools.count(1)

    # -- recording -----------------------------------------------------------

    def now_ns(self) -> int:
        return time.perf_counter_ns()

    def record(self, name: str, cat: str, start_ns: int, dur_usec: int,
               rank: int = 0, sampled: bool = False, **args) -> None:
        """One complete span. ``start_ns`` is a perf_counter_ns timestamp;
        ``sampled=True`` subjects the span to --tracesample (op spans and
        the per-op tpu/stream sub-spans — anything with per-op volume);
        phase markers pass sampled=False and are always kept."""
        if sampled and self.sample < 1.0 \
                and self._rng.random() >= self.sample:
            self.num_sampled_out += 1
            return
        event = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": max(start_ns - self._t0_ns, 0) // 1000,
            "dur": max(int(dur_usec), 0),
            "pid": self.rank_offset,
            "tid": rank,
            "args": args,
        }
        self._push(event)

    def _push(self, event: dict) -> None:
        with self._lock:
            slot = self._idx % self._cap
            if self._ring[slot] is not None:
                self.num_overwritten += 1
            self._ring[slot] = event
            self._idx += 1
            self.num_recorded += 1

    def to_trace_ts(self, start_ns: int) -> int:
        return max(start_ns - self._t0_ns, 0) // 1000

    def record_rpc(self, name: str, start_ns: int, dur_usec: int,
                   rank: int, flow_id: int, side: str) -> None:
        """One control-plane RPC edge end: a complete span PLUS the bound
        Chrome flow event that lets Perfetto draw the master->service
        arrow. ``side`` is "out" (master sent the request; flow start
        "s") or "in" (service handled it; flow finish "f"/bp=e). The
        flow event's ts sits at the span start so it binds to the span
        it is emitted with. Never sampled: RPC volume is per-phase, not
        per-op."""
        ts = self.to_trace_ts(start_ns)
        self.record(name, "rpc", start_ns, dur_usec, rank=rank,
                    flow=flow_id)
        flow = {
            "name": "rpc", "cat": "rpc",
            "ph": "s" if side == "out" else "f",
            "id": flow_id, "ts": ts,
            "pid": self.rank_offset, "tid": rank,
        }
        if side != "out":
            flow["bp"] = "e"  # bind to the enclosing slice
        self._push(flow)

    def record_op(self, op: str, phase: str, start_ns: int, dur_usec: int,
                  rank: int, offset: int, size: int,
                  slot: "int | None" = None, op_id=None) -> None:
        """Storage-op span (phase, rank, op type, offset, size, latency,
        staging slot, and the op id its stage spans share). Subject to
        --tracesample."""
        args = {"phase": phase, "offset": offset, "size": size}
        if slot is not None:
            args["slot"] = slot
        if op_id is not None:
            args["op"] = op_id
        self.record(op, "io", start_ns, dur_usec, rank=rank, sampled=True,
                    **args)

    @property
    def num_dropped(self) -> int:
        """Spans this trace LOST: sampled out by --tracesample plus
        overwritten in the ring before a write."""
        return self.num_sampled_out + self.num_overwritten

    # -- output --------------------------------------------------------------

    def snapshot_events(self) -> "list[dict]":
        """Chronological copy of the ring (oldest first)."""
        with self._lock:
            if self._idx <= self._cap:
                events = [e for e in self._ring[:self._idx]]
            else:
                head = self._idx % self._cap
                events = self._ring[head:] + self._ring[:head]
            return [e for e in events if e is not None]

    def write(self) -> None:
        """(Re)write the Chrome trace JSON file with everything recorded
        so far. Idempotent; called at phase end and at teardown so a
        killed run still leaves a loadable trace. Atomic via
        temp-then-rename so a scraper/Perfetto never reads a torn file."""
        events = self.snapshot_events()
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "tool": "elbencho-tpu",
                "rankOffset": self.rank_offset,
                "sample": self.sample,
                "numRecorded": self.num_recorded,
                "numOverwritten": self.num_overwritten,
                "numSampledOut": self.num_sampled_out,
                "numDropped": self.num_dropped,
                "wallAnchorUsec": self.wall_anchor_usec,
                **self.extra_other_data,
            },
        }
        atomic_write_json(self.path, doc)


class StageSpans:
    """One worker's per-phase sink for the stage spans of the
    storage->HBM path: the Tracer ring (``--tracefile``), the JAX
    profiler (a session recording at phase start: ``--tpuprofile``, the
    benchmark's traced window), or both.

    Stages are timed by the caller's own perf_counter_ns stamps, the ones
    that feed the always-on stage counters, so a span covers exactly the
    interval its counter books. ``begin`` opens a profiler annotation on
    the calling thread; only leaf stages that wrap one blocking call are
    opened there, so the device trace's idle gaps are named by what the
    host was doing, never by an umbrella over it. Every span of one op
    carries the op's id (``op`` in the ring's args); the op's root span
    ``op`` runs from its storage submit to its last stage."""

    def __init__(self, ring: "Tracer | None", rank: int, profiler: bool):
        self.ring = ring
        self.rank = rank
        self._annotation = None
        if profiler:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    def next_op(self) -> "tuple[int, int] | None":
        """A fresh op id, (rank, seq); None when only the profiler
        records (its annotations carry names alone)."""
        if self.ring is None:
            return None
        return (self.rank, next(self.ring.op_seqs))

    def begin(self, name: str):
        """Open ``name`` on the profiler's timeline; the token for
        ``end`` (None when only the ring records)."""
        if self._annotation is None:
            return None
        token = self._annotation(name)
        token.__enter__()
        return token

    def end(self, token, name: str, cat: str, start_ns: int, end_ns: int,
            op=None, **args) -> None:
        """Close a stage opened by ``begin`` and record it in the ring."""
        if token is not None:
            token.__exit__(None, None, None)
        self.record(name, cat, start_ns, end_ns, op, **args)

    def record(self, name: str, cat: str, start_ns: int, end_ns: int,
               op=None, **args) -> None:
        """A span in the ring only: stages that enclose other ops' work
        (``reap_queue``), retroactive ones and the op's root span."""
        if self.ring is None:
            return
        if op is not None:
            args["op"] = op
        self.ring.record(name, cat, start_ns, (end_ns - start_ns) // 1000,
                         rank=self.rank, sampled=True, **args)


def phase_stage_spans(ring: "Tracer | None", rank: int,
                      device_path: bool) -> "StageSpans | None":
    """The phase's stage-span handle, or None when neither --tracefile
    nor a recording profiler wants the spans: every hook on the hot path
    is then one ``is None`` test. The profiler is asked only on the
    device path (a TPU context exists, so JAX is already loaded)."""
    profiler = False
    if device_path:
        from jax.profiler import TraceAnnotation
        profiler = bool(TraceAnnotation.is_enabled())
    if ring is None and not profiler:
        return None
    return StageSpans(ring, rank, profiler)


def make_tracer(cfg) -> "Tracer | None":
    """The single arming point: a Tracer exists iff --tracefile was given
    (instrumentation stays no-op otherwise)."""
    path = getattr(cfg, "trace_file_path", "")
    if not path:
        return None
    return Tracer(path,
                  sample=getattr(cfg, "trace_sample", 1.0),
                  rank_offset=getattr(cfg, "rank_offset", 0))
