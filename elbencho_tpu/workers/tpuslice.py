"""TPUSLICE: pod-slice sharded ingest + ICI redistribution phase.

The step from "TPU benchmark" to "pod-slice benchmark" (ROADMAP item 2):
where TPUBENCH moves synthetic bytes and the --tpuids read path feeds ONE
chip per worker, this phase runs the data plane of a sharded-checkpoint
restore as one composable benchmark:

  stripe s of the dataset          (file/bdev paths, striped by chip)
    -> every worker reads its chips' shards off storage
       (StagingPool slots; the fused --tpustream ring where eligible)
    -> host->HBM DMA through the worker's TransferPipeline
       (one shard per chip of the mesh, P(("host","chip")) layout)
    -> ICI redistribution of the assembled stripe to --redistspec
       (jitted sharding change; parallel/slice_phase.SliceRunner)
    -> on-device fingerprint verify against the host bytes

with stripe s+1's storage ingest OVERLAPPING stripe s's ICI
redistribution: the driver dispatches the redistribution asynchronously
and only completes it after the next stripe's shards are read, so
storage, PCIe/DMA and ICI are all in flight together — the pipeline
shape real restores have.

Roles: every local worker is a FEEDER for the mesh devices
``WorkerManager.slice_shard_assignment`` gives it; the first local
worker is additionally the DRIVER that assembles stripes and runs the
SPMD steps (one SPMD program per process, like the collective patterns).

Counters (PATH_AUDIT_COUNTERS; auto-plumbed to JSON//metrics/traces):
ShardIngestMiB per feeder, IciRedistMiB/IciRedistUSec sums and the
IciGbpsHwm MAX on the driver. Redistribution records its own ``tpu_ici``
trace spans (--tracefile), giving the chart tool a redistribution lane.

Fault policy: a chip lost mid-phase ABORTS the phase loudly — a slice
stripe is one SPMD program over every chip, so the per-worker
--tpufallback chip/host failover of the single-chip paths cannot apply
(there is no "surviving subset" of an in-flight collective).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..phases import BenchPhase
from ..toolkits import logger
from .shared import WorkerException, WorkerInterruptedException

#: barrier poll interval; every wait slice re-checks interrupts
_WAIT_SLICE_SECS = 0.2


class SliceAbortError(WorkerException):
    """The slice phase failed on a sibling worker; carriers re-raise a
    quiet interrupt so only the original error reaches the report."""


class _SliceState:
    """Per-phase rendezvous shared by this process's workers: shard
    publication, host-fingerprint folding, and the feed/redistribute
    lockstep. Created lazily by the first worker entering the phase
    (keyed by the phase's bench UUID)."""

    def __init__(self, n_workers: int, n_devices: int):
        self.cond = threading.Condition()
        self.n_workers = n_workers
        self.n_devices = n_devices
        self.shards: "dict[int, object]" = {}
        self.host_sum = 0
        self.host_xor = 0
        self.published = 0
        self.consumed_stripe = -1  # last stripe the driver consumed
        self.failed: "Exception | None" = None

    def fail(self, err: Exception) -> None:
        with self.cond:
            if self.failed is None:
                self.failed = err
            self.cond.notify_all()

    def _check(self, worker) -> None:
        worker.check_interruption_flag_only()
        if self.failed is not None:
            raise SliceAbortError(
                f"slice phase aborted by a sibling worker: "
                f"{type(self.failed).__name__}: {self.failed}")

    def publish(self, worker, shards: "dict[int, object]",
                host_sum: int, host_xor: int) -> None:
        with self.cond:
            self._check(worker)
            self.shards.update(shards)
            self.host_sum = (self.host_sum + host_sum) & 0xFFFFFFFF
            self.host_xor ^= host_xor
            self.published += 1
            self.cond.notify_all()

    def wait_all_published(self, worker) -> "tuple[dict, int, int]":
        """Driver: block until every worker published its shards of the
        current stripe; returns (shards, host_sum, host_xor) and resets
        the slots for the next stripe."""
        with self.cond:
            while self.published < self.n_workers:
                self._check(worker)
                self.cond.wait(_WAIT_SLICE_SECS)
            self._check(worker)
            shards, s, x = self.shards, self.host_sum, self.host_xor
            self.shards = {}
            self.host_sum = 0
            self.host_xor = 0
            self.published = 0
            return shards, s, x

    def mark_consumed(self, stripe_idx: int) -> None:
        with self.cond:
            self.consumed_stripe = stripe_idx
            self.cond.notify_all()

    def wait_consumed(self, worker, stripe_idx: int) -> None:
        """Feeders: block until the driver consumed stripe_idx, keeping
        feed and redistribute in lockstep (at most one stripe of ingest
        ahead of the in-flight redistribution)."""
        with self.cond:
            while self.consumed_stripe < stripe_idx:
                self._check(worker)
                self.cond.wait(_WAIT_SLICE_SECS)
            self._check(worker)


def _get_state(shared, n_workers: int, n_devices: int) -> _SliceState:
    with shared.cond:
        st = getattr(shared, "slice_state", None)
        if st is None or st[0] != shared.bench_uuid:
            st = (shared.bench_uuid, _SliceState(n_workers, n_devices))
            shared.slice_state = st
        return st[1]


# ----------------------------------------------------------------------
# storage shard readers: plain preadv loop vs the fused native stream
# ----------------------------------------------------------------------

class _PreadShardReader:
    """Baseline reader: preadv into rotating staging-pool slots, per-op
    --ioretries via the worker's retrier (same classifier as the Python
    block loop)."""

    def __init__(self, worker, fds):
        self._worker = worker
        self._fds = fds
        self._slots = worker._staging_pool.views
        self._next = 0

    def read_block(self, fd_idx: int, offset: int,
                   length: int) -> "tuple[np.ndarray, int]":
        worker = self._worker
        slot = self._slots[self._next % len(self._slots)]
        self._next += 1

        def one_op():
            t0 = time.perf_counter_ns()
            n = os.preadv(self._fds[fd_idx], [slot[:length]], offset)
            if n != length:
                from .io_errors import ShortIOError
                raise ShortIOError(True, offset, n, length)
            return (time.perf_counter_ns() - t0) // 1000

        if worker._io_retrier is None:
            lat_usec = one_op()
        else:
            lat_usec = worker._io_retrier.run(
                one_op, path=worker._retry_path_hint())
        return (np.frombuffer(slot[:length], dtype=np.uint32), lat_usec)

    def close(self) -> None:
        pass


class _StreamShardReader:
    """Fused reader: the native engine's streaming ring keeps the shard
    reads of a stripe in flight over the registered staging slots
    (io_uring/AIO with the GIL released) while the feeder overlaps HBM
    DMA dispatch — the --tpustream ring reused for the slice phase.
    Reads are submitted for the WHOLE stripe up front (bounded by the
    slot count) and reaped in completion order."""

    def __init__(self, worker, fds, native):
        from ..utils.native import NativeStreamError
        pool = worker._staging_pool
        self._worker = worker
        self._slots = pool.views
        try:
            self._stream = native.open_stream(
                fds, pool.slot_addrs, max(worker.cfg.block_size, 1),
                pool=None if pool.broken else pool.native_pool)
        except NativeStreamError as err:
            raise _StreamUnavailable(str(err)) from err
        if worker.cfg.io_timeout_secs:
            self._stream.set_timeout(
                worker.cfg.io_timeout_secs * 1_000_000)
        self.backend_name = self._stream.backend_name
        self.pooled = self._stream.pooled

    def read_blocks(self, ops: "list[tuple[int, int, int]]"):
        """ops: [(fd_idx, offset, length)] — submit up to slot-count
        reads, yield (op_index, np.uint32 view, lat_usec) in completion
        order. The yielded view is only valid until the slot is
        re-submitted; callers must consume (device_put) before the next
        yield loop iteration submits more."""
        worker = self._worker
        free = list(range(len(self._slots)))
        slot_op: "dict[int, int]" = {}
        next_op = 0
        while next_op < len(ops) or slot_op:
            worker.check_interruption_request(force=True)
            while free and next_op < len(ops):
                slot = free.pop()
                fd_idx, off, length = ops[next_op]
                self._stream.submit(slot, fd_idx, off, length, False)
                slot_op[slot] = next_op
                next_op += 1
            st = worker._stages
            token = st.begin("stream_reap") if st is not None else None
            t0 = time.perf_counter_ns() if st is not None else 0
            events = self._stream.reap(
                min_complete=1, timeout_msecs=1000,
                interrupt_flag=worker._native_interrupt)
            if st is not None:
                st.end(token, "stream_reap", "stream", t0,
                       time.perf_counter_ns(), events=len(events),
                       min_complete=1)
            for slot, lat_usec, res in events:
                op_idx = slot_op.pop(slot)
                fd_idx, off, length = ops[op_idx]
                if res != length:
                    if res < 0:
                        raise WorkerException(
                            f"slice shard read failed at offset {off}: "
                            f"{os.strerror(-res)}")
                    from .io_errors import ShortIOError
                    raise WorkerException(
                        str(ShortIOError(True, off, max(res, 0), length)))
                view = np.frombuffer(self._slots[slot][:length],
                                     dtype=np.uint32)
                yield op_idx, view, lat_usec
                free.append(slot)

    def close(self) -> None:
        if self._stream.close() != 0:
            self._worker._stream_drain_failed = True
            logger.log_error(
                f"worker {self._worker.rank}: slice stream ring drain "
                f"failed; keeping I/O buffers mapped until process exit")


class _StreamUnavailable(Exception):
    """Stream ring could not be opened; feeder falls back to preadv."""


def _stream_blocker(worker) -> "str | None":
    """Why the fused ring cannot serve the slice feeder (None =
    eligible); mirrors LocalWorker._tpu_stream_blocker for the features
    the slice reader supports."""
    from ..utils.native import get_native_engine
    cfg = worker.cfg
    if cfg.tpu_stream == "off":
        return "--tpustream off"
    native = get_native_engine()
    if native is None:
        return "native ioengine unavailable"
    if not native.stream_supported():
        return "kernel lacks both io_uring and AIO"
    if worker._ops_log is not None:
        return "--opslog per-op records"
    if worker._rate_limiter_read or worker._rate_limiter_write:
        return "per-op rate limits"
    if worker._io_retrier is not None:
        return "--ioretries per-op retry (slice ring has no re-arm)"
    return None


# ----------------------------------------------------------------------
# the phase
# ----------------------------------------------------------------------

def run_tpu_slice_phase(worker, phase: BenchPhase) -> None:
    """Entry point from LocalWorker._dispatch_phase_inner."""
    from ..tpu.device import is_device_loss_error
    try:
        _run_slice_phase_inner(worker, phase)
    except (WorkerInterruptedException, WorkerException):
        raise
    except Exception as err:  # noqa: BLE001 - classified below
        if is_device_loss_error(err):
            # a stripe is ONE SPMD program over every chip: the
            # per-worker --tpufallback failover of the single-chip paths
            # cannot save an in-flight collective — abort loudly
            raise WorkerException(
                f"TPU chip lost during the --tpuslice phase "
                f"({type(err).__name__}: {err}); slice phases abort on "
                f"chip loss (--tpufallback does not apply to SPMD mesh "
                f"phases)") from err
        raise


def _run_slice_phase_inner(worker, phase: BenchPhase) -> None:
    # via _get_jax so the persistent compile cache is configured: slice
    # jits are the most expensive in the repo and bench processes are
    # short-lived
    from ..tpu.device import _get_jax, device_stamp, tpu_devices
    jax = _get_jax()

    from .tpubench import _select_collective_devices

    cfg = worker.cfg
    n_local = max(1, cfg.num_threads)
    local_rank = worker.rank % n_local
    is_driver = local_rank == 0

    devices = _select_collective_devices(cfg, jax)
    worker.tpu_device_stamp = device_stamp(tpu_devices(), devices[0])
    state = _get_state(worker.shared, n_local, len(devices))
    try:
        _run_slice_phase_guarded(worker, state, devices, is_driver,
                                 local_rank, n_local)
    except (SliceAbortError, WorkerInterruptedException):
        raise
    except BaseException as err:
        state.fail(err)  # wake siblings parked on the barrier
        raise


def _run_slice_phase_guarded(worker, state, devices, is_driver,
                             local_rank, n_local) -> None:
    from ..parallel.mesh import (MeshShapeError, make_ingest_mesh,
                                 parse_mesh_shape)
    from ..parallel.slice_phase import SliceRunner, host_fingerprint
    from ..tpu.device import TransferPipeline

    cfg = worker.cfg
    n_dev = len(devices)
    bs = cfg.block_size
    if bs % 4:
        raise WorkerException(
            "--tpuslice shards are uint32 arrays: --block must be a "
            "multiple of 4 bytes")

    # dataset geometry: file/bdev mode, one file of file_size per path,
    # striped by chip — stripe s places block (s, d) on mesh device d at
    # dataset offset s*stripe_bytes + d*block_size
    fds = worker._path_fds
    if not fds:
        raise WorkerException(
            "--tpuslice requires file/blockdev bench paths (no open "
            "path fds; directory-tree paths are not striped over chips)")
    dataset_bytes = cfg.file_size * len(fds)
    stripe_bytes = n_dev * bs
    n_stripes = dataset_bytes // stripe_bytes
    if n_stripes == 0:
        raise WorkerException(
            f"--tpuslice dataset too small: {dataset_bytes} bytes is "
            f"less than one stripe ({n_dev} devices x {bs} block bytes "
            f"= {stripe_bytes})")
    trimmed = dataset_bytes - n_stripes * stripe_bytes
    if trimmed and is_driver:
        logger.log(logger.LOG_NORMAL,
                   f"NOTE: --tpuslice dataset trimmed to "
                   f"{n_stripes * stripe_bytes} bytes ({n_stripes} "
                   f"stripes of {stripe_bytes}); the trailing {trimmed} "
                   f"bytes do not fill a whole stripe")

    # per-chip rank->shard assignment (manager owns the rank math).
    # Feeders only ever place shards on ADDRESSABLE devices: in a
    # multi-host runtime each process feeds its own chips and jax
    # stitches the global stripe from every process's local shards —
    # exactly how a real restore stripes a pod.
    import jax

    from .manager import WorkerManager
    proc = jax.process_index()
    local_dev_indices = [i for i, dev in enumerate(devices)
                         if dev.process_index == proc]
    if not local_dev_indices:
        raise WorkerException(
            "--tpuslice: this process addresses no device of the mesh")
    picks = WorkerManager.slice_shard_assignment(
        len(local_dev_indices), n_local, local_rank)
    my_devices = [local_dev_indices[k] for k in picks]
    worker.got_phase_work = bool(my_devices) or is_driver

    # the driver builds the mesh + jitted steps; feeders only need their
    # device handles. Compiles land OUTSIDE the timed loop via warmup().
    runner = None
    if is_driver:
        shape = None
        if cfg.mesh_shape_str:
            shape = parse_mesh_shape(cfg.mesh_shape_str)
        try:
            mesh = make_ingest_mesh(devices, shape=shape)
        except MeshShapeError as err:
            raise WorkerException(str(err)) from None
        try:
            runner = SliceRunner(mesh, cfg.redist_spec or "alltoall",
                                 bs // 4)
        except ValueError as err:
            raise WorkerException(str(err)) from None
        runner.warmup()
        logger.log(logger.LOG_VERBOSE,
                   f"slice mesh {mesh.devices.shape[0]}x"
                   f"{mesh.devices.shape[1]}, {n_stripes} stripes, "
                   f"redistspec {cfg.redist_spec or 'alltoall'}")

    # per-worker transfer pipeline: HBM ingest accounting + --tpubudget,
    # the same split dispatch-vs-DMA discipline as the single-chip path
    depth = min(max(cfg.tpu_depth or cfg.io_depth, 1),
                max(len(worker._staging_pool.views), 1))
    pipeline = TransferPipeline(depth,
                                budget_usec=cfg.tpu_dispatch_budget_usec)
    pipeline.stages = worker._stages

    # storage reader: fused native-stream ring where eligible, else the
    # preadv loop — logged once per phase like the single-chip path
    reader = None
    stream_reader = None
    blocker = _stream_blocker(worker)
    if blocker is None:
        from ..utils.native import get_native_engine
        try:
            stream_reader = _StreamShardReader(worker, fds,
                                               get_native_engine())
            if is_driver:
                logger.log(logger.LOG_NORMAL,
                           f"slice ingest ring engaged (backend="
                           f"{stream_reader.backend_name}"
                           + (", pool-registered"
                              if stream_reader.pooled else "") + ")")
        except _StreamUnavailable as err:
            blocker = f"stream ring setup failed ({err})"
    if stream_reader is None:
        if cfg.tpu_stream == "on":
            raise WorkerException(
                f"--tpustream on: fused slice ingest ring unavailable "
                f"({blocker})")
        if is_driver and cfg.tpu_stream != "off":
            logger.log(logger.LOG_NORMAL,
                       f"NOTE: fused slice ingest ineligible ({blocker}); "
                       f"using the preadv loop")
        reader = _PreadShardReader(worker, fds)

    pending = None  # in-flight redistribution of the previous stripe
    per_chip: "dict[int, int]" = {}
    try:
        for s in range(n_stripes):
            shards, host_sum, host_xor = _ingest_stripe(
                worker, s, my_devices, devices, fds, stripe_bytes, bs,
                cfg.file_size, pipeline, reader, stream_reader,
                host_fingerprint, per_chip)
            state.publish(worker, shards, host_sum, host_xor)
            if is_driver:
                all_shards, stripe_sum, stripe_xor = \
                    state.wait_all_published(worker)
                global_arr = runner.assemble(all_shards)
                if pending is not None:
                    # stripe s-1's ICI ran while stripe s was read off
                    # storage — the overlap this phase exists to measure
                    _complete_redistribution(worker, runner, pipeline,
                                             pending)
                pending = _launch_redistribution(worker, runner, pipeline,
                                                global_arr, s,
                                                stripe_sum, stripe_xor)
                state.mark_consumed(s)
            else:
                state.wait_consumed(worker, s)
        if is_driver and pending is not None:
            _complete_redistribution(worker, runner, pipeline, pending)
    finally:
        if stream_reader is not None:
            stream_reader.close()
        elif reader is not None:
            reader.close()
        # drain the transfer ring; --tpubudget covers ingest dispatch +
        # the driver's SPMD dispatch cost — but only on the clean path:
        # a budget breach must never mask the in-flight abort cause
        import sys as _sys
        pipeline.flush(check_budget=_sys.exc_info()[0] is None)
        worker.tpu_dispatch_usec = pipeline.dispatch_usec
        worker.tpu_transfer_usec = pipeline.transfer_usec
        if worker._tpu is None and per_chip:
            # per-chip rows for workers without a single-chip context
            # (statistics reads tpu_per_chip when _tpu is None)
            worker.tpu_per_chip = {c: (b, 0) for c, b in per_chip.items()}


def _ingest_stripe(worker, stripe_idx, my_devices, devices, fds,
                   stripe_bytes, bs, file_size, pipeline, reader,
                   stream_reader, host_fingerprint, per_chip):
    """Read this worker's shards of one stripe and place each onto its
    mesh device through the transfer pipeline. Returns
    ({device_idx: shard array}, host_sum, host_xor)."""
    import jax

    shards: "dict[int, object]" = {}
    host_sum = 0
    host_xor = 0
    ops = []
    for d in my_devices:
        off = stripe_idx * stripe_bytes + d * bs
        ops.append((off // file_size, off % file_size, bs))

    def place(op_idx, view, lat_usec):
        nonlocal host_sum, host_xor
        d = my_devices[op_idx]
        s, x = host_fingerprint(view)
        # an OWNED copy, never the slot view: jax's CPU backend may
        # zero-copy alias an aligned host buffer on device_put, and the
        # slice phase recycles slots for stripe s+1 while stripe s is
        # still in flight on the mesh — an aliased shard would mutate
        # under the running redistribution (caught by the fingerprint
        # verify when it bit). Shard rows are (1, words) so assembly is
        # a pure layout map.
        block = np.array(view.reshape(1, -1))
        arr = pipeline.submit(
            lambda: jax.device_put(block, devices[d]))
        shards[d] = arr
        host_sum = (host_sum + s) & 0xFFFFFFFF
        host_xor ^= x
        worker.iops_latency_histo.add_latency(lat_usec)
        worker.live_ops.num_bytes_done += bs
        worker.live_ops.num_iops_done += 1
        worker.tpu_transfer_bytes += bs
        worker._shard_ingest_bytes += bs
        worker.shard_ingest_mib = worker._shard_ingest_bytes >> 20
        per_chip[d] = per_chip.get(d, 0) + bs
        if worker._staging_pool is not None:
            worker._staging_pool.account_ops(1)

    if stream_reader is not None:
        for op_idx, view, lat_usec in stream_reader.read_blocks(ops):
            place(op_idx, view, lat_usec)
    else:
        for op_idx, (fd_idx, off, length) in enumerate(ops):
            worker.check_interruption_request(force=True)
            view, lat_usec = reader.read_block(fd_idx, off, length)
            place(op_idx, view, lat_usec)
    return shards, host_sum, host_xor


def _launch_redistribution(worker, runner, pipeline, global_arr,
                           stripe_idx, host_sum, host_xor) -> dict:
    handle = runner.launch(global_arr)
    # the SPMD dispatch cost rides the pipeline's budget accounting so
    # --tpubudget bounds the slice phase's host-side overhead too
    pipeline.note_dispatch(handle["dispatch_usec"])
    handle["stripe_idx"] = stripe_idx
    handle["host_sum"] = host_sum
    handle["host_xor"] = host_xor
    return handle


def _complete_redistribution(worker, runner, pipeline, handle) -> None:
    import jax

    from ..parallel.slice_phase import SliceFingerprintError

    dev_sum, dev_xor, usec = runner.complete(handle)
    stripe_bytes = runner.stripe_bytes
    if jax.process_count() == 1:
        # fingerprint-exact verify: only a single-process driver saw the
        # host bytes of EVERY shard; multi-host runs verify on-device
        # consistency implicitly via the replicated fingerprint
        try:
            runner.verify(dev_sum, dev_xor, handle["host_sum"],
                          handle["host_xor"], handle["stripe_idx"])
        except SliceFingerprintError as err:
            raise WorkerException(str(err)) from None
    from ..tpu.device import fold_fingerprint
    fold_fingerprint(worker, dev_sum, dev_xor)
    worker._ici_redist_bytes += stripe_bytes
    worker.ici_redist_mib = worker._ici_redist_bytes >> 20
    worker.ici_redist_usec += usec
    gbps = round(stripe_bytes * 8 / (usec * 1000), 3)
    worker.ici_gbps_hwm = max(worker.ici_gbps_hwm, gbps)
    worker.live_ops.num_entries_done += 1  # one stripe redistributed
    worker.entries_latency_histo.add_latency(usec)
    if worker._tracer is not None:
        # the redistribution's own sub-span lane (chart: tpu_ici lane)
        worker._tracer.record(
            "tpu_ici", "tpu_ici", handle["t_submit_ns"], usec,
            rank=worker.rank, sampled=True,
            stripe=handle["stripe_idx"], bytes=stripe_bytes,
            spec=runner.redist_spec)
