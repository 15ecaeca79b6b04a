"""LocalWorker: one I/O worker thread — the workload engine's heart.

Reference: source/workers/LocalWorker.{h,cpp} (8.5 kLoC) — per-phase re-init
of function pointers + offset generator (initPhaseFunctionPointers
:1210-1379), the giant phase dispatch in run() (:193-418), dir-mode
iteration with the deterministic namespace ``r<rank>/d<dir>/r<rank>-f<file>``
(:3097), file/bdev striping (:3511-3769), the sync hot loop rwBlockSized
(:1702-1814), integrity verify (:2124-2212), block variance refill (:2242),
rwmix per-op split (:1741), sync/dropcaches (:8075/:8118).

The TPU data path replaces the reference's CUDA staging (allocGPUIOBuffer
:1427-1537, cudaMemcpy wrappers :2437-2490, cuFile wrappers :2633-2749):
workers map to TPU chips by ``rank % len(tpu_ids)`` (as the reference does
for GPUs, :1444) and stage blocks into HBM via PjRt transfers — see
elbencho_tpu/tpu/device.py. The function seam (func_positional_read/write +
tpu pre/post hooks) is kept so the C++ ioengine and the TPU path plug into
the same spots.
"""

from __future__ import annotations

import errno
import mmap
import os
import stat as stat_mod
import time

import numpy as np

from ..phases import BenchMode, BenchPathType, BenchPhase, phase_name
from ..toolkits import logger
from ..toolkits.file_tk import FileRangeLock
from ..toolkits.offset_gen import (OffsetGenRandom, OffsetGenRandomAligned,
                                   OffsetGenRandomAlignedFullCoverage,
                                   OffsetGenReverseSeq, OffsetGenSequential,
                                   OffsetGenStrided)
from ..toolkits.random_algos import create_rand_algo
from ..toolkits.rate_limiter import RateLimiter
from .base import Worker
from .shared import WorkerException, WorkerInterruptedException

MKFILE_MODE = 0o644  # reference: MKFILE_MODE, Common.h:96
MKDIR_MODE = 0o755

class LocalWorker(Worker):
    def __init__(self, shared, rank: int):
        super().__init__(shared, rank)
        self.cfg = shared.config
        # io_depth staging slots so async/pipelined paths never overwrite
        # a block still in flight (reference: allocIOBuffer x iodepth,
        # :1386). All slots come from the unified staging pool
        # (utils/staging_pool.py) — one allocator owns the hugepage/NUMA/
        # registration lifecycle for every data path.
        self._staging_pool = None
        self._io_bufs: "list[memoryview]" = []
        self._io_buf: "memoryview | None" = None
        self._own_path_fds: "list[int]" = []
        self._path_fds: "list[int]" = []
        self._rand_offset_algo = None
        self._block_var_algo = None
        self._rate_limiter_read: "RateLimiter | None" = None
        self._rate_limiter_write: "RateLimiter | None" = None
        self._tpu = None           # TpuWorkerContext when --tpuids given
        self._numa_zone = None     # set when --zones bound this worker
        # --tpuslice: per-chip ingest bytes of a context-less mesh feeder
        # (statistics reads this when _tpu is None, the RemoteWorker idiom)
        self.tpu_per_chip: "dict[int, tuple[int, int]]" = {}
        self._ops_log = None
        self._num_iops_submitted = 0  # rwmix modulo counter
        self._prepared = False
        self._stream_mode_logged = False  # once-per-phase fused-loop note
        self._stream_drain_failed = False  # aborted ring drain: leak bufs
        self._io_retrier = None        # --ioretries (workers/io_errors.py)
        self._tolerate_note_logged = False  # partial-dataset delete note
        # --slowops: the entry path the CURRENT block loop works on, so a
        # captured tail op can name its file (dir mode sets it per file;
        # file/bdev mode falls back to the first bench path)
        self._slowop_path = ""
        # the phase's stage-span handle (telemetry/tracer.py StageSpans):
        # None unless --tracefile or a recording profiler wants the spans
        self._stages = None
        import ctypes
        self._native_interrupt = ctypes.c_int(0)  # seen by the C++ engine

    def interrupt_execution(self) -> None:
        super().interrupt_execution()
        self._native_interrupt.value = 1

    def reset_stats(self) -> None:
        super().reset_stats()
        self._native_interrupt.value = 0
        self.tpu_per_chip = {}
        self._stream_mode_logged = False  # log the mode once per phase
        self._tolerate_note_logged = False
        self._slowop_path = ""  # re-resolved by the phase's entry loop
        if self._io_retrier is not None:
            self._io_retrier.reset()  # per-phase backoff budget
        if self._tpu is not None:
            # path-audit counters are per-phase, like tpu_transfer_bytes
            self._tpu.reset_path_counters()
        if self._staging_pool is not None:
            # pool audit counters are per-phase; the POOL persists
            self._staging_pool.reset_counters()
        # stage spans are armed per phase: the profiler sink only when a
        # profiler session records now (--tpuprofile, a traced window)
        from ..telemetry.tracer import phase_stage_spans
        self._stages = phase_stage_spans(self._tracer, self.rank,
                                         device_path=self._tpu is not None)
        if self._tpu is not None:
            self._tpu.set_stages(self._stages)

    def finish_phase_stats(self) -> None:
        if self._tpu is not None:
            # a phase cut by --timelimit leaves transfers in flight and
            # on-device verifies pending: complete them, so that each H2D
            # op of the phase books its ready wait and resident latency in
            # this phase's record, and a bad block fails the phase
            self._tpu_guarded(self._tpu.drain_to, 0)
            self._book_resident()
        super().finish_phase_stats()

    def _book_resident(self) -> None:
        """Move the context's op-resident latencies into the histogram,
        in bulk."""
        vals = self._tpu.take_resident_usec()
        if vals:
            self.op_resident_histo.add_latencies_array(
                np.frombuffer(vals, dtype=np.uint64))

    # ------------------------------------------------------------------
    # preparation (reference: preparePhase, LocalWorker.cpp:424)
    # ------------------------------------------------------------------

    def prepare(self) -> None:
        cfg = self.cfg
        self._apply_core_binding()
        if cfg.file_size > 0 or cfg.tree_file_path \
                or cfg.bench_mode in (BenchMode.NETBENCH, BenchMode.S3):
            self._alloc_io_buffer()
        self._s3_client = None  # created lazily by workers/s3_worker.py
        if cfg.tpu_multihost and (cfg.tpu_ids or cfg.run_tpu_slice):
            # join the pod-wide runtime BEFORE first device use so jax
            # meshes span every host (idempotent + lock-safe across
            # concurrently-prepping worker threads and re-preps)
            from ..parallel.mesh import init_multihost
            init_multihost(cfg.tpu_multihost)
        if cfg.tpu_ids:
            from ..tpu.device import (TpuWorkerContext, device_stamp,
                                      tpu_devices)
            chip = cfg.tpu_ids[self.rank % len(cfg.tpu_ids)]
            # --tpudepth overrides the iodepth ride-along (the
            # reference's cuFile iodepth analogue). For every unbatched
            # transfer the depth is clamped to the host-buffer count: a
            # direct import aliases its host buffer and a staged
            # device_put reads it until the ring drains the transfer,
            # and buffer rotation only guarantees that when the ring is
            # no deeper than the rotation period. Only a staged --tpubatch
            # run is left unclamped: it copies each block into its own
            # aggregation ring (and --tpuverify turns batching off).
            depth = max(cfg.tpu_depth or cfg.io_depth, 1)
            unbatched = (cfg.use_tpu_direct or cfg.do_tpu_verify
                         or max(cfg.tpu_batch_blocks, 1) == 1)
            if unbatched and depth > max(cfg.io_depth, 1):
                if self.rank % max(1, cfg.num_threads) == 0:
                    logger.log(
                        logger.LOG_NORMAL,
                        f"NOTE: --tpudepth {depth} exceeds --iodepth "
                        f"{cfg.io_depth}; clamped to {max(cfg.io_depth, 1)} "
                        f"(a host buffer must not be rewritten before the "
                        f"transfer that reads it drained)")
                depth = max(cfg.io_depth, 1)
            self._tpu = TpuWorkerContext(
                chip_id=chip, block_size=cfg.block_size,
                direct=cfg.use_tpu_direct, verify_on_device=cfg.do_tpu_verify,
                pipeline_depth=depth,
                hbm_limit_pct=cfg.tpu_hbm_limit_pct,
                batch_blocks=max(cfg.tpu_batch_blocks, 1),
                dispatch_budget_usec=cfg.tpu_dispatch_budget_usec,
                staging_pool=self._staging_pool)
            self.tpu_device_stamp = device_stamp(tpu_devices(),
                                                 self._tpu.device)
            needs_fill = (cfg.run_create_files
                          or (cfg.run_tpu_bench
                              and cfg.tpu_bench_pattern in ("d2h", "both")))
            if needs_fill and not cfg.integrity_check_salt:
                self._tpu.warmup_fill()  # jit outside the timed phase
            needs_ingest = (cfg.run_read_files
                            or (cfg.run_tpu_bench
                                and cfg.tpu_bench_pattern in ("h2d",
                                                              "both")))
            if needs_ingest and not cfg.use_tpu_direct:
                # copy-step jit + donation probe outside the timed phase
                # (and outside the --tpubudget accounting). Skipped in
                # direct mode: its primary path never stages, and the
                # warmup would pin pipeline_depth full-size HBM staging
                # blocks in _slot_prev for the whole run — headroom
                # --tpuhbmpct exists to protect. (The direct->staged
                # fallback then jit-compiles lazily; that run is already
                # off its fast path and says so loudly.)
                self._tpu.warmup_transfer()
        if cfg.bench_path_type != BenchPathType.DIR \
                and cfg.bench_mode == BenchMode.POSIX:
            self._prepare_path_fds()
        if cfg.ops_log_path:
            from ..toolkits.ops_logger import OpsLogger
            self._ops_log = OpsLogger(cfg.ops_log_path, self.rank,
                                      use_lock=cfg.ops_log_lock)
        if cfg.bench_mode == BenchMode.NETBENCH:
            from .netbench import prepare_netbench
            prepare_netbench(self)  # cross-host connect/accept barrier
        self._rand_offset_algo = create_rand_algo(
            cfg.rand_offset_algo, seed=None)
        if cfg.block_variance_pct:
            self._block_var_algo = create_rand_algo(cfg.block_variance_algo)
        if cfg.limit_read_bps:
            self._rate_limiter_read = RateLimiter(cfg.limit_read_bps)
        if cfg.limit_write_bps:
            self._rate_limiter_write = RateLimiter(cfg.limit_write_bps)
        # --ioretries: per-op transient-error retry (None = exact
        # fail-fast parity; workers/io_errors.py)
        from .io_errors import make_io_retrier
        self._io_retrier = make_io_retrier(self)
        # native limiter windows (RateState x2: read, write); created once
        # per prepare and shared by this worker's phases — the exact
        # lifetime of the Python RateLimiter objects above
        import ctypes
        self._native_rl_state = (ctypes.c_uint64 * 4)()
        # load (and first time: build) the native engine here, OUTSIDE the
        # timed phase, so `make` never charges to a measured result
        from ..utils.native import get_native_engine
        native = get_native_engine()
        if cfg.io_engine != "auto":
            # explicitly requested engines must never silently fall back
            if native is None:
                raise WorkerException(
                    f"--ioengine {cfg.io_engine} requires the native "
                    f"ioengine (csrc/libioengine.so failed to build/load)")
            if cfg.io_engine == "uring" and not native.uring_supported():
                raise WorkerException(
                    "--ioengine uring: this kernel does not support "
                    "io_uring (compiled out or disabled via sysctl)")
        self._prepared = True

    def cleanup(self) -> None:
        for fd in self._own_path_fds:
            try:
                os.close(fd)
            except OSError:
                pass
        self._own_path_fds = []
        if getattr(self, "_s3_pipeline", None) is not None:
            self._s3_pipeline.close()
            self._s3_pipeline = None
        if self._tpu is not None:
            self._tpu.close()  # drop device arrays before buffer teardown
            self._tpu = None
        self._io_buf = None
        if self._staging_pool is not None:
            # ONE teardown for every staging buffer (io slots + TPU
            # aggregation aux slabs). A failed stream-ring drain leaks
            # the slab to process teardown inside the pool — kernel DMA
            # may still target it (the old gc.collect()-guarded mmap
            # dance and the module leak list both lived here).
            if getattr(self, "_stream_drain_failed", False):
                self._staging_pool.leak()
            self._staging_pool.close()
            self._staging_pool = None
        self._io_bufs = []
        if self._ops_log is not None:
            self._ops_log.close()
        if getattr(self, "_s3_client", None) is not None:
            # --s3single: the client is the process-wide singleton other
            # workers may still be using — only a per-worker client is
            # closed here (the singleton's sockets close on GC/rebuild)
            if not getattr(self.cfg, "use_s3_client_singleton", False):
                self._s3_client.close()
            self._s3_client = None
        if getattr(self, "_netbench_conns", None):
            from .netbench import cleanup_netbench
            cleanup_netbench(self)

    def _apply_core_binding(self) -> None:
        """Round-robin worker->core binding (reference: --cores/--zones via
        NumaTk; here sched_setaffinity, NUMA zones via utils/numa)."""
        cfg = self.cfg
        if cfg.cpu_cores_str:
            from ..toolkits.units import parse_uint_list
            cores = parse_uint_list(cfg.cpu_cores_str)
            if cores:
                core = cores[self.rank % len(cores)]
                try:
                    os.sched_setaffinity(0, {core})
                except OSError as err:
                    logger.log_error(f"core binding failed: {err}")
        elif cfg.numa_zones_str:
            from ..utils.numa import bind_to_numa_zone
            from ..toolkits.units import parse_uint_list
            zones = parse_uint_list(cfg.numa_zones_str)
            if zones:
                zone = zones[self.rank % len(zones)]
                # binds CPU affinity AND thread memory policy; the zone
                # is kept so _alloc_io_buffer can mbind the buffers too
                if bind_to_numa_zone(zone):
                    self._numa_zone = zone

    def _alloc_io_buffer(self) -> None:
        """One unified staging pool per worker, one slot per iodepth
        (replaces the reference's posix_memalign x iodepth,
        LocalWorker.cpp:1386-1401, AND this worker's former bespoke
        per-slot mmaps): hugepage-backed where available, O_DIRECT-
        aligned, NUMA-bound to the worker's --zones zone, and — where
        the kernel provides io_uring — registered ONCE as fixed buffers
        shared by the classic block loop and the streaming ring
        (--iosqpoll rides on the same ring). Slots are pre-filled with
        random data so writes aren't trivially compressible."""
        from ..utils.staging_pool import StagingPool
        cfg = self.cfg
        self._staging_pool = StagingPool(
            max(cfg.io_depth, 1), max(cfg.block_size, 1),
            numa_zone=self._numa_zone,
            fill_algo=create_rand_algo("fast", seed=self.rank + 1),
            madvise_flags=cfg.madvise_flags,
            register=cfg.pool_registration != "off",
            want_sqpoll=cfg.io_sqpoll,
            sqpoll_idle_ms=cfg.io_sqpoll_idle_ms,
            log_rank=self.rank)
        self._io_bufs = self._staging_pool.views
        self._io_buf = self._io_bufs[0]

    def _prepare_path_fds(self) -> None:
        """File/blockdev mode FDs. Shared FDs live in cfg.bench_path_fds
        (opened once by the WorkerManager); --nofdsharing makes each worker
        open its own (reference: prepareBenchPathFDsVec, ProgArgs.cpp:1981)."""
        cfg = self.cfg
        if cfg.bench_path_fds and not cfg.no_fd_sharing:
            self._path_fds = cfg.bench_path_fds
            return
        flags = os.O_RDWR
        if cfg.run_create_files or cfg.scenario_creates_files:
            flags |= os.O_CREAT
        if cfg.use_direct_io:
            flags |= os.O_DIRECT
        self._own_path_fds = [os.open(p, flags, MKFILE_MODE)
                              for p in cfg.paths]
        self._path_fds = self._own_path_fds

    # ------------------------------------------------------------------
    # phase loop (reference: LocalWorker::run, LocalWorker.cpp:193-418)
    # ------------------------------------------------------------------

    def run(self) -> None:
        self.prepare()
        # capture the current uuid BEFORE signalling prep-done: the
        # coordinator may start the first phase the moment the last worker
        # checks in, and we must notice that uuid change
        last_uuid = self.shared.bench_uuid
        self.shared.inc_num_workers_done()  # prep barrier
        try:
            while True:
                phase, last_uuid = self.shared.wait_for_phase_change(last_uuid)
                if phase == BenchPhase.TERMINATE:
                    return
                if phase == BenchPhase.IDLE:
                    continue
                self.reset_stats()
                try:
                    while True:
                        self._dispatch_phase(phase)
                        if not self.cfg.do_infinite_io_loop:
                            break
                        self.check_interruption_request(force=True)
                    self.finish_phase_stats()
                    self.shared.inc_num_workers_done()
                except WorkerInterruptedException:
                    self.finish_phase_stats()
                    self.shared.inc_num_workers_done()
                except Exception as err:  # noqa: BLE001
                    logger.log_error(
                        f"Worker {self.rank} phase "
                        f"{phase.name} failed: {type(err).__name__}: {err}")
                    self.shared.inc_num_workers_done_with_error(err)
        finally:
            self.cleanup()

    def _dispatch_phase(self, phase: BenchPhase) -> None:
        cfg = self.cfg
        self._num_iops_submitted = 0
        self._loader_pacer = self._make_loader_pacer(
            is_write=(phase != BenchPhase.READFILES))
        # --rwmixthr: the first N local ranks of a WRITE phase run the READ
        # workload instead, accounted as rwmix-read (reference: rwmix-threads
        # reader conversion, LocalWorker.cpp:1054-1062)
        if (phase == BenchPhase.CREATEFILES
                and cfg.num_rwmix_read_threads
                and (self.rank % max(1, cfg.num_threads))
                < cfg.num_rwmix_read_threads):
            self._run_as_rwmix_reader()
            return
        self._dispatch_phase_inner(phase)

    def _run_as_rwmix_reader(self) -> None:
        """Swap accounting to the rwmix-read counters, run the read
        workload, swap back."""
        def swap():
            self.live_ops, self.live_ops_rwmix_read = \
                self.live_ops_rwmix_read, self.live_ops
            self.iops_latency_histo, self.iops_latency_histo_rwmix = \
                self.iops_latency_histo_rwmix, self.iops_latency_histo
            self.entries_latency_histo, self.entries_latency_histo_rwmix = \
                self.entries_latency_histo_rwmix, self.entries_latency_histo

        swap()
        self._rwmix_thread_reader = True
        try:
            self._dispatch_phase_inner(BenchPhase.READFILES)
        finally:
            self._rwmix_thread_reader = False
            swap()

    def _dispatch_phase_inner(self, phase: BenchPhase) -> None:
        cfg = self.cfg
        if phase == BenchPhase.SYNC:
            self._any_mode_sync()
        elif phase == BenchPhase.DROPCACHES:
            self._any_mode_drop_caches()
        elif phase == BenchPhase.TPUBENCH:
            from .tpubench import run_tpubench_phase
            run_tpubench_phase(self, phase)
        elif phase == BenchPhase.TPUSLICE:
            from .tpuslice import run_tpu_slice_phase
            run_tpu_slice_phase(self, phase)
        elif cfg.bench_mode == BenchMode.S3:
            from .s3_worker import dispatch_s3_phase
            dispatch_s3_phase(self, phase)
        elif cfg.bench_mode == BenchMode.HDFS:
            from .hdfs_worker import dispatch_hdfs_phase
            dispatch_hdfs_phase(self, phase)
        elif cfg.bench_mode == BenchMode.NETBENCH:
            from .netbench import run_netbench_phase
            run_netbench_phase(self, phase)
        elif phase in (BenchPhase.CREATEDIRS, BenchPhase.DELETEDIRS,
                       BenchPhase.STATDIRS):
            self._dir_mode_iterate_dirs(phase)
        elif cfg.bench_path_type == BenchPathType.DIR:
            if cfg.tree_file_path:
                self._custom_tree_iterate_files(phase)
            else:
                self._dir_mode_iterate_files(phase)
        else:
            self._file_mode_phase(phase)

    # ------------------------------------------------------------------
    # dir mode (reference: dirModeIterateDirs :2811 / IterateFiles :3055)
    # ------------------------------------------------------------------

    @staticmethod
    def dir_rel_path_for(rank: int, dir_idx: int, dir_sharing: bool) -> str:
        """Namespace: "r<rank>/d<idx>", or shared "d<idx>" with --dirsharing
        (reference: LocalWorker.cpp:3097 + dirsharing)."""
        if dir_sharing:
            return f"d{dir_idx}"
        return f"r{rank}/d{dir_idx}"

    @staticmethod
    def file_rel_path_for(rank: int, dir_idx: int, file_idx: int,
                          dir_sharing: bool) -> str:
        base = LocalWorker.dir_rel_path_for(rank, dir_idx, dir_sharing)
        return f"{base}/r{rank}-f{file_idx}"

    def _dir_rel_path(self, dir_idx: int) -> str:
        return self.dir_rel_path_for(self.rank, dir_idx,
                                     self.cfg.do_dir_sharing)

    def _file_rel_path(self, dir_idx: int, file_idx: int) -> str:
        return self.file_rel_path_for(self.rank, dir_idx, file_idx,
                                      self.cfg.do_dir_sharing)

    def _bench_path_for_dir(self, dir_idx: int) -> str:
        """Round-robin dirs over bench paths (reference: :3110)."""
        paths = self.cfg.paths
        return paths[(self.rank + dir_idx) % len(paths)]

    def _dir_mode_iterate_dirs(self, phase: BenchPhase) -> None:
        cfg = self.cfg
        if cfg.do_dir_sharing and self.rank % cfg.num_threads != 0 \
                and phase != BenchPhase.STATDIRS:
            # with dirsharing only one local worker creates/deletes the
            # shared dirs (others would collide)
            self.got_phase_work = False
            return
        for dir_idx in range(cfg.num_dirs):
            self.check_interruption_request(force=True)
            base = self._bench_path_for_dir(dir_idx)
            rel = self._dir_rel_path(dir_idx)
            path = os.path.join(base, rel)
            with self.oplog(phase.name.lower(), path) as op_rec:
                t0 = time.perf_counter_ns()
                if phase == BenchPhase.CREATEDIRS:
                    os.makedirs(path, MKDIR_MODE, exist_ok=True)
                elif phase == BenchPhase.DELETEDIRS:
                    try:
                        os.rmdir(path)
                        parent = os.path.dirname(path)
                        if os.path.basename(parent).startswith("r"):
                            try:
                                os.rmdir(parent)  # remove empty rank dir
                            except OSError:
                                pass
                    except FileNotFoundError:
                        if not cfg.ignore_delete_errors \
                                and not self._partial_tolerance(phase):
                            raise
                        op_rec.error = True
                else:  # STATDIRS
                    os.stat(path)
                lat_usec = (time.perf_counter_ns() - t0) // 1000
            self.entries_latency_histo.add_latency(lat_usec)
            self.live_ops.num_entries_done += 1

    _NATIVE_FILE_OPS = {BenchPhase.CREATEFILES: "write",
                        BenchPhase.READFILES: "read",
                        BenchPhase.STATFILES: "stat",
                        BenchPhase.DELETEFILES: "unlink"}

    def _can_use_native_file_loop(self, native, phase: BenchPhase) -> bool:
        """The whole open->blocks->close per-file loop runs in C++ when no
        per-op Python feature is active (the LOSF hot path; reference:
        dirModeIterateFiles is native there by construction)."""
        cfg = self.cfg
        return (self._native_loop_eligible(native)
                and self._ops_log is None  # per-entry records stay Python
                and phase in self._NATIVE_FILE_OPS
                and cfg.io_engine in ("auto", "sync")
                and cfg.io_depth <= 1
                and not cfg.do_stat_inline
                and not cfg.do_prealloc_file
                and not cfg.do_truncate_to_size
                and not cfg.fadvise_flags
                and not cfg.use_mmap
                and not cfg.use_random_offsets
                and not cfg.do_reverse_seq_offsets
                # the native per-file loop generates its own sequential
                # offsets; the shuffle-window permutation feeds the
                # gen-driven loops instead
                and not cfg.shuffle_window)

    def _run_native_file_loop(self, native, phase: BenchPhase) -> None:
        """Chunked delegation of the per-file loop to the C++ engine."""
        cfg = self.cfg
        op = self._NATIVE_FILE_OPS[phase]
        if phase == BenchPhase.CREATEFILES:
            open_flags = self._open_flags_write()
        else:
            open_flags = os.O_RDONLY | (os.O_DIRECT if cfg.use_direct_io
                                        else 0)
        if op in ("write", "read") and cfg.file_size:
            # cap each native call at ~8192 blocks AND ~256 MiB of I/O so
            # live stats/stonewall snapshots stay fresh (same bounds as
            # _native_chunk_blocks)
            blocks_per_file = max(
                (cfg.file_size + cfg.block_size - 1) // cfg.block_size, 1)
            chunk = max(1, min(
                self._NATIVE_CHUNK_MAX_BLOCKS // blocks_per_file,
                self._NATIVE_CHUNK_MAX_BYTES // cfg.file_size))
        else:
            # stat/unlink: no block I/O, only path batching
            chunk = self._NATIVE_CHUNK_MAX_BLOCKS
        paths: "list[str]" = []
        from ..utils.native import NativeVerifyError

        def submit():
            self.check_interruption_request(force=True)

            def call(paths=paths):
                native.run_file_loop(
                    paths, op, open_flags, cfg.file_size, cfg.block_size,
                    # stat/unlink (and 0-byte files) never touch the buffer
                    buf_addr=self._buf_addr() if self._io_bufs else 0,
                    ignore_delete_errors=cfg.ignore_delete_errors
                    or self._partial_tolerance(phase),
                    worker=self, interrupt_flag=self._native_interrupt,
                    verify_salt=cfg.integrity_check_salt,
                    block_var_pct=cfg.block_variance_pct,
                    block_var_seed=self._block_var_seed(),
                    rwmix_pct=cfg.rwmix_read_pct
                    if phase == BenchPhase.CREATEFILES else 0,
                    limit_read_bps=cfg.limit_read_bps,
                    limit_write_bps=cfg.limit_write_bps,
                    rl_state=self._native_rl_state,
                    inline_readback=(cfg.do_read_inline
                                     or cfg.do_direct_verify),
                    flock_mode=self._flock_mode_code())

            try:
                # unlink chunks never retry: a re-run would ENOENT on the
                # files the first attempt already removed
                self._retrying_native(call, retryable=op != "unlink")
            except NativeVerifyError as err:
                bpf = max((cfg.file_size + cfg.block_size - 1)
                          // cfg.block_size, 1)
                file_off = (err.block_idx % bpf) * cfg.block_size \
                    + err.word_idx * 8
                raise WorkerException(
                    f"data integrity check failed at file offset "
                    f"{file_off} of {paths[err.block_idx // bpf]}: "
                    f"expected {err.want:#x}, got {err.got:#x}"
                    + self._verify_fail_hint(err.got)) from None
            except FileNotFoundError as err:
                if phase == BenchPhase.CREATEFILES \
                        and not cfg.run_create_dirs:
                    # parity hint (reference: dirModeOpenAndPrepFile :7395)
                    raise WorkerException(
                        "File create/open failed. Did you forget to enable "
                        "directory creation ('--mkdirs'/-d)?") from err
                raise

        for dir_idx in range(cfg.num_dirs):
            base = self._bench_path_for_dir(dir_idx)
            for file_idx in range(cfg.num_files):
                paths.append(os.path.join(
                    base, self._file_rel_path(dir_idx, file_idx)))
                if len(paths) >= chunk:
                    submit()
                    paths = []
        if paths:
            submit()

    def _dir_mode_iterate_files(self, phase: BenchPhase) -> None:
        """open -> [stat-inline] -> block loop -> close per file; entry
        latency histogram per file (reference: dirModeIterateFiles
        :3055-3281, unlinkat/fstatat for del/stat :3237-3249)."""
        cfg = self.cfg
        from ..utils.native import get_native_engine
        native = get_native_engine()
        if self._can_use_native_file_loop(native, phase):
            self._run_native_file_loop(native, phase)
            return
        for dir_idx in range(cfg.num_dirs):
            for file_idx in range(cfg.num_files):
                self.check_interruption_request(force=True)
                base = self._bench_path_for_dir(dir_idx)
                path = os.path.join(base,
                                    self._file_rel_path(dir_idx, file_idx))
                with self.oplog(phase.name.lower(), path) as op_rec:
                    t0 = time.perf_counter_ns()
                    if phase == BenchPhase.CREATEFILES:
                        self._write_one_file(path)
                    elif phase == BenchPhase.READFILES:
                        self._read_one_file(path)
                    elif phase == BenchPhase.STATFILES:
                        os.stat(path)
                    elif phase == BenchPhase.DELETEFILES:
                        try:
                            os.unlink(path)
                        except FileNotFoundError:
                            if not cfg.ignore_delete_errors \
                                    and not self._partial_tolerance(phase):
                                raise
                            op_rec.error = True
                    lat_usec = (time.perf_counter_ns() - t0) // 1000
                self.entries_latency_histo.add_latency(lat_usec)
                self.live_ops.num_entries_done += 1
                if self._tracer is not None:
                    self._tracer.record_op(
                        phase.name.lower(), phase_name(phase), t0,
                        lat_usec, self.rank, 0, cfg.file_size)
                if self._slowops is not None and phase in (
                        BenchPhase.STATFILES, BenchPhase.DELETEFILES):
                    # entry-granular phases: the whole entry IS the op
                    # (create/read capture per-block records inside
                    # _rw_block_sized instead)
                    self._slowops.record(
                        phase.name.lower(), phase_name(phase), lat_usec,
                        0, cfg.file_size, path=path, start_ns=t0)

    def _open_flags_write(self) -> int:
        cfg = self.cfg
        flags = os.O_WRONLY | os.O_CREAT
        if cfg.rwmix_read_pct or cfg.do_read_inline or cfg.do_direct_verify:
            flags = os.O_RDWR | os.O_CREAT
        if cfg.use_direct_io:
            flags |= os.O_DIRECT
        if cfg.do_truncate:
            flags |= os.O_TRUNC
        return flags

    def _write_one_file(self, path: str) -> None:
        cfg = self.cfg
        self._slowop_path = path  # --slowops: name the file in captures
        try:
            flags = self._open_flags_write()
            if cfg.use_mmap:
                # a writable mapping needs a read-write fd
                flags = (flags & ~os.O_WRONLY) | os.O_RDWR
            fd = os.open(path, flags, MKFILE_MODE)
        except FileNotFoundError as err:
            if not cfg.run_create_dirs:
                # parity hint (reference: dirModeOpenAndPrepFile :7395)
                raise WorkerException(
                    f"File create/open failed. Did you forget to enable "
                    f"directory creation ('--mkdirs'/-d)? Path: {path}"
                ) from err
            raise
        try:
            if cfg.do_stat_inline:
                os.fstat(fd)
            if cfg.do_prealloc_file and cfg.file_size:
                os.posix_fallocate(fd, 0, cfg.file_size)
            if cfg.do_truncate_to_size:
                os.ftruncate(fd, cfg.file_size)
            if cfg.file_size:
                if cfg.use_mmap:
                    self._rw_block_sized_mmap(fd, is_write=True)
                else:
                    gen = self._make_offset_gen_for_file(is_write=True)
                    self._rw_block_sized(fd, gen, is_write=True)
            self._apply_fadvise(fd)
        finally:
            os.close(fd)

    def _read_one_file(self, path: str) -> None:
        cfg = self.cfg
        self._slowop_path = path  # --slowops: name the file in captures
        flags = os.O_RDONLY
        if cfg.use_direct_io:
            flags |= os.O_DIRECT
        fd = os.open(path, flags)
        try:
            self._apply_fadvise(fd)
            if cfg.do_stat_inline:
                os.fstat(fd)  # --statinline (reference: stat-inline :3140)
            if cfg.file_size:
                if cfg.use_mmap:
                    self._rw_block_sized_mmap(fd, is_write=False)
                else:
                    gen = self._make_offset_gen_for_file(is_write=False)
                    self._rw_block_sized(fd, gen, is_write=False)
        finally:
            os.close(fd)

    def _apply_fadvise(self, fd: int) -> None:
        flags_str = self.cfg.fadvise_flags
        if not flags_str:
            return
        advice_map = {"seq": os.POSIX_FADV_SEQUENTIAL,
                      "rand": os.POSIX_FADV_RANDOM,
                      "willneed": os.POSIX_FADV_WILLNEED,
                      "dontneed": os.POSIX_FADV_DONTNEED,
                      "noreuse": os.POSIX_FADV_NOREUSE}
        for name in flags_str.split(","):
            name = name.strip()
            if not name:
                continue
            if name not in advice_map:
                raise WorkerException(f"unknown fadvise flag: {name}")
            os.posix_fadvise(fd, 0, 0, advice_map[name])

    # ------------------------------------------------------------------
    # offset generator wiring (reference: initPhaseRWOffsetGen :1141-1186)
    # ------------------------------------------------------------------

    def _make_shuffle_gen(self, num_bytes: int, start: int = 0):
        """--shufflewindow: seeded windowed permutation (every block
        exactly once, locality bounded by the window) — the
        training-pipeline shuffle-buffer shape. ONE seed mix for both
        the dir-mode and shared-file constructions: the scenario epoch
        (so the epochs scenario re-shuffles per epoch) times a prime,
        plus the worker rank (so workers don't read in lockstep)."""
        cfg = self.cfg
        bs = cfg.block_size
        from ..toolkits.offset_gen import OffsetGenShuffleWindow
        return OffsetGenShuffleWindow(
            num_bytes, bs, max(cfg.shuffle_window, bs),
            seed=cfg.scenario_epoch * 1_000_003 + self.rank, start=start)

    def _make_offset_gen_for_file(self, is_write: bool):
        cfg = self.cfg
        size, bs = cfg.file_size, cfg.block_size
        if not is_write and cfg.shuffle_window:
            return self._make_shuffle_gen(size)
        if cfg.use_random_offsets:
            amount = max(cfg.random_amount // max(1, cfg.num_dataset_threads),
                         bs) if cfg.random_amount else size
            if cfg.no_random_align:
                return OffsetGenRandom(self._rand_offset_algo, amount, bs,
                                       range_len=size)
            if is_write:
                # full-coverage LCG: every block exactly once (default for
                # aligned random writes, reference LocalWorker.cpp:1177-1184)
                return OffsetGenRandomAlignedFullCoverage(
                    self._rand_offset_algo, amount, bs, range_len=size)
            return OffsetGenRandomAligned(self._rand_offset_algo, amount, bs,
                                          range_len=size)
        if cfg.do_reverse_seq_offsets:
            return OffsetGenReverseSeq(size, bs)
        return OffsetGenSequential(size, bs)

    # ------------------------------------------------------------------
    # hot loop (reference: rwBlockSized, LocalWorker.cpp:1702-1814)
    # ------------------------------------------------------------------

    def _rw_block_sized(self, fd: int, gen, is_write: bool,
                        file_offset_base: int = 0,
                        multi_file: "object | None" = None,
                        stripe: "tuple | None" = None) -> None:
        """offset-gen loop -> rate limit -> [rwmix decision] -> [fill buf] ->
        positional I/O -> [verify] -> [TPU H2D] -> latency + counters.

        When the native C++ ioengine is available and the workload qualifies
        (no TPU staging — see ``_native_loop_eligible``), the whole loop is
        delegated to it: verify, rwmix-pct, block variance, rate limits,
        flock, inline read-back and opslog records all run INSIDE the engine
        (BlockMod), and striped multi-file mode maps through
        ``stripe=(fds, file_size)`` (the structured form of the
        ``multi_file`` mapping).
        """
        cfg = self.cfg
        if stripe is not None and multi_file is None:
            # single source of truth: derive the Python-fallback mapping
            # from the structured stripe info
            stripe_fds, stripe_size = stripe

            def multi_file(global_off, length):  # noqa: ARG001
                return (stripe_fds[global_off // stripe_size],
                        global_off % stripe_size)
        from ..utils.native import get_native_engine
        native = get_native_engine()
        # fused TPU streaming ring (--tpustream): storage I/O runs in the
        # engine's submission/completion ring while Python overlaps HBM
        # DMA dispatch — the default on eligible --tpuids phases, with a
        # clean fallback chain native-stream (uring -> AIO) -> Python
        # loop, logged once per phase
        if self._tpu is not None and cfg.tpu_stream != "off":
            blocker = self._tpu_stream_blocker(native, multi_file, stripe,
                                               gen)
            if blocker is None:
                if self._run_fused_tpu_stream_loop(
                        native, fd, gen, is_write, file_offset_base,
                        stripe):
                    return
                blocker = ("stream ring setup failed, or the pinned "
                           "--ioengine is not the ring's actual backend")
            if cfg.tpu_stream == "on":
                raise WorkerException(
                    f"--tpustream on: fused native-stream loop "
                    f"unavailable ({blocker})")
            self._log_stream_mode(
                f"NOTE: fused TPU stream ineligible ({blocker}); "
                f"using the Python loop")
        sync_path = cfg.io_depth <= 1 and cfg.io_engine in ("auto", "sync")
        if (self._native_loop_eligible(native)
                and (multi_file is None or stripe is not None)
                # per-op flock and inline read-back are sync-loop features
                # (in C++ too); async engines fall back to Python for them
                and (sync_path or not (cfg.do_read_inline
                                       or cfg.do_direct_verify
                                       or cfg.use_file_locks))):
            if self._run_native_block_loop(native, fd, gen, is_write,
                                           file_offset_base, stripe):
                return
        if cfg.io_engine != "auto":
            raise WorkerException(
                f"--ioengine {cfg.io_engine} only supports the native "
                f"block loop — incompatible with --rwmixthrpct/--tpuids/"
                f"--tracefile/non-'fast' --blockvaralgo (and "
                f"--verifydirect/--readinline/--flock need the sync "
                f"engine)")
        num_bufs = len(self._io_bufs)
        # the pacer is PER PHASE (created in _dispatch_phase): dir-mode
        # read phases enter here once per file, and the consume clock /
        # batch count must span the whole epoch, not restart per shard
        pacer = None if is_write else getattr(self, "_loader_pacer", None)
        is_rwmix_reader = getattr(self, "_rwmix_thread_reader", False)
        # the byte-ratio balancer only applies to the mixed WRITE phase
        # (writers + converted readers); a later pure READ phase must not
        # be throttled against zero writer bytes
        balancer = (self.shared.rwmix_balancer
                    if (is_write or is_rwmix_reader) else None)
        # chaos-test seams: a deterministic per-op delay for exactly one
        # (port, op_index), and a uniform every-op latency floor (the
        # autotune suite's constructed storage bottleneck) — both None/0
        # outside ELBENCHO_TPU_TESTING fleets
        from ..telemetry.slowops import test_op_delay, test_uniform_op_delay
        fault_delay = test_op_delay(cfg)
        uniform_delay_usec = test_uniform_op_delay(cfg)
        for off, length in gen:
            # rotate buffers so pipelined TPU transfers never race a reuse
            buf = self._io_bufs[self._num_iops_submitted % num_bufs]
            do_read_this_op = (not is_write) or self._rwmix_decides_read()
            limiter = (self._rate_limiter_read if do_read_this_op
                       else self._rate_limiter_write)
            if limiter or balancer:
                # limiter/balancer sleeps can be long; check every op here
                self.check_interruption_request(force=True)
                if balancer:
                    if do_read_this_op or is_rwmix_reader:
                        balancer.wait_read(length)
                    else:
                        balancer.wait_write(length)
                if limiter:
                    limiter.wait(length)
            else:
                self.check_interruption_request()
            if multi_file is not None:
                fd, real_off = multi_file(off, length)
            else:
                real_off = file_offset_base + off
            # --slowops stage split: bracket this op's TPU hand-offs
            # (D2H pre-write fill here, H2D post-read below) with the
            # context's dispatch/DMA accounting so a captured tail op
            # says WHERE its time went
            tpu_snap = ((self._tpu.dispatch_usec, self._tpu.transfer_usec)
                        if self._slowops is not None
                        and self._tpu is not None else None)
            slow_r0 = self.io_retries if self._slowops is not None else 0
            if not do_read_this_op:
                self._pre_write_fill(buf, real_off, length)

            def one_op(fd=fd, real_off=real_off, length=length,
                       do_read=do_read_this_op, buf=buf,
                       delay=uniform_delay_usec + (
                           fault_delay[1]
                           if fault_delay is not None
                           and self._num_iops_submitted
                           == fault_delay[0] else 0)):
                """One positional I/O attempt; a short transfer raises
                the (transient) ShortIOError so --ioretries covers it."""
                t0 = time.perf_counter_ns()
                if delay:  # chaos-test seam: provably slow op
                    time.sleep(delay / 1e6)
                if cfg.use_file_locks:
                    with FileRangeLock(fd, cfg.use_file_locks, real_off,
                                       length, is_write=not do_read):
                        if do_read:
                            n = os.preadv(fd, [buf[:length]], real_off)
                        else:
                            n = os.pwritev(fd, [buf[:length]], real_off)
                elif do_read:
                    n = os.preadv(fd, [buf[:length]], real_off)
                else:
                    n = os.pwritev(fd, [buf[:length]], real_off)
                if n != length:
                    from .io_errors import ShortIOError
                    raise ShortIOError(do_read, real_off, n, length)
                # t0 rides along for the tracer span (the final
                # successful attempt's window, excluding retry backoff)
                return n, (time.perf_counter_ns() - t0) // 1000, t0

            try:
                if self._io_retrier is None:
                    n, lat_usec, t0 = one_op()
                else:
                    n, lat_usec, t0 = self._io_retrier.run(
                        one_op, path=self._retry_path_hint())
            except OSError as err:
                from .io_errors import ShortIOError
                if isinstance(err, ShortIOError):
                    # exact historic short-I/O message (fail-fast parity)
                    raise WorkerException(str(err)) from None
                raise
            if self._ops_log:
                self._ops_log.log_op("read" if do_read_this_op else "write",
                                     "", real_off, length)
            op = self._stages.next_op() if self._stages is not None \
                else None
            if do_read_this_op:
                self._post_read_actions(buf, real_off, length, t0, op)
            elif cfg.do_read_inline or cfg.do_direct_verify:
                self._inline_read_back(fd, buf, real_off, length)
            ops = (self.live_ops_rwmix_read
                   if (is_write and do_read_this_op) else self.live_ops)
            histo = (self.iops_latency_histo_rwmix
                     if (is_write and do_read_this_op)
                     else self.iops_latency_histo)
            histo.add_latency(lat_usec)
            if self._tracer is not None:  # no-op path: one attribute test
                self._tracer.record_op(
                    "read" if do_read_this_op else "write",
                    phase_name(self.shared.current_phase), t0, lat_usec,
                    self.rank, real_off, length,
                    slot=self._num_iops_submitted % num_bufs, op_id=op)
            if self._slowops is not None:  # no-op path: one attribute test
                self._slowops.record(
                    "read" if do_read_this_op else "write",
                    phase_name(self.shared.current_phase), lat_usec,
                    real_off, length,
                    path=self._slowop_path
                    or (cfg.paths[0] if cfg.paths else ""),
                    retries=self.io_retries - slow_r0,
                    dispatch_usec=(self._tpu.dispatch_usec - tpu_snap[0]
                                   if tpu_snap is not None else 0),
                    dma_usec=(self._tpu.transfer_usec - tpu_snap[1]
                              if tpu_snap is not None else 0),
                    slot=self._num_iops_submitted % num_bufs,
                    start_ns=t0)
            ops.num_bytes_done += n
            ops.num_iops_done += 1
            self._num_iops_submitted += 1
            if self._staging_pool is not None:
                self._staging_pool.account_ops(1)
            if pacer is not None:
                # dataloader emulation: decode burn + consume-cadence
                # wait per closed batch (--scenario dataloader)
                pacer.on_block()
        if self._tpu is not None:
            # drain pipelined transfers before phase end (guarded: an
            # in-flight transfer of a dying chip surfaces here)
            self._tpu_guarded(self._tpu.flush)
            self._sync_tpu_usec()
            self._book_resident()

    def _sync_tpu_usec(self) -> None:
        """Mirror the context's split timing counters into this worker's
        phase stats (dispatch = host-side submit cost, transfer = DMA
        wall time; both accumulated per-phase by TransferPipeline)."""
        self.tpu_dispatch_usec = self._tpu.dispatch_usec
        self.tpu_transfer_usec = self._tpu.transfer_usec

    # ------------------------------------------------------------------
    # data-plane fault tolerance (--ioretries / --iotimeout /
    # --tpufallback; workers/io_errors.py + tpu/device.py failover)
    # ------------------------------------------------------------------

    def _partial_tolerance(self, phase: BenchPhase) -> bool:
        """Delete phases tolerate missing entries when an earlier write
        phase of this run was aborted (time limit, interrupt, or a
        permanent storage error): the dataset is partial by definition,
        and failing the cleanup over expected ENOENTs would bury the
        benchmark results that were already printed. Logged once per
        phase; --nodelerr keeps covering the cross-run cleanup case."""
        if phase not in (BenchPhase.DELETEFILES, BenchPhase.DELETEDIRS):
            return False
        if not self.shared.partial_dataset:
            return False
        if not self._tolerate_note_logged:
            self._tolerate_note_logged = True
            if self.rank % max(1, self.cfg.num_threads) == 0:
                logger.log(
                    logger.LOG_NORMAL,
                    "NOTE: an earlier write phase was aborted; the delete "
                    "phase tolerates entries missing from the partial "
                    "dataset")
        return True

    def _retry_path_hint(self) -> str:
        """Path used by the retry classifier's network-filesystem check
        (EIO is transient on NFS/FUSE/parallel filesystems, permanent on
        local media)."""
        paths = self.cfg.paths
        return paths[0] if paths else ""

    def _retrying_native(self, call, retryable: bool = True):
        """Run one native-engine chunk call under --ioretries. A retry
        re-issues the WHOLE chunk (accounting only books after a chunk
        succeeds, so nothing double-counts; re-running completed
        read/write ops is idempotent benchmark I/O)."""
        if self._io_retrier is None or not retryable:
            return call()
        return self._io_retrier.run(call, path=self._retry_path_hint())

    def _tpu_guarded(self, fn, *args, **kwargs):
        """Run one TPU transfer-path call with device-loss failover
        (--tpufallback). Anything that is not a classified XLA-runtime/
        device-loss error propagates untouched — a --tpubudget breach or
        a logic error must abort, never failover."""
        from ..tpu.device import is_device_loss_error
        attempts = 0
        while True:
            try:
                return fn(*args, **kwargs)
            except WorkerInterruptedException:
                raise
            except Exception as err:  # noqa: BLE001 - classified below
                if self._tpu is None or not is_device_loss_error(err):
                    raise
                attempts += 1
                if attempts > len(self.cfg.tpu_ids) + 1:
                    raise  # every chip (and host staging) failed: abort
                self._tpu_handle_device_loss(err)

    def _tpu_handle_device_loss(self, err: Exception) -> None:
        """One device-loss event: poison the chip fleet-wide, then abort
        (default), fail over to a surviving --tpuids chip, or degrade to
        host-memory staging per --tpufallback."""
        cfg = self.cfg
        ctx = self._tpu
        with self.shared.cond:
            self.shared.poisoned_tpu_chips.add(ctx.chip_id)
        if self._tracer is not None:  # --tracefile failover marker
            self._tracer.record("tpu_failover", "fault",
                                self._tracer.now_ns(), 0, rank=self.rank)
        mode = getattr(cfg, "tpu_fallback", "abort")
        if mode == "abort":
            from ..tpu.device import TpuDeviceLostError
            raise WorkerException(
                str(TpuDeviceLostError(ctx.chip_id, err))) from err
        if mode == "chip":
            with self.shared.cond:
                survivors = [c for c in cfg.tpu_ids
                             if c not in self.shared.poisoned_tpu_chips]
            if survivors:
                ctx.failover_to_chip(survivors[self.rank % len(survivors)])
                return
            logger.log_error(
                "--tpufallback chip: no surviving --tpuids chip left; "
                "degrading to host-memory staging")
        ctx.failover_to_host()

    def _loader_pacing_active(self, is_write: bool) -> bool:
        """Dataloader-emulation pacing (--scenario dataloader) shapes the
        READ loop with per-batch decode burns and consume-cadence waits —
        per-op Python behavior no native loop expresses."""
        cfg = self.cfg
        return (not is_write
                and bool(cfg.scenario_step_usec or cfg.scenario_decode_usec))

    def _make_loader_pacer(self, is_write: bool):
        if not self._loader_pacing_active(is_write):
            return None
        from ..toolkits.rate_limiter import DataLoaderPacer
        cfg = self.cfg
        return DataLoaderPacer(
            cfg.scenario_batch_blocks or 1, cfg.scenario_step_usec,
            cfg.scenario_decode_usec, cfg.scenario_prefetch or 1,
            interrupt_check=lambda:
                self.check_interruption_request(force=True))

    def _native_loop_eligible(self, native) -> bool:
        """Conditions every native delegation shares: no per-op Python
        feature may be active. Verify/rwmix-pct/block-variance run INSIDE
        the native loop (csrc BlockMod — the reference keeps them in its
        hot loop too, LocalWorker.cpp:1741,2124,2242) and so do the
        per-thread rate limiters (C++ RateLimiter.h analogue); what still
        drops to Python is TPU staging, the rwmix-threads byte-ratio
        balancer, and non-default variance PRNGs (opslog block records
        are written by the engine; dir-mode entry records stay Python). Loop-specific
        extras (flock, read-inline...) are checked at the call sites."""
        cfg = self.cfg
        return (native is not None
                and self._tpu is None
                # --tracefile spans are recorded by the Python loops (the
                # fused TPU stream loop records its own and stays native)
                and self._tracer is None
                # --slowops captures per-op context (path/offset/retry
                # chain) the block-loop arrays don't carry — same
                # fallback rule as tracing; the fused stream ring stays
                # engaged and records from its reap events
                and self._slowops is None
                and self.shared.rwmix_balancer is None
                # dataloader-emulation pacing is per-op Python behavior
                # (the knobs are only set on the loader read leg, so a
                # scenario's setup write still runs native)
                and not (cfg.scenario_step_usec or cfg.scenario_decode_usec)
                and (not cfg.block_variance_pct
                     or cfg.block_variance_algo == "fast"))

    # ------------------------------------------------------------------
    # fused TPU streaming ring (--tpustream): the engine keeps up to
    # iodepth storage ops in flight over the registered staging slots
    # (GIL released across the blocking reap), Python reaps completed
    # slots and hands them straight to the TPU transfer pipeline — disk
    # DMA in the kernel overlaps HBM DMA dispatch in Python, the
    # cuFileRead overlap of the reference's GPUDirect path
    # (LocalWorker.cpp:2633-2749) rebuilt on io_uring/AIO + PjRt.
    # ------------------------------------------------------------------

    @staticmethod
    def _stripe_offsets(offsets, file_offset_base, stripe_size):
        """Vectorized calcFileIdxAndOffsetStriped (LocalWorker.cpp:2084):
        global block offsets -> (per-block fd index or None, in-file
        offsets). The ONE mapping shared by the native block loop and
        the fused stream loop, so the two paths can never diverge on
        which file region a block lands in."""
        if stripe_size:
            goffs = offsets + np.uint64(file_offset_base)
            return ((goffs // np.uint64(stripe_size)).astype(np.uint32),
                    goffs % np.uint64(stripe_size))
        if file_offset_base:
            return None, offsets + np.uint64(file_offset_base)
        return None, offsets

    def _log_stream_mode(self, msg: str) -> None:
        """Once per phase, from the first local worker only."""
        if self._stream_mode_logged:
            return
        self._stream_mode_logged = True
        if self.rank % max(1, self.cfg.num_threads) == 0:
            logger.log(logger.LOG_NORMAL, msg)

    def _tpu_stream_blocker(self, native, multi_file, stripe,
                            gen=None) -> "str | None":
        """Why the fused native-stream loop cannot serve this phase
        (None = eligible). Everything the stream cannot express stays on
        the Python loop: per-op Python features, and explicit engine
        pins that don't match the kernel's stream backend."""
        from ..utils.native import ENGINE_CODES
        cfg = self.cfg
        if native is None:
            return "native ioengine unavailable"
        if cfg.bench_path_type == BenchPathType.DIR and gen is not None:
            # dir-mode/custom-tree phases open one stream PER FILE: for
            # files only a couple of ring-fills long, the ring setup +
            # registration + teardown would outweigh the overlap it buys
            ops = getattr(gen, "num_bytes", 0) // max(cfg.block_size, 1)
            if ops < 2 * max(len(self._io_bufs), 1):
                return "per-file stream too short to amortize ring setup"
        if not native.stream_supported():
            return "kernel lacks both io_uring and AIO"
        if multi_file is not None and stripe is None:
            return "unstructured multi-file mapping"
        if self._ops_log is not None:
            return "--opslog per-op records"
        if self.shared.rwmix_balancer is not None:
            return "--rwmixthr byte-ratio balancer"
        if cfg.use_file_locks:
            return "--flock per-op locks"
        if cfg.do_read_inline or cfg.do_direct_verify:
            return "--readinline/--verifydirect inline read-back"
        if self._rate_limiter_read or self._rate_limiter_write:
            return "per-op rate limits"
        if cfg.scenario_step_usec or cfg.scenario_decode_usec:
            return "dataloader-emulation pacing (--scenario dataloader)"
        if cfg.io_engine != "auto" and \
                ENGINE_CODES.get(cfg.io_engine) != native.stream_backend():
            return (f"--ioengine {cfg.io_engine} pinned but the stream "
                    f"backend is {native.stream_backend_name()}")
        return None

    def _run_fused_tpu_stream_loop(self, native, fd, gen, is_write,
                                   file_offset_base,
                                   stripe=None) -> bool:
        """Drive the whole block loop through the engine's streaming
        ring. Returns False when the ring cannot be opened (the caller
        logs the fallback and runs the Python loop). Accounting goes
        through the array-based _account_chunk per drained chunk, with
        the dispatch-vs-DMA split riding the TransferPipeline counters
        exactly like the Python loop."""
        from collections import deque
        from ..utils.native import NativeStreamError
        cfg = self.cfg
        if stripe is not None:
            fds, stripe_size = list(stripe[0]), stripe[1]
        else:
            fds, stripe_size = [fd], 0
        pool = self._staging_pool
        slot_addrs = pool.slot_addrs
        try:
            # borrow the pool's persistent ring where one exists: the
            # slab was registered as fixed buffers ONCE at pool open
            # (and SQPOLL rides along) — else an owned per-phase ring
            stream = native.open_stream(
                fds, slot_addrs, max(cfg.block_size, 1),
                pool=None if pool.broken else pool.native_pool)
        except NativeStreamError:
            return False
        if cfg.io_engine != "auto":
            # the open may have fallen back (e.g. uring probe ok but
            # ring mmaps ENOMEM at this slot count): an explicit
            # --ioengine pin is enforced against the ACTUAL backend
            from ..utils.native import ENGINE_CODES
            if ENGINE_CODES.get(cfg.io_engine) != stream.backend:
                stream.close()
                return False
        self._log_stream_mode(
            f"fused TPU stream engaged (backend={stream.backend_name}, "
            f"slots={len(slot_addrs)}"
            + (", pool-registered" if stream.pooled else "")
            + (", sqpoll" if stream.sqpoll else "") + ")")
        if cfg.io_timeout_secs:
            # --iotimeout: hung ops surface as -ETIMEDOUT with the slot
            # re-armed instead of wedging the reap loop
            stream.set_timeout(cfg.io_timeout_secs * 1_000_000)
        fault_spec = os.environ.get("ELBENCHO_TPU_IO_FAULT")
        if fault_spec:
            # test-only deterministic fault injection; config validation
            # already rejected this knob outside a test harness
            stream.set_fault_from_spec(fault_spec)
        # slot-reuse discipline: a slot is free, in the engine ring
        # (slot_op), or held back after its H2D until the transfer ring
        # provably drained the transfer that reads it (holdback_depth:
        # staged and direct alike). The depth is FROZEN for the phase:
        # a host-staging failover mid-stream must not release slots
        # whose earlier transfers are still in the ring undrained —
        # holding them a little longer is merely conservative, the
        # reverse is a use-after-reuse.
        hold = self._tpu.holdback_depth()
        free = deque(range(len(slot_addrs)))
        held: "deque[int]" = deque()
        slot_op: dict = {}
        chunk = self._native_chunk_blocks()
        try:
            while True:
                batch = gen.next_batch(chunk)
                if batch is None:
                    break
                self._fused_stream_chunk(stream, batch, is_write,
                                         file_offset_base, stripe_size,
                                         free, held, slot_op, hold)
        finally:
            # drains outstanding kernel DMA first; a failed drain means
            # the kernel still owns ops targeting the slot buffers —
            # cleanup() must then leak the mmaps to process teardown
            # instead of unmapping memory a late completion DMAs into
            if stream.close() != 0:
                self._stream_drain_failed = True
                logger.log_error(
                    f"worker {self.rank}: stream ring drain failed; "
                    f"keeping I/O buffers mapped until process exit")
        # phase-end transfer drain + --tpubudget check (guarded for
        # --tpufallback like every other transfer-path call)
        self._tpu_guarded(self._tpu.flush)
        self._sync_tpu_usec()
        return True

    def _fused_stream_chunk(self, stream, batch, is_write,
                            file_offset_base, stripe_size, free, held,
                            slot_op, hold) -> None:
        """One bounded chunk of the fused loop: submit every op (reaping
        for slots as needed), then drain to a chunk barrier so the
        array-based accounting is exact; an interrupt books exactly the
        ops the ring completed before propagating.

        Per op, a storage-submit stamp rides in its slot_op entry into
        the transfer ring (the op-resident latency), and the wait from
        its reap to its H2D dispatch books ReapQueueUSec; each held-back
        slot that goes back to the engine books one SlotReleaseWaitOps,
        and a release drain (one made only to free a held slot) books
        its wait in SlotReleaseWaitUSec; with stage spans armed, the op's
        spans share one id."""
        import ctypes
        from ..utils.native import _account_chunk
        cfg = self.cfg
        ctx = self._tpu
        st = self._stages
        offsets, lengths = batch
        n = len(offsets)
        if n == 0:
            return
        fd_idx, real_offs = self._stripe_offsets(offsets,
                                                 file_offset_base,
                                                 stripe_size)
        flags = self._rwmix_read_flags(n) if is_write else None
        lengths_np = (lengths if isinstance(lengths, np.ndarray)
                      else np.asarray(lengths, dtype=np.uint64))
        total = int(lengths_np.sum())
        lat_arr = (ctypes.c_uint64 * n)()
        done = bytearray(n)  # 1 per op the ring completed
        state = {"bytes": 0}

        def retry_or_raise(slot, entry, err) -> bool:
            """--ioretries for a failed fused-ring op: backoff, then
            re-submit the SAME op on the SAME slot (the slot buffer still
            holds the write source; a read retries into it). attempts is
            tracked PER OP in slot_op — the ring interleaves many
            in-flight ops, so the retrier's shared consecutive counter
            would misaccount across them. Returns True when the retry
            was submitted, raises the original error when retries are
            off/exhausted/not applicable."""
            from .io_errors import IoRetryBudgetExhausted, ShortIOError
            i, fdi, r_off, length, rd, attempts, t_sub, op = entry
            retrier = self._io_retrier
            if retrier is None or not retrier.should_retry(
                    err, path=self._retry_path_hint(), attempt=attempts):
                if isinstance(err, ShortIOError):
                    raise WorkerException(str(err)) from None
                raise err
            try:
                retrier.backoff(attempt=attempts)
            except IoRetryBudgetExhausted:
                raise err from None
            # the op keeps its first submit stamp: its resident latency
            # includes the retries
            slot_op[slot] = (i, fdi, r_off, length, rd, attempts + 1,
                             t_sub, op)
            stream.submit(slot, fdi, r_off, length, is_write=not rd)
            return True

        def reap_some(min_complete: int) -> None:
            from .io_errors import ShortIOError
            token = st.begin("stream_reap") if st is not None else None
            t_reap = time.perf_counter_ns() if st is not None else 0
            events = stream.reap(min_complete, 1000,
                                 self._native_interrupt)
            t_reaped = time.perf_counter_ns()
            if st is not None:
                # the reap's wait, and the ops it harvested
                st.end(token, "stream_reap", "stream", t_reap, t_reaped,
                       events=len(events), min_complete=min_complete,
                       ops=[slot_op[e[0]][7] for e in events
                            if e[0] in slot_op])
            if self._staging_pool is not None:
                # registration/SQPOLL audit (PoolRegisteredOps and co)
                self._staging_pool.account_stream_events(stream,
                                                         len(events))
            if not events:
                # timeout or interrupt: surface the interrupt, else retry
                self.check_interruption_request(force=True)
                if cfg.io_timeout_secs and slot_op:
                    # un-cancellable hung op (kernel-AIO io_cancel is
                    # best-effort): once an op is WAY past the deadline
                    # with no completion in sight, abort the phase
                    # loudly instead of spinning forever — the ring's
                    # close() drain then leaks the slot buffers safely
                    age = stream.oldest_age_usec()
                    limit = cfg.io_timeout_secs * 2_000_000 + 5_000_000
                    if age > limit:
                        raise WorkerException(
                            f"storage op stuck for {age // 1_000_000}s — "
                            f"past --iotimeout {cfg.io_timeout_secs}s and "
                            f"uncancellable on the "
                            f"{stream.backend_name} backend; aborting "
                            f"the phase")
                return
            for slot, lat, res in events:
                entry = slot_op.pop(slot)
                i, fdi, r_off, length, rd, attempts, t_sub, op = entry
                if res < 0:
                    if -res == errno.ETIMEDOUT:
                        # --iotimeout cancelled a hung op (audited; the
                        # error itself is transient, so --ioretries can
                        # re-drive the op on the re-armed slot)
                        self.io_timeouts += 1
                    retry_or_raise(slot, entry,
                                   OSError(-res, os.strerror(-res)))
                    continue
                if res != length:
                    retry_or_raise(slot, entry,
                                   ShortIOError(rd, r_off, res, length))
                    continue
                lat_arr[i] = lat
                done[i] = 1
                state["bytes"] += res
                ctx.stream_fused_ops += 1
                if self._tracer is not None:
                    # span start back-derived from the engine's latency
                    self._tracer.record_op(
                        "read" if rd else "write",
                        phase_name(self.shared.current_phase),
                        self._tracer.now_ns() - int(lat) * 1000, lat,
                        self.rank, r_off, length, slot=slot, op_id=op)
                if self._slowops is not None:
                    # per-op latency straight from the engine's reap
                    # event; file attribution via the stripe fd index
                    self._slowops.record(
                        "read" if rd else "write",
                        phase_name(self.shared.current_phase), int(lat),
                        r_off, length,
                        path=(self._slowop_path
                              or (cfg.paths[fdi]
                                  if fdi < len(cfg.paths) else "")),
                        retries=attempts,
                        slot=slot,
                        start_ns=(time.perf_counter_ns()
                                  - int(lat) * 1000))
                if rd:
                    # the op waited from its reap until here, behind the
                    # earlier ops of the batch (ring only: the span
                    # encloses their stages)
                    t_disp = time.perf_counter_ns()
                    self.reap_queue_usec += (t_disp - t_reaped) // 1000
                    self.reap_queue_ops += 1
                    if st is not None:
                        st.record("reap_queue", "stream", t_reaped, t_disp,
                                  op)
                    # host->HBM DMA + verify (host memcmp or on-device),
                    # identical to the Python loop's post-read hook
                    self._post_read_actions(self._io_bufs[slot], r_off,
                                            length, t_sub, op)
                    if hold:  # frozen per phase, see the caller
                        held.append(slot)
                        while len(held) > hold:
                            # the submit's own drain completed its
                            # transfer: a release without a wait
                            free.append(held.popleft())
                            self.slot_release_wait_ops += 1
                    else:
                        free.append(slot)
                else:
                    free.append(slot)

        try:
            for i in range(n):
                self.check_interruption_request()
                while not free:
                    if slot_op:
                        reap_some(0)  # harvest anything already done
                        if free:
                            break
                    if held:
                        # release the oldest ingested slot by draining
                        # its H2D from the transfer ring: after
                        # drain_to(len(held)-1) the ring's FIFO in-flight
                        # window only covers the newer held slots, so
                        # held[0]'s transfer has provably completed.
                        # Without this, the holdback would cap the engine
                        # ring at n_slots-(depth-1) ops and serialize
                        # storage I/O. The drain encloses its h2d_wait,
                        # so its span stays in the ring.
                        t_rel = time.perf_counter_ns()
                        ctx.drain_to(len(held) - 1)
                        t_freed = time.perf_counter_ns()
                        self.slot_release_wait_usec += \
                            (t_freed - t_rel) // 1000
                        self.slot_release_wait_ops += 1
                        if st is not None:
                            st.record("slot_release", "stream", t_rel,
                                      t_freed, slot=held[0])
                        free.append(held.popleft())
                    else:
                        reap_some(1)
                slot = free.popleft()
                length = int(lengths_np[i])
                r_off = int(real_offs[i])
                rd = bool(flags[i]) if (is_write and flags is not None) \
                    else not is_write
                if not rd:
                    # write-source block originates in HBM: D2H into the
                    # slot (the Python loop's pre-write hook)
                    self._pre_write_fill(self._io_bufs[slot], r_off,
                                         length)
                fdi = int(fd_idx[i]) if fd_idx is not None else 0
                op = st.next_op() if st is not None else None
                slot_op[slot] = (i, fdi, r_off, length, rd, 0,
                                 time.perf_counter_ns(), op)
                stream.submit(slot, fdi, r_off, length, is_write=not rd)
                if self._staging_pool is not None:
                    self._staging_pool.note_occupancy(len(slot_op))
            while slot_op:  # chunk barrier: exact accounting below
                reap_some(1)
        except WorkerInterruptedException:
            _account_chunk(self, lat_arr, lengths_np, n, state["bytes"],
                           total, flags, done)
            raise
        _account_chunk(self, lat_arr, lengths_np, n, state["bytes"],
                       total, flags)
        self._book_resident()

    #: bounds for one native engine call, so live stats progress and
    #: interrupts stay responsive (shared by every native delegation)
    _NATIVE_CHUNK_MAX_BLOCKS = 8192
    _NATIVE_CHUNK_MAX_BYTES = 256 << 20

    def _native_chunk_blocks(self) -> int:
        cfg = self.cfg
        max_bytes = self._NATIVE_CHUNK_MAX_BYTES
        # under a rate limit, one engine call must not span minutes of
        # throttled I/O (live stats only refresh between chunks): cap a
        # chunk at ~2 seconds of the tightest active budget
        limits = [x for x in (cfg.limit_read_bps, cfg.limit_write_bps) if x]
        if limits:
            max_bytes = min(max_bytes, 2 * min(limits))
        by_bytes = max_bytes // max(cfg.block_size, 1)
        return max(1, min(self._NATIVE_CHUNK_MAX_BLOCKS, by_bytes))

    def _run_native_block_loop(self, native, fd, gen, is_write,
                               file_offset_base, stripe=None) -> bool:
        """Delegate the block loop to the C++ engine in chunks (bounded
        memory, live-stats progress, interruptibility between chunks);
        counters and latency buckets sync back per chunk. The engine also
        polls our interrupt flag every 128 ops within a chunk. With
        ``stripe=(fds, file_size)`` global offsets map to per-block
        (file, in-file offset) pairs (calcFileIdxAndOffsetStriped).
        Verify/rwmix-pct/variance run inside the engine (BlockMod)."""
        from ..utils.native import NativeVerifyError
        cfg = self.cfg
        chunk = self._native_chunk_blocks()
        stripe_fds, stripe_size = stripe if stripe else (None, 0)

        def submit(offsets, lengths):
            self.check_interruption_request(force=True)
            idx, offsets = self._stripe_offsets(offsets, file_offset_base,
                                                stripe_size)
            fds = stripe_fds if stripe_fds else None
            # per-op modulo split, vectorized (reference:
            # (workerRank+numIOPSSubmitted)%100 < pct, :1741-1742)
            flags = self._rwmix_read_flags(len(offsets)) if is_write \
                else None

            def call(offsets=offsets, lengths=lengths, idx=idx, fds=fds,
                     flags=flags):
                native.run_block_loop(
                    fd=fd, offsets=offsets, lengths=lengths,
                    is_write=is_write, buf_addr=self._buf_addr(),
                    iodepth=cfg.io_depth, worker=self,
                    interrupt_flag=self._native_interrupt,
                    engine=cfg.io_engine, fds=fds, fd_idx=idx,
                    op_is_read=flags,
                    verify_salt=cfg.integrity_check_salt,
                    block_var_pct=cfg.block_variance_pct,
                    block_var_seed=self._block_var_seed(),
                    limit_read_bps=cfg.limit_read_bps,
                    limit_write_bps=cfg.limit_write_bps,
                    rl_state=self._native_rl_state,
                    inline_readback=(cfg.do_read_inline
                                     or cfg.do_direct_verify),
                    flock_mode=self._flock_mode_code(),
                    ops_fd=(self._ops_log.fd if self._ops_log is not None
                            else -1),
                    ops_lock=cfg.ops_log_lock, worker_rank=self.rank,
                    # classic-engine leg of the unified pool: the uring
                    # engine runs this chunk over the pool's persistent
                    # ring + once-registered fixed buffers (the engine
                    # falls through to the per-call path for sync/aio)
                    pool=(self._staging_pool.native_pool
                          if self._staging_pool is not None
                          and not self._staging_pool.broken else None),
                    pool_stats=self._staging_pool)
                if self._staging_pool is not None \
                        and self._staging_pool.native_pool is not None \
                        and cfg.io_engine == "uring":
                    self._staging_pool.note_occupancy(
                        min(cfg.io_depth, self._staging_pool.n_slots))

            try:
                # --ioretries: a transient chunk failure re-issues the
                # whole chunk (accounting only books after success, so
                # nothing double-counts; the re-run is idempotent I/O)
                self._retrying_native(call)
            except NativeVerifyError as err:
                file_off = int(offsets[err.block_idx]) + err.word_idx * 8
                raise WorkerException(
                    f"data integrity check failed at file offset "
                    f"{file_off}: expected {err.want:#x}, "
                    f"got {err.got:#x}"
                    + self._verify_fail_hint(err.got)) from None

        while True:
            batch = gen.next_batch(chunk)
            if batch is None:
                break
            submit(batch[0], batch[1])
        return True

    def _buf_addr(self) -> int:
        return self._staging_pool.slot_addrs[0]

    def rotated_staging_buf(self) -> memoryview:
        """The staging slot serving the NEXT op under the worker's
        rotation discipline — the shared hand-out point of the S3/GCS,
        HDFS and tpubench families (the POSIX loops rotate inline).
        Books the hand-out in the pool's reuse accounting."""
        buf = self._io_bufs[self._num_iops_submitted % len(self._io_bufs)]
        if self._staging_pool is not None:
            self._staging_pool.account_ops(1)
        return buf

    def _rwmix_read_flags(self, n: int) -> "np.ndarray | None":
        """Per-op rwmix read flags for the next n ops of a write phase —
        the vectorized form of _rwmix_decides_read, bit-identical to the
        engine's (rwmix_base + block_idx) % 100 sequence."""
        pct = self.cfg.rwmix_read_pct
        if not pct:
            return None
        base = np.uint64(self.rank + self._num_iops_submitted)
        return (((base + np.arange(n, dtype=np.uint64)) % np.uint64(100))
                < np.uint64(pct)).astype(np.uint8)

    def _flock_mode_code(self) -> int:
        """--flock mode for the engine: 0 none, 1 range, 2 full."""
        return {"": 0, "range": 1, "full": 2}[self.cfg.use_file_locks]

    def _block_var_seed(self) -> int:
        """Variance-refill seed, varied per worker and per chunk."""
        return (self.rank << 32) ^ self._num_iops_submitted

    @staticmethod
    def _verify_fail_hint(got: int) -> str:
        """An all-zero mismatch usually means an unwritten/sparse region
        was read (e.g. rwmix reads against a file still being created),
        not on-disk corruption — say so instead of crying corruption."""
        return (" (read of an unwritten/sparse region?)"
                if got == 0 else "")

    def _rwmix_decides_read(self) -> bool:
        """Per-op modulo split (reference: (workerRank+numIOPSSubmitted)%100
        < rwMixReadPercent, LocalWorker.cpp:1741-1742)."""
        pct = self.cfg.rwmix_read_pct
        if not pct:
            return False
        return (self.rank + self._num_iops_submitted) % 100 < pct

    # -- write-side block content -------------------------------------------

    def _pre_write_fill(self, buf: memoryview, offset: int,
                        length: int) -> None:
        cfg = self.cfg
        if self._tpu is not None:
            # TPU staging: block content originates in HBM; device->host
            # transfer lands it in the write buffer (replaces cudaMemcpy
            # D2H pre-write, reference LocalWorker.cpp:2437-2490). With
            # --verify the pattern itself is generated on-device so the
            # read-back check still holds. Guarded: a device loss here
            # triggers --tpufallback failover instead of a bare abort.
            self._tpu_guarded(self._tpu.device_to_host, buf, length,
                              verify_salt=cfg.integrity_check_salt,
                              file_offset=offset)
            self._sync_tpu_usec()
            self.tpu_transfer_bytes += length
            return
        if cfg.integrity_check_salt:
            self._fill_verify_pattern(buf, offset, length,
                                      cfg.integrity_check_salt)
        elif cfg.block_variance_pct:
            refill = (length * cfg.block_variance_pct) // 100
            if refill:
                buf[:refill] = self._block_var_algo.fill_buffer(refill)

    @staticmethod
    def _fill_verify_pattern(buf: memoryview, offset: int, length: int,
                             salt: int) -> None:
        """Each 8-byte-aligned word = (file offset of word + salt)
        (reference: preWriteIntegrityCheckFillBuf, LocalWorker.cpp:2124)."""
        n_words = length // 8
        arr = np.frombuffer(buf[:n_words * 8], dtype=np.uint64)
        with np.errstate(over="ignore"):
            arr[:] = (np.arange(n_words, dtype=np.uint64) * np.uint64(8)
                      + np.uint64(offset) + np.uint64(salt))
        tail = length - n_words * 8
        if tail:
            buf[n_words * 8:length] = bytes(tail)

    def _verify_read_buf(self, buf: memoryview, offset: int,
                         length: int) -> None:
        """memcmp + exact mismatch offset report (reference:
        postReadIntegrityCheckVerifyBuf, LocalWorker.cpp:2170)."""
        salt = self.cfg.integrity_check_salt
        n_words = length // 8
        got = np.frombuffer(buf[:n_words * 8], dtype=np.uint64)
        with np.errstate(over="ignore"):
            want = (np.arange(n_words, dtype=np.uint64) * np.uint64(8)
                    + np.uint64(offset) + np.uint64(salt))
        bad = np.nonzero(got != want)[0]
        if bad.size:
            first = int(bad[0])
            raise WorkerException(
                f"data integrity check failed at file offset "
                f"{offset + first * 8}: expected {int(want[first]):#x}, "
                f"got {int(got[first]):#x}")

    # -- read-side block actions --------------------------------------------

    def _post_read_actions(self, buf: memoryview, offset: int,
                           length: int, submit_ns: int = 0,
                           op=None) -> None:
        """H2D and verify of a read block. submit_ns: the op's storage
        submit (perf_counter_ns; 0 books no resident latency); op: its
        span id."""
        cfg = self.cfg
        if self._tpu is not None:
            self._tpu.op_submit_ns = submit_ns
            self._tpu.op_id = op
            # host->HBM DMA of the read block (replaces cudaMemcpy H2D post-
            # read / cuFile read, reference LocalWorker.cpp:2633-2749);
            # guarded for --tpufallback chip failover
            self._tpu_guarded(self._tpu.host_to_device, buf, length,
                              verify_salt=cfg.integrity_check_salt
                              if cfg.do_tpu_verify else 0,
                              file_offset=offset)
            self._sync_tpu_usec()
            self.tpu_transfer_bytes += length
            # host-staging failover clears verify_on_device, so a
            # degraded phase falls through to the host memcmp below
            if cfg.do_tpu_verify and cfg.integrity_check_salt \
                    and self._tpu.verify_on_device:
                return  # verified on-device by the Pallas kernel
        if cfg.integrity_check_salt:
            self._verify_read_buf(buf, offset, length)

    def _inline_read_back(self, fd: int, buf: memoryview, offset: int,
                          length: int) -> None:
        """--readinline/--verifydirect: read back immediately after write
        (reference: pwriteAndReadWrapper, LocalWorker.cpp:2566)."""
        n = os.preadv(fd, [buf[:length]], offset)
        if n != length:
            raise WorkerException(f"short inline read-back at {offset}")
        if self.cfg.integrity_check_salt:
            self._verify_read_buf(buf, offset, length)

    # ------------------------------------------------------------------
    # mmap I/O path (reference: mmap wrappers, LocalWorker.cpp:2534+)
    # ------------------------------------------------------------------

    def _rw_block_sized_mmap(self, fd: int, is_write: bool,
                             gen=None) -> None:
        cfg = self.cfg
        size = cfg.file_size
        if is_write and stat_mod.S_ISREG(os.fstat(fd).st_mode):
            os.ftruncate(fd, size)  # block devices keep their size
        prot = mmap.PROT_WRITE | mmap.PROT_READ if is_write else mmap.PROT_READ
        mapped = mmap.mmap(fd, size, prot=prot)
        try:
            self._apply_madvise(mapped)
            if gen is None:
                gen = self._make_offset_gen_for_file(is_write)
            from ..utils.native import get_native_engine
            native = get_native_engine()
            if self._native_loop_eligible(native):
                self._run_native_mmap_loop(native, mapped, gen, is_write)
                return
            for off, length in gen:
                self.check_interruption_request()
                buf = self.rotated_staging_buf()
                t0 = time.perf_counter_ns()
                if is_write:
                    self._pre_write_fill(buf, off, length)
                    mapped[off:off + length] = buf[:length]
                else:
                    buf[:length] = mapped[off:off + length]
                lat_usec = (time.perf_counter_ns() - t0) // 1000
                if not is_write:
                    self._post_read_actions(buf, off, length)
                self.iops_latency_histo.add_latency(lat_usec)
                self.live_ops.num_bytes_done += length
                self.live_ops.num_iops_done += 1
                self._num_iops_submitted += 1
            if self._tpu is not None:
                self._tpu_guarded(self._tpu.flush)
                self._sync_tpu_usec()
        finally:
            mapped.close()

    def _run_native_mmap_loop(self, native, mapped, gen, is_write) -> None:
        """Chunked C++ memcpy loop over the mapping (the --mmap analogue
        of _run_native_block_loop; same eligibility and block-modifier
        handling)."""
        from ..utils.native import NativeVerifyError
        cfg = self.cfg
        # np.frombuffer works for read-only PROT_READ mappings too (ctypes
        # from_buffer would demand writability); the address stays valid
        # while `mapped` is open
        map_addr = np.frombuffer(mapped, dtype=np.uint8).ctypes.data
        chunk = self._native_chunk_blocks()
        while True:
            batch = gen.next_batch(chunk)
            if batch is None:
                break
            self.check_interruption_request(force=True)
            offsets, lengths = batch
            flags = self._rwmix_read_flags(len(offsets)) if is_write \
                else None
            try:
                native.run_mmap_loop(
                    map_addr, offsets, lengths, is_write,
                    buf_addr=self._buf_addr(), worker=self,
                    interrupt_flag=self._native_interrupt,
                    op_is_read=flags,
                    verify_salt=cfg.integrity_check_salt,
                    block_var_pct=cfg.block_variance_pct,
                    block_var_seed=self._block_var_seed(),
                    limit_read_bps=cfg.limit_read_bps,
                    limit_write_bps=cfg.limit_write_bps,
                    rl_state=self._native_rl_state)
            except NativeVerifyError as err:
                # mmap reads of unwritten sparse regions memcpy zeros (no
                # short-read signal like the pread loops) — the hint below
                # covers that case
                file_off = int(offsets[err.block_idx]) + err.word_idx * 8
                raise WorkerException(
                    f"data integrity check failed at file offset "
                    f"{file_off}: expected {err.want:#x}, "
                    f"got {err.got:#x}"
                    + self._verify_fail_hint(err.got)) from None

    def _apply_madvise(self, mapped: mmap.mmap) -> None:
        flags_str = self.cfg.madvise_flags
        if not flags_str:
            return
        advice_map = {"seq": mmap.MADV_SEQUENTIAL,
                      "rand": mmap.MADV_RANDOM,
                      "willneed": mmap.MADV_WILLNEED,
                      "dontneed": mmap.MADV_DONTNEED,
                      # reference: ARG_MADVISE_FLAG_{,NO}HUGEPAGE_NAME
                      "hugepage": getattr(mmap, "MADV_HUGEPAGE", 14),
                      "nohugepage": getattr(mmap, "MADV_NOHUGEPAGE", 15)}
        for name in flags_str.split(","):
            name = name.strip()
            if not name:
                continue
            if name not in advice_map:
                raise WorkerException(f"unknown madvise flag: {name}")
            mapped.madvise(advice_map[name])

    # ------------------------------------------------------------------
    # file/bdev mode (reference: fileModeIterateFilesSeq :3597,
    # fileModeIterateFilesRand :3511, fileModeDeleteFiles :3769)
    # ------------------------------------------------------------------

    def _file_mode_phase(self, phase: BenchPhase) -> None:
        cfg = self.cfg
        if phase == BenchPhase.DELETEFILES:
            # workers round-robin the given files (reference :3769)
            for i, p in enumerate(cfg.paths):
                if i % cfg.num_dataset_threads == \
                        (self.rank % cfg.num_dataset_threads):
                    try:
                        os.unlink(p)
                    except FileNotFoundError:
                        if not cfg.ignore_delete_errors \
                                and not self._partial_tolerance(phase):
                            raise
                    self.live_ops.num_entries_done += 1
            return
        if phase == BenchPhase.STATFILES:
            for p in cfg.paths:
                os.stat(p)
                self.live_ops.num_entries_done += 1
            return

        is_write = (phase == BenchPhase.CREATEFILES)
        num_files = len(cfg.paths)
        total_range = cfg.file_size * num_files

        gen = self._make_file_mode_offset_gen(is_write, total_range)
        if gen is None:
            self.got_phase_work = False
            return
        if is_write and cfg.do_truncate_to_size:
            for fd in self._path_fds:
                os.ftruncate(fd, cfg.file_size)
        if cfg.use_mmap and num_files == 1:
            # file/bdev mode via memory mapping (reference: prepareMmapVec,
            # ProgArgs.cpp:2109); worker's share drives the same gen
            self._rw_block_sized_mmap(self._path_fds[0], is_write, gen=gen)
            return
        # single file/bdev: global offsets ARE in-file offsets; striped
        # multi-file passes the (fds, file_size) mapping — the native C++
        # engine takes the hot loop in both shapes, the Python fallback
        # derives its per-block mapping from the same stripe tuple
        # (reference: calcFileIdxAndOffsetStriped, LocalWorker.cpp:2084)
        self._rw_block_sized(
            self._path_fds[0], gen, is_write,
            stripe=(list(self._path_fds), cfg.file_size)
            if num_files > 1 else None)

    def _make_file_mode_offset_gen(self, is_write: bool, total_range: int):
        """Per-worker share of the shared file/bdev range: seq mode slices a
        contiguous range per dataset thread; rand mode divides randamount;
        --strided interleaves blocks (reference: initPhaseRWOffsetGen +
        SURVEY.md section 2.4 "Shared-file striping")."""
        cfg = self.cfg
        bs = cfg.block_size
        ndst = max(1, cfg.num_dataset_threads)
        rank = self.rank % ndst
        if cfg.use_random_offsets:
            amount_total = cfg.random_amount or total_range
            amount = amount_total // ndst
            if amount < bs:
                return None
            if cfg.no_random_align:
                return OffsetGenRandom(self._rand_offset_algo, amount, bs,
                                       range_len=total_range)
            if is_write:
                return OffsetGenRandomAlignedFullCoverage(
                    self._rand_offset_algo, amount, bs, range_len=total_range)
            return OffsetGenRandomAligned(self._rand_offset_algo, amount, bs,
                                          range_len=total_range)
        if cfg.do_strided_access:
            num_blocks = total_range // bs
            blocks_per_worker = num_blocks // ndst + \
                (1 if rank < num_blocks % ndst else 0)
            if not blocks_per_worker:
                return None
            return OffsetGenStrided(blocks_per_worker * bs, bs, rank, ndst)
        # sequential contiguous slice per dataset thread
        slice_len = total_range // ndst
        slice_start = rank * slice_len
        if rank == ndst - 1:
            slice_len = total_range - slice_start  # last takes remainder
        if not slice_len:
            return None
        if not is_write and cfg.shuffle_window:
            # shared-file shape: each worker permutes its own
            # contiguous slice with the common epoch+rank seed
            return self._make_shuffle_gen(slice_len, start=slice_start)
        if cfg.do_reverse_seq_offsets:
            return OffsetGenReverseSeq(slice_len, bs, start=slice_start)
        return OffsetGenSequential(slice_len, bs, start=slice_start)

    # ------------------------------------------------------------------
    # custom tree mode (reference: dirModeIterateCustomDirs :2960/:3294)
    # ------------------------------------------------------------------

    def _custom_tree_iterate_files(self, phase: BenchPhase) -> None:
        from ..toolkits.path_store import PathStore
        cfg = self.cfg
        store = PathStore(block_size=cfg.block_size)
        if phase in (BenchPhase.CREATEDIRS, BenchPhase.DELETEDIRS):
            store.load_dirs_from_file(cfg.tree_file_path)
        else:
            store.load_files_from_file(cfg.tree_file_path,
                                       round_up_size=cfg.tree_round_up_size)
        if cfg.use_custom_tree_rand:
            store.random_shuffle(seed=42)  # same order on all hosts
        else:
            store.sort_by_path_len()
        ndst = max(1, cfg.num_dataset_threads)
        rank = self.rank % ndst
        non_shared, shared = store.split_by_share_size(
            cfg.file_share_size or (cfg.block_size * ndst))
        my_files = non_shared.get_worker_sublist_non_shared(rank, ndst).elems
        if cfg.use_custom_tree_round_robin:
            my_files += shared.get_worker_sublist_shared_round_robin(
                rank, ndst).elems
        else:
            my_files += shared.get_worker_sublist_shared(rank, ndst).elems
        base = cfg.paths[0]
        if phase == BenchPhase.DELETEFILES:
            # only one worker deletes a shared file (the slice at offset
            # 0); skipped slices are no phase work — identical accounting
            # on the native and fallback paths
            my_files = [e for e in my_files if e.range_start == 0]
            if not my_files:
                self.got_phase_work = False
                return
        from ..utils.native import get_native_engine
        native = get_native_engine()
        if self._can_use_native_file_loop(native, phase):
            self._run_native_tree_loop(native, phase, base, my_files)
            return
        for elem in my_files:
            self.check_interruption_request(force=True)
            path = os.path.join(base, elem.path)
            t0 = time.perf_counter_ns()
            if phase == BenchPhase.CREATEFILES:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                fd = os.open(path, self._open_flags_write(), MKFILE_MODE)
                try:
                    if elem.range_len:
                        gen = OffsetGenSequential(elem.range_len,
                                                 cfg.block_size,
                                                 start=elem.range_start)
                        self._rw_block_sized(fd, gen, is_write=True)
                finally:
                    os.close(fd)
            elif phase == BenchPhase.READFILES:
                flags = os.O_RDONLY | (os.O_DIRECT if cfg.use_direct_io else 0)
                fd = os.open(path, flags)
                try:
                    if elem.range_len:
                        gen = OffsetGenSequential(elem.range_len,
                                                 cfg.block_size,
                                                 start=elem.range_start)
                        self._rw_block_sized(fd, gen, is_write=False)
                finally:
                    os.close(fd)
            elif phase == BenchPhase.STATFILES:
                os.stat(path)
            elif phase == BenchPhase.DELETEFILES:
                try:  # non-zero shared slices were filtered out above
                    os.unlink(path)
                except FileNotFoundError:
                    if not cfg.ignore_delete_errors \
                            and not self._partial_tolerance(phase):
                        raise
            lat_usec = (time.perf_counter_ns() - t0) // 1000
            self.entries_latency_histo.add_latency(lat_usec)
            self.live_ops.num_entries_done += 1

    def _run_native_tree_loop(self, native, phase: BenchPhase, base: str,
                              my_files) -> None:
        """Custom-tree files through the C++ file loop with per-file byte
        ranges (shared-file slices keep their [range_start, range_len))."""
        cfg = self.cfg
        op = self._NATIVE_FILE_OPS[phase]
        if phase == BenchPhase.CREATEFILES:
            open_flags = self._open_flags_write()
            # dirs are created up front (the reference pre-creates the
            # tree's dirs in their own phase; mkdir is not per-file work)
            for d in {os.path.dirname(os.path.join(base, e.path))
                      for e in my_files}:
                os.makedirs(d or ".", exist_ok=True)
        else:
            open_flags = os.O_RDONLY | (os.O_DIRECT if cfg.use_direct_io
                                        else 0)
        paths: "list[str]" = []
        starts: "list[int]" = []
        lens: "list[int]" = []
        chunk_bytes = 0

        from ..utils.native import NativeVerifyError

        def submit():
            self.check_interruption_request(force=True)

            def call(paths=paths, starts=starts, lens=lens):
                native.run_file_loop(
                    paths, op, open_flags, cfg.file_size, cfg.block_size,
                    buf_addr=self._buf_addr() if self._io_bufs else 0,
                    ignore_delete_errors=cfg.ignore_delete_errors
                    or self._partial_tolerance(phase),
                    worker=self, interrupt_flag=self._native_interrupt,
                    ranges=(starts, lens) if op in ("write", "read")
                    else None,
                    verify_salt=cfg.integrity_check_salt,
                    block_var_pct=cfg.block_variance_pct,
                    block_var_seed=self._block_var_seed(),
                    rwmix_pct=cfg.rwmix_read_pct
                    if phase == BenchPhase.CREATEFILES else 0,
                    limit_read_bps=cfg.limit_read_bps,
                    limit_write_bps=cfg.limit_write_bps,
                    rl_state=self._native_rl_state,
                    inline_readback=(cfg.do_read_inline
                                     or cfg.do_direct_verify),
                    flock_mode=self._flock_mode_code())

            try:
                self._retrying_native(call, retryable=op != "unlink")
            except NativeVerifyError as err:
                # map the global block index back through the per-file
                # [range_start, range_len) slices
                blk = err.block_idx
                hint = self._verify_fail_hint(err.got)
                for path, r_start, r_len in zip(paths, starts, lens):
                    # zero-length files contribute zero blocks, exactly
                    # like the engine's per-file block count
                    n_blocks = (r_len + cfg.block_size - 1) \
                        // cfg.block_size
                    if blk < n_blocks:
                        off = r_start + blk * cfg.block_size \
                            + err.word_idx * 8
                        raise WorkerException(
                            f"data integrity check failed at file offset "
                            f"{off} of {path}: expected {err.want:#x}, "
                            f"got {err.got:#x}{hint}") from None
                    blk -= n_blocks
                raise WorkerException(
                    f"data integrity check failed (block {err.block_idx}): "
                    f"expected {err.want:#x}, got {err.got:#x}{hint}"
                ) from None

        for elem in my_files:
            paths.append(os.path.join(base, elem.path))
            starts.append(elem.range_start)
            lens.append(elem.range_len)
            chunk_bytes += elem.range_len
            if len(paths) >= self._NATIVE_CHUNK_MAX_BLOCKS \
                    or chunk_bytes >= self._NATIVE_CHUNK_MAX_BYTES:
                submit()
                paths, starts, lens, chunk_bytes = [], [], [], 0
        if paths:
            submit()

    # ------------------------------------------------------------------
    # sync / dropcaches (reference: anyModeSync :8075 / DropCaches :8118)
    # ------------------------------------------------------------------

    def _any_mode_sync(self) -> None:
        """Only the first worker syncs; others report no phase work."""
        if self.rank % max(1, self.cfg.num_threads) != 0:
            self.got_phase_work = False
            return
        os.sync()
        self.live_ops.num_entries_done += 1

    def _any_mode_drop_caches(self) -> None:
        if self.rank % max(1, self.cfg.num_threads) != 0:
            self.got_phase_work = False
            return
        try:
            with open("/proc/sys/vm/drop_caches", "w") as f:
                f.write("3")
        except PermissionError as err:
            raise WorkerException(
                "dropping caches requires root privileges") from err
        self.live_ops.num_entries_done += 1
