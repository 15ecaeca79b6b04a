"""ctypes loader for the native C++ ioengine (csrc/libioengine.so).

The reference's hot I/O loops are native C++ (rwBlockSized
LocalWorker.cpp:1702, aioBlockSized :1828 via libaio); this framework keeps
that property: the block loop runs in C++ when available and falls back to
the pure-Python loop otherwise (tests, unsupported workload features).

Build: ``make -C csrc`` (g++; no external deps beyond libaio if present).
"""

from __future__ import annotations

import ctypes
import errno as errno_mod
import os
import threading

_lock = threading.Lock()
_engine = None
_engine_checked = False

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# repo checkout layout (csrc/ beside the package) — buildable via make
_SO_PATH = os.path.join(os.path.dirname(_PKG_DIR), "csrc", "libioengine.so")
# installed layout (deb/rpm/wheel ship the prebuilt .so inside the package)
_SO_PATH_INSTALLED = os.path.join(_PKG_DIR, "_native", "libioengine.so")


# engine selector values (must match csrc/ioengine.cpp)
ENGINE_CODES = {"auto": 0, "sync": 1, "aio": 2, "uring": 3}
#: reverse map for logs/diagnostics (single owner of the naming)
ENGINE_NAMES = {code: name for name, code in ENGINE_CODES.items()}

# ABI generation expected from the .so; ioengine_version() reports
# "elbencho-tpu ioengine <N> (...)". A mismatch means a stale binary
# (e.g. installed prebuilt vs newer source) — refuse it rather than run
# benchmarks against outdated native code.
EXPECTED_ABI = 11

#: ioengine_pool_features bits (csrc POOL_FEAT_*)
POOL_FEAT_URING = 1
POOL_FEAT_FIXED_BUFFERS = 2
POOL_FEAT_SQPOLL = 4

#: ioengine_stream_set_fault kinds (csrc STREAM_FAULT_*; TEST ONLY —
#: config validation rejects the env knob outside a test harness)
STREAM_FAULT_KINDS = {"eio": 1, "short": 2, "hang": 3}


def parse_fault_spec(spec: str) -> "tuple[int, int, int]":
    """Parse the ELBENCHO_TPU_IO_FAULT test knob: "kind:every_n[:seed]"
    (kind in eio|short|hang) -> (seed, every_n, kind_code). Raises
    ValueError on a malformed spec so a typo fails loudly in the harness
    instead of silently injecting nothing."""
    parts = spec.split(":")
    if len(parts) not in (2, 3) or parts[0] not in STREAM_FAULT_KINDS:
        raise ValueError(
            f"malformed ELBENCHO_TPU_IO_FAULT {spec!r} (want "
            f"'eio|short|hang:EVERY_N[:SEED]')")
    every_n = int(parts[1])
    seed = int(parts[2]) if len(parts) == 3 else 0
    if every_n <= 0:
        raise ValueError("ELBENCHO_TPU_IO_FAULT every_n must be > 0")
    return seed, every_n, STREAM_FAULT_KINDS[parts[0]]

_EILSEQ = errno_mod.EILSEQ  # engine's verify-mismatch return code


class NativeVerifyError(Exception):
    """In-loop data integrity check failed (ioengine -EILSEQ). Carries the
    exact mismatch location so the caller can report the file offset the
    way postReadIntegrityCheckVerifyBuf does (LocalWorker.cpp:2170)."""

    def __init__(self, block_idx: int, word_idx: int, want: int, got: int):
        self.block_idx = block_idx
        self.word_idx = word_idx
        self.want = want
        self.got = got
        super().__init__(f"integrity check failed at block {block_idx} "
                         f"word {word_idx}")


def _as_ptr(values, n, np_dtype_name, c_type):
    """ctypes view of a numpy array (zero-copy) or python list."""
    import numpy as np
    if isinstance(values, np.ndarray):
        arr = np.ascontiguousarray(values, dtype=np.dtype(np_dtype_name))
        ptr = arr.ctypes.data_as(ctypes.POINTER(c_type))
        ptr._keepalive = arr  # the view must outlive the native call
        return ptr
    return (c_type * n)(*values)


def _as_u64_ptr(values, n):
    return _as_ptr(values, n, "uint64", ctypes.c_uint64)


def _account_chunk(worker, lat_arr, lengths_np, n: int, bytes_done: int,
                   total_bytes: int, op_is_read, done=None) -> None:
    """Post-chunk accounting shared by the block and mmap loops: on a
    complete chunk, latencies and counters are attributed exactly (split
    into the rwmix-read counters when per-op flags are present). On an
    interrupted chunk, ``done`` (a byte per op, 1 where it completed; the
    fused stream loop knows it) books exactly the completed ops, their
    latencies included. Without it, completions can be out of order
    (AIO), so only the done-prefix estimate is booked and latencies are
    skipped — with flags the prefix split keeps the read/write ratio
    roughly right (exact for the in-order sync/mmap paths)."""
    import numpy as np
    if bytes_done == total_bytes or done is not None:
        lat = np.frombuffer(lat_arr, dtype=np.uint64)
        if bytes_done != total_bytes:
            mask = np.frombuffer(done, dtype=np.bool_)
            lat, lengths_np = lat[mask], lengths_np[mask]
            if op_is_read is not None:
                op_is_read = op_is_read[mask]
            n_done = len(lat)
        else:
            n_done = n
        if op_is_read is not None and op_is_read.any():
            rd = op_is_read.astype(bool)
            worker.iops_latency_histo_rwmix.add_latencies_array(lat[rd])
            worker.iops_latency_histo.add_latencies_array(lat[~rd])
            n_read = int(rd.sum())
            read_bytes = int(lengths_np[rd].sum())
            worker.live_ops_rwmix_read.num_iops_done += n_read
            worker.live_ops_rwmix_read.num_bytes_done += read_bytes
            worker.live_ops.num_iops_done += n_done - n_read
            worker.live_ops.num_bytes_done += bytes_done - read_bytes
        else:
            worker.iops_latency_histo.add_latencies_array(lat)
            worker.live_ops.num_iops_done += n_done
            worker.live_ops.num_bytes_done += bytes_done
    else:
        avg_len = max(total_bytes // n, 1)
        done_ops = min(n, bytes_done // avg_len)
        if op_is_read is not None and done_ops:
            rd = op_is_read[:done_ops].astype(bool)
            n_read = int(rd.sum())
            read_bytes = int(lengths_np[:done_ops][rd].sum())
            worker.live_ops_rwmix_read.num_iops_done += n_read
            worker.live_ops_rwmix_read.num_bytes_done += read_bytes
            worker.live_ops.num_iops_done += done_ops - n_read
            worker.live_ops.num_bytes_done += \
                max(bytes_done - read_bytes, 0)
        else:
            worker.live_ops.num_iops_done += done_ops
            worker.live_ops.num_bytes_done += bytes_done
    worker._num_iops_submitted += n
    pool = getattr(worker, "_staging_pool", None)
    if pool is not None:
        # staging-slot reuse accounting at the one seam every array
        # path (native block/mmap loops, fused stream) flows through
        pool.account_ops(n)
    worker.create_stonewall_stats_if_triggered()


class NativeStreamError(OSError):
    """Stream open/submit/reap failed inside the engine (-errno)."""

    def __init__(self, errno_val: int, what: str):
        super().__init__(errno_val, f"{os.strerror(errno_val)} ({what})")


class NativePoolError(OSError):
    """Pool ring open failed inside the engine (-errno) — the caller's
    cue to log the loud per-call-registration fallback."""

    def __init__(self, errno_val: int, what: str):
        super().__init__(errno_val, f"{os.strerror(errno_val)} ({what})")


class NativePool:
    """Persistent registered-buffer pool ring (ioengine_pool_*; ABI 11):
    the staging pool's slab registered ONCE as io_uring fixed buffers,
    shared by the classic block loop (run_block_loop(pool=...)) and the
    streaming producer mode (open_stream(pool=...)). Optionally SQPOLL —
    kernel submission-queue polling, no io_uring_enter on the submit
    path. The slot buffers belong to the caller (utils/staging_pool.py)
    and must stay mapped until close() returned."""

    def __init__(self, lib: ctypes.CDLL, slot_addrs, slot_size: int,
                 want_sqpoll: bool = False, sqpoll_idle_ms: int = 2000):
        self._lib = lib
        self._handle = None
        n_slots = len(slot_addrs)
        self.n_slots = n_slots
        self.slot_size = slot_size
        addr_arr = (ctypes.c_uint64 * n_slots)(*slot_addrs)
        err = ctypes.c_int(0)
        handle = lib.ioengine_pool_open(
            addr_arr, n_slots, slot_size, 1 if want_sqpoll else 0,
            max(sqpoll_idle_ms, 0), ctypes.byref(err))
        if not handle:
            raise NativePoolError(-err.value or errno_mod.EINVAL,
                                  "pool open")
        self._handle = handle
        feats = int(lib.ioengine_pool_features(handle))
        self.fixed_buffers = bool(feats & POOL_FEAT_FIXED_BUFFERS)
        self.sqpoll_active = bool(feats & POOL_FEAT_SQPOLL)

    @property
    def handle(self):
        return self._handle

    def close(self) -> int:
        """0, or -EBUSY while a pooled stream still owns the ring (the
        stream's close drains kernel DMA out of the slab first)."""
        ret = 0
        if self._handle is not None:
            ret = self._lib.ioengine_pool_close(self._handle)
            if ret == 0:
                self._handle = None
        return ret

    def __del__(self):  # belt-and-braces: never leak a kernel ring
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass


class NativeStream:
    """Submission/completion ring over registered staging slots
    (ioengine_stream_*): up to len(slot_addrs) io_uring reads/writes in
    flight with the GIL released, reaped slot-by-slot so the caller can
    overlap storage I/O with TPU HBM transfers (the fused loop of
    workers/local_worker.py). One in-flight op per slot — the engine
    returns -EBUSY on a violation of the slot-reuse discipline.

    With ``pool`` (a NativePool), the stream borrows the pool's
    PERSISTENT ring instead of building its own: no ring setup and no
    buffer registration on this open — the slab was registered once at
    pool open (slot i of the stream is pool slot i). Falls back to an
    owned ring when the pool ring is unavailable/busy."""

    #: reap batch bound (cq depth can reach 2x sq entries)
    _MAX_EVENTS = 64

    def __init__(self, lib: ctypes.CDLL, fds, slot_addrs, slot_size: int,
                 pool: "NativePool | None" = None):
        self._lib = lib
        self._handle = None
        n_slots = len(slot_addrs)
        self.n_slots = n_slots
        fds_arr = (ctypes.c_int * len(fds))(*fds)
        handle = None
        self.pooled = False
        if pool is not None and pool.handle is not None \
                and pool.n_slots == n_slots:
            err = ctypes.c_int(0)
            handle = lib.ioengine_stream_open_pooled(
                pool.handle, fds_arr, len(fds), ctypes.byref(err))
            self.pooled = bool(handle)
        if not handle:
            addr_arr = (ctypes.c_uint64 * n_slots)(*slot_addrs)
            err = ctypes.c_int(0)
            handle = lib.ioengine_stream_open(
                fds_arr, len(fds), addr_arr, n_slots, slot_size,
                ctypes.byref(err))
        if not handle:
            raise NativeStreamError(-err.value or errno_mod.EINVAL,
                                    "stream open")
        self._handle = handle
        #: registration/SQPOLL audit hooks (pool counters ride on these)
        self.fixed_buffers = bool(
            lib.ioengine_stream_fixed_buffers(handle))
        self.sqpoll = bool(lib.ioengine_stream_sqpoll(handle))
        #: ENGINE_CODES value of the backend THIS ring runs on (the open
        #: may fall back from uring to AIO; pins/logs must use this)
        self.backend = int(lib.ioengine_stream_backend_of(handle))
        self.backend_name = ENGINE_NAMES.get(self.backend, "none")
        max_ev = max(self._MAX_EVENTS, 2 * n_slots)
        self._out_slots = (ctypes.c_uint32 * max_ev)()
        self._out_lat = (ctypes.c_uint64 * max_ev)()
        self._out_res = (ctypes.c_int64 * max_ev)()
        self._max_events = max_ev

    def submit(self, slot: int, fd_idx: int, offset: int, length: int,
               is_write: bool) -> None:
        ret = self._lib.ioengine_stream_submit(
            self._handle, slot, fd_idx, offset, length,
            1 if is_write else 0)
        if ret < 0:
            raise NativeStreamError(-ret, f"stream submit slot {slot}")

    def reap(self, min_complete: int = 1, timeout_msecs: int = 1000,
             interrupt_flag=None) -> "list[tuple[int, int, int]]":
        """Blocking (bounded, interruptible) harvest; returns
        [(slot, lat_usec, res), ...] — res is the raw per-op result
        (bytes moved, or -errno), checked by the caller so a short read
        mid-stream surfaces with its slot context."""
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))
        got = self._lib.ioengine_stream_reap(
            self._handle, min_complete, timeout_msecs, self._out_slots,
            self._out_lat, self._out_res, self._max_events,
            ctypes.byref(interrupt))
        if got < 0:
            raise NativeStreamError(-got, "stream reap")
        return [(self._out_slots[i], self._out_lat[i], self._out_res[i])
                for i in range(got)]

    def inflight(self) -> int:
        return self._lib.ioengine_stream_inflight(self._handle)

    def set_timeout(self, timeout_usec: int) -> None:
        """--iotimeout: per-op deadline. Ops older than this at reap time
        are cancelled and surface as res == -ETIMEDOUT with their slot
        re-armed (0 disarms)."""
        ret = self._lib.ioengine_stream_set_timeout(self._handle,
                                                    max(timeout_usec, 0))
        if ret < 0:
            raise NativeStreamError(-ret, "stream set_timeout")

    def set_fault(self, seed: int, every_n: int, kind: int) -> None:
        """Deterministic fault injection (TEST ONLY; STREAM_FAULT_KINDS).
        Op k (by submit order) is faulted when (k+seed) % every_n == 0."""
        ret = self._lib.ioengine_stream_set_fault(self._handle, seed,
                                                  every_n, kind)
        if ret < 0:
            raise NativeStreamError(-ret, "stream set_fault")

    def set_fault_from_spec(self, spec: str) -> None:
        seed, every_n, kind = parse_fault_spec(spec)
        self.set_fault(seed, every_n, kind)

    def cancel(self, slot: int) -> None:
        """Request cancellation of the slot's in-flight op; its completion
        surfaces via reap (-ECANCELED, or the real result if the op beat
        the cancel). -ENOENT (no in-flight op) is not an error here."""
        ret = self._lib.ioengine_stream_cancel(self._handle, slot)
        if ret < 0 and ret != -errno_mod.ENOENT:
            raise NativeStreamError(-ret, f"stream cancel slot {slot}")

    def oldest_age_usec(self) -> int:
        """Age of the oldest in-flight op (op-age tracking; 0 = idle)."""
        return int(self._lib.ioengine_stream_oldest_age_usec(self._handle))

    def close(self) -> int:
        """Drains outstanding kernel DMA before the ring is torn down;
        idempotent. Returns 0, or -errno when the drain had to be
        aborted with ops still kernel-owned — the caller must then keep
        the slot buffers mapped for the life of the process (a late
        completion DMAs into them)."""
        ret = 0
        if self._handle is not None:
            ret = self._lib.ioengine_stream_close(self._handle)
            self._handle = None
        return ret

    def __enter__(self) -> "NativeStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # belt-and-braces: never leak a kernel ring
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass


class _NativeEngine:
    """Thin wrapper over libioengine.so. See csrc/ioengine.cpp for the ABI."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.ioengine_run_block_loop4.restype = ctypes.c_int
        lib.ioengine_run_block_loop4.argtypes = [
            ctypes.POINTER(ctypes.c_int),     # fds
            ctypes.POINTER(ctypes.c_uint32),  # per-block fd index (or None)
            ctypes.POINTER(ctypes.c_uint64),  # offsets
            ctypes.POINTER(ctypes.c_uint64),  # lengths
            ctypes.c_uint64,                  # num_blocks
            ctypes.c_int,                     # is_write
            ctypes.c_void_p,                  # buffer
            ctypes.c_uint64,                  # buffer size
            ctypes.c_int,                     # iodepth
            ctypes.POINTER(ctypes.c_uint64),  # out: latencies (usec/block)
            ctypes.POINTER(ctypes.c_uint64),  # out: bytes done
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
            ctypes.c_int,                     # engine (ENGINE_CODES)
            ctypes.POINTER(ctypes.c_ubyte),   # rwmix per-op read flags
            ctypes.c_uint64,                  # verify salt
            ctypes.c_int,                     # do_verify
            ctypes.c_int,                     # block variance pct
            ctypes.c_uint64,                  # block variance seed
            ctypes.POINTER(ctypes.c_uint64),  # out: verify mismatch info[4]
            ctypes.c_uint64,                  # read rate limit (bytes/s)
            ctypes.c_uint64,                  # write rate limit (bytes/s)
            ctypes.POINTER(ctypes.c_uint64),  # in/out rate windows [4]
            ctypes.c_int,                     # inline readback (sync only)
            ctypes.c_int,                     # flock mode 0|1=range|2=full
            ctypes.c_int,                     # opslog fd (-1 = off)
            ctypes.c_int,                     # opslog flock
            ctypes.c_int,                     # worker rank (for records)
        ]
        lib.ioengine_uring_supported.restype = ctypes.c_int
        lib.ioengine_uring_supported.argtypes = []
        # resolved here so a stale .so missing any symbol downgrades to
        # the pure-Python fallback via get_native_engine's AttributeError
        # catch instead of crashing at call time
        lib.ioengine_version.restype = ctypes.c_char_p
        lib.ioengine_version.argtypes = []
        lib.ioengine_run_mmap_loop3.restype = ctypes.c_int
        lib.ioengine_run_mmap_loop3.argtypes = [
            ctypes.c_void_p,                  # mapping base address
            ctypes.POINTER(ctypes.c_uint64),  # offsets
            ctypes.POINTER(ctypes.c_uint64),  # lengths
            ctypes.c_uint64,                  # num blocks
            ctypes.c_int,                     # is_write
            ctypes.c_void_p,                  # io buffer
            ctypes.POINTER(ctypes.c_uint64),  # out: latencies
            ctypes.POINTER(ctypes.c_uint64),  # out: bytes done
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
            ctypes.POINTER(ctypes.c_ubyte),   # rwmix per-op read flags
            ctypes.c_uint64,                  # verify salt
            ctypes.c_int,                     # do_verify
            ctypes.c_int,                     # block variance pct
            ctypes.c_uint64,                  # block variance seed
            ctypes.POINTER(ctypes.c_uint64),  # out: verify mismatch info[4]
            ctypes.c_uint64,                  # read rate limit (bytes/s)
            ctypes.c_uint64,                  # write rate limit (bytes/s)
            ctypes.POINTER(ctypes.c_uint64),  # in/out rate windows [4]
        ]
        lib.ioengine_net_client_loop.restype = ctypes.c_int
        lib.ioengine_net_client_loop.argtypes = [
            ctypes.c_int,                     # connected socket fd
            ctypes.c_void_p,                  # request payload
            ctypes.c_uint64,                  # block (request) size
            ctypes.c_uint64,                  # response size
            ctypes.c_uint64,                  # number of round trips
            ctypes.POINTER(ctypes.c_uint64),  # out: latencies
            ctypes.POINTER(ctypes.c_uint64),  # out: bytes moved
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
        ]
        lib.ioengine_net_server_loop.restype = ctypes.c_int
        lib.ioengine_net_server_loop.argtypes = [
            ctypes.POINTER(ctypes.c_int),     # connection fds
            ctypes.c_uint64,                  # number of connections
            ctypes.POINTER(ctypes.c_uint64),  # in/out per-conn state
            ctypes.c_uint64,                  # block size
            ctypes.c_uint64,                  # response size
            ctypes.c_void_p,                  # response payload
            ctypes.c_uint64,                  # max responses this slice
            ctypes.c_uint64,                  # slice duration msecs
            ctypes.POINTER(ctypes.c_uint64),  # out: latencies
            ctypes.POINTER(ctypes.c_uint64),  # out: bytes moved
            ctypes.POINTER(ctypes.c_uint64),  # out: responses sent
            ctypes.POINTER(ctypes.c_uint64),  # out: open connections left
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
        ]
        lib.ioengine_stream_open.restype = ctypes.c_void_p
        lib.ioengine_stream_open.argtypes = [
            ctypes.POINTER(ctypes.c_int),     # fds
            ctypes.c_uint32,                  # num fds
            ctypes.POINTER(ctypes.c_uint64),  # slot base addresses
            ctypes.c_uint64,                  # num slots
            ctypes.c_uint64,                  # slot size (bytes)
            ctypes.POINTER(ctypes.c_int),     # out: -errno on failure
        ]
        lib.ioengine_stream_submit.restype = ctypes.c_int
        lib.ioengine_stream_submit.argtypes = [
            ctypes.c_void_p,                  # stream handle
            ctypes.c_uint32,                  # slot index
            ctypes.c_uint32,                  # fd index
            ctypes.c_uint64,                  # file offset
            ctypes.c_uint64,                  # length
            ctypes.c_int,                     # is_write
        ]
        lib.ioengine_stream_reap.restype = ctypes.c_int
        lib.ioengine_stream_reap.argtypes = [
            ctypes.c_void_p,                  # stream handle
            ctypes.c_int,                     # min completions to wait for
            ctypes.c_int,                     # timeout msecs
            ctypes.POINTER(ctypes.c_uint32),  # out: completed slot indices
            ctypes.POINTER(ctypes.c_uint64),  # out: latencies (usec)
            ctypes.POINTER(ctypes.c_int64),   # out: raw cqe results
            ctypes.c_int,                     # max events
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
        ]
        lib.ioengine_stream_inflight.restype = ctypes.c_int
        lib.ioengine_stream_inflight.argtypes = [ctypes.c_void_p]
        lib.ioengine_stream_close.restype = ctypes.c_int
        lib.ioengine_stream_close.argtypes = [ctypes.c_void_p]
        # ABI 10: per-op deadlines, cancellation, fault injection
        lib.ioengine_stream_set_timeout.restype = ctypes.c_int
        lib.ioengine_stream_set_timeout.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_uint64]
        lib.ioengine_stream_set_fault.restype = ctypes.c_int
        lib.ioengine_stream_set_fault.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int]
        lib.ioengine_stream_cancel.restype = ctypes.c_int
        lib.ioengine_stream_cancel.argtypes = [ctypes.c_void_p,
                                               ctypes.c_uint32]
        lib.ioengine_stream_oldest_age_usec.restype = ctypes.c_int64
        lib.ioengine_stream_oldest_age_usec.argtypes = [ctypes.c_void_p]
        lib.ioengine_stream_backend.restype = ctypes.c_int
        lib.ioengine_stream_backend.argtypes = []
        lib.ioengine_stream_backend_of.restype = ctypes.c_int
        lib.ioengine_stream_backend_of.argtypes = [ctypes.c_void_p]
        # ABI 11: registered-buffer staging pool + SQPOLL
        lib.ioengine_pool_open.restype = ctypes.c_void_p
        lib.ioengine_pool_open.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),  # slot base addresses
            ctypes.c_uint64,                  # num slots
            ctypes.c_uint64,                  # slot size (bytes)
            ctypes.c_int,                     # want SQPOLL
            ctypes.c_uint32,                  # SQPOLL idle timeout (ms)
            ctypes.POINTER(ctypes.c_int),     # out: -errno on failure
        ]
        lib.ioengine_pool_features.restype = ctypes.c_int
        lib.ioengine_pool_features.argtypes = [ctypes.c_void_p]
        lib.ioengine_pool_close.restype = ctypes.c_int
        lib.ioengine_pool_close.argtypes = [ctypes.c_void_p]
        lib.ioengine_sqpoll_supported.restype = ctypes.c_int
        lib.ioengine_sqpoll_supported.argtypes = []
        lib.ioengine_stream_open_pooled.restype = ctypes.c_void_p
        lib.ioengine_stream_open_pooled.argtypes = [
            ctypes.c_void_p,                  # pool handle
            ctypes.POINTER(ctypes.c_int),     # fds
            ctypes.c_uint32,                  # num fds
            ctypes.POINTER(ctypes.c_int),     # out: -errno on failure
        ]
        lib.ioengine_stream_fixed_buffers.restype = ctypes.c_int
        lib.ioengine_stream_fixed_buffers.argtypes = [ctypes.c_void_p]
        lib.ioengine_stream_sqpoll.restype = ctypes.c_int
        lib.ioengine_stream_sqpoll.argtypes = [ctypes.c_void_p]
        lib.ioengine_run_block_loop5.restype = ctypes.c_int
        lib.ioengine_run_block_loop5.argtypes = \
            [ctypes.c_void_p] + list(lib.ioengine_run_block_loop4.argtypes) \
            + [ctypes.POINTER(ctypes.c_uint64)]  # out: pool stats[3]
        self._stream_backend = None  # kernel capability, probed once
        lib.ioengine_run_file_loop3.restype = ctypes.c_int
        lib.ioengine_run_file_loop3.argtypes = [
            ctypes.c_char_p,                  # NUL-separated paths blob
            ctypes.POINTER(ctypes.c_uint32),  # per-path blob offsets
            ctypes.c_uint64,                  # num files
            ctypes.c_int,                     # op (FILE_OPS)
            ctypes.c_int,                     # open flags
            ctypes.c_uint64,                  # file size
            ctypes.c_uint64,                  # block size
            ctypes.c_void_p,                  # io buffer
            ctypes.POINTER(ctypes.c_uint64),  # per-file range starts (opt)
            ctypes.POINTER(ctypes.c_uint64),  # per-file range lengths (opt)
            ctypes.c_int,                     # ignore delete errors
            ctypes.POINTER(ctypes.c_uint64),  # out: entry latencies
            ctypes.POINTER(ctypes.c_uint64),  # out: block latencies
            ctypes.POINTER(ctypes.c_uint64),  # out: bytes done
            ctypes.POINTER(ctypes.c_uint64),  # out: entries done
            ctypes.POINTER(ctypes.c_uint64),  # out: failing file index
            ctypes.POINTER(ctypes.c_int),     # interrupt flag
            ctypes.c_uint64,                  # verify salt
            ctypes.c_int,                     # do_verify
            ctypes.c_int,                     # block variance pct
            ctypes.c_uint64,                  # block variance seed
            ctypes.c_int,                     # rwmix read pct (write op)
            ctypes.c_uint64,                  # rwmix base (rank+submitted)
            ctypes.POINTER(ctypes.c_uint64),  # out: verify mismatch info[4]
            ctypes.POINTER(ctypes.c_uint64),  # out: rwmix {blocks, bytes}
            ctypes.c_uint64,                  # read rate limit (bytes/s)
            ctypes.c_uint64,                  # write rate limit (bytes/s)
            ctypes.POINTER(ctypes.c_uint64),  # in/out rate windows [4]
            ctypes.c_int,                     # inline readback (write op)
            ctypes.c_int,                     # flock mode 0|1=range|2=full
        ]

    def uring_supported(self) -> bool:
        return bool(self._lib.ioengine_uring_supported())

    def sqpoll_supported(self) -> bool:
        """--iosqpoll capability probe: can this process get an SQPOLL
        ring (kernel 5.11+ for unprivileged; policy may refuse)."""
        return bool(self._lib.ioengine_sqpoll_supported())

    def open_pool(self, slot_addrs, slot_size: int,
                  want_sqpoll: bool = False,
                  sqpoll_idle_ms: int = 2000) -> NativePool:
        """Open the persistent registered-buffer pool ring over the
        staging slab (see NativePool); raises NativePoolError when the
        kernel cannot provide a ring — callers log the loud fallback to
        the per-call registration paths."""
        return NativePool(self._lib, slot_addrs, slot_size,
                          want_sqpoll=want_sqpoll,
                          sqpoll_idle_ms=sqpoll_idle_ms)

    def stream_supported(self) -> bool:
        """Streaming producer mode: io_uring primary, kernel-AIO tier."""
        return self.stream_backend() != 0

    def stream_backend(self) -> int:
        """ENGINE_CODES value of the backend a stream would PREDICTABLY
        use on this kernel: 3 = io_uring, 2 = kernel AIO, 0 =
        unavailable. Probed once (it creates and destroys a ring); the
        kernel capability cannot change mid-run. A live stream reports
        its ACTUAL backend via NativeStream.backend — the open can still
        fall back to AIO (e.g. ENOMEM on the ring mmaps at a large slot
        count), so engine pins must check the stream, not this."""
        if self._stream_backend is None:
            self._stream_backend = int(self._lib.ioengine_stream_backend())
        return self._stream_backend

    def stream_backend_name(self) -> str:
        return ENGINE_NAMES.get(self.stream_backend(), "none")

    def open_stream(self, fds, slot_addrs, slot_size: int,
                    pool: "NativePool | None" = None) -> NativeStream:
        """Open a submission/completion ring over the given staging slots
        (see NativeStream); raises NativeStreamError when the kernel
        cannot provide one (callers fall back to the Python loop). With
        ``pool``, the stream borrows the pool's persistent ring and its
        once-registered fixed buffers instead of building its own."""
        return NativeStream(self._lib, fds, slot_addrs, slot_size,
                            pool=pool)

    def version(self) -> str:
        return self._lib.ioengine_version().decode()

    def abi_version(self) -> int:
        # "elbencho-tpu ioengine <N> (...)" -> N; 0 if unparseable
        parts = self.version().split()
        try:
            return int(parts[2])
        except (IndexError, ValueError):
            return 0

    #: op codes of ioengine_run_file_loop (csrc/ioengine.cpp FILE_OP_*)
    FILE_OPS = {"write": 0, "read": 1, "stat": 2, "unlink": 3}

    def run_file_loop(self, paths: "list[str]", op: str, open_flags: int,
                      file_size: int, block_size: int, buf_addr: int,
                      ignore_delete_errors: bool, worker,
                      interrupt_flag=None, ranges=None,
                      verify_salt: int = 0, block_var_pct: int = 0,
                      block_var_seed: int = 0,
                      rwmix_pct: int = 0, limit_read_bps: int = 0,
                      limit_write_bps: int = 0, rl_state=None,
                      inline_readback: bool = False,
                      flock_mode: int = 0) -> None:
        """Dir-mode LOSF hot path: open->blocks->close (or stat/unlink)
        per file, entirely in C++. Counters/histograms update after the
        call; partial (interrupted) chunks attribute only completed
        files. ranges: optional (starts, lens) uint64 arrays for
        custom-tree per-file byte slices (default: [0, file_size)).
        verify/rwmix/variance run inside the loop (FileLoopMod); a
        verify mismatch raises NativeVerifyError with the global block
        index."""
        import numpy as np
        n = len(paths)
        encoded = [os.fsencode(p) for p in paths]
        blob = b"\0".join(encoded) + b"\0"
        offs = (ctypes.c_uint32 * n)()
        pos = 0
        for i, e in enumerate(encoded):
            offs[i] = pos
            pos += len(e) + 1
        io_op = op in ("write", "read") and block_size
        if ranges is not None:
            starts_arr = _as_u64_ptr(ranges[0], n)
            lens_arr = _as_u64_ptr(ranges[1], n)
            per_file_blocks = (
                (np.asarray(ranges[1], dtype=np.uint64)
                 + np.uint64(block_size - 1)) // np.uint64(block_size)
            ).astype(np.int64) if io_op else None
            total_blocks = int(per_file_blocks.sum()) if io_op else 0
        else:
            starts_arr = lens_arr = per_file_blocks = None
            bpf = (file_size + block_size - 1) // block_size \
                if io_op and file_size else 0
            total_blocks = n * bpf
        entry_lat = (ctypes.c_uint64 * n)()
        block_lat = (ctypes.c_uint64 * max(total_blocks, 1))()
        bytes_done = ctypes.c_uint64(0)
        entries_done = ctypes.c_uint64(0)
        fail_idx = ctypes.c_uint64(0)
        verify_info = (ctypes.c_uint64 * 4)()
        rwmix_out = (ctypes.c_uint64 * 2)()
        rwmix_base = worker.rank + worker._num_iops_submitted
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))
        ret = self._lib.ioengine_run_file_loop3(
            blob, offs, n, self.FILE_OPS[op], open_flags, file_size,
            block_size, ctypes.c_void_p(buf_addr), starts_arr, lens_arr,
            1 if ignore_delete_errors else 0, entry_lat, block_lat,
            ctypes.byref(bytes_done), ctypes.byref(entries_done),
            ctypes.byref(fail_idx), ctypes.byref(interrupt),
            verify_salt, 1 if verify_salt else 0, block_var_pct,
            block_var_seed, rwmix_pct, rwmix_base, verify_info, rwmix_out,
            limit_read_bps, limit_write_bps, rl_state,
            1 if inline_readback else 0, flock_mode)
        if ret == -_EILSEQ:
            raise NativeVerifyError(int(verify_info[0]),
                                    int(verify_info[1]),
                                    int(verify_info[2]),
                                    int(verify_info[3]))
        if ret < 0:
            failed = paths[min(fail_idx.value, n - 1)]
            raise OSError(-ret, f"{os.strerror(-ret)} "
                                f"({op}: {failed})", failed)
        done = entries_done.value
        if done:
            worker.entries_latency_histo.add_latencies_array(
                np.frombuffer(entry_lat, dtype=np.uint64)[:done])
        if per_file_blocks is not None:
            num_blocks = int(per_file_blocks[:done].sum())
        else:
            num_blocks = done * (total_blocks // n if n else 0)
        rwmix_blocks, rwmix_bytes = rwmix_out[0], rwmix_out[1]
        if num_blocks:
            lat = np.frombuffer(block_lat, dtype=np.uint64)[:num_blocks]
            if rwmix_pct and op == "write" and rwmix_blocks:
                # same in-loop modulo as the engine: flags are exact
                rd = (((np.uint64(rwmix_base)
                        + np.arange(num_blocks, dtype=np.uint64))
                       % np.uint64(100)) < np.uint64(rwmix_pct))
                worker.iops_latency_histo_rwmix.add_latencies_array(lat[rd])
                worker.iops_latency_histo.add_latencies_array(lat[~rd])
            else:
                worker.iops_latency_histo.add_latencies_array(lat)
        worker.live_ops.num_entries_done += done
        worker.live_ops.num_iops_done += num_blocks - rwmix_blocks
        worker.live_ops.num_bytes_done += bytes_done.value - rwmix_bytes
        worker.live_ops_rwmix_read.num_iops_done += rwmix_blocks
        worker.live_ops_rwmix_read.num_bytes_done += rwmix_bytes
        worker._num_iops_submitted += num_blocks
        worker.create_stonewall_stats_if_triggered()

    def run_net_client_loop(self, fd: int, payload: bytes, resp_size: int,
                            n_ops: int, worker,
                            interrupt_flag=None) -> None:
        """n_ops netbench round trips (send payload, await resp_size)."""
        import numpy as np
        lat_arr = (ctypes.c_uint64 * n_ops)()
        bytes_done = ctypes.c_uint64(0)
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))
        ret = self._lib.ioengine_net_client_loop(
            fd, payload, len(payload), resp_size, n_ops, lat_arr,
            ctypes.byref(bytes_done), ctypes.byref(interrupt))
        if ret < 0:
            raise OSError(-ret, os.strerror(-ret))
        per_op = len(payload) + resp_size
        done_ops = bytes_done.value // per_op if per_op else 0
        worker.iops_latency_histo.add_latencies_array(
            np.frombuffer(lat_arr, dtype=np.uint64)[:done_ops])
        worker.live_ops.num_iops_done += done_ops
        worker.live_ops.num_bytes_done += bytes_done.value
        worker.create_stonewall_stats_if_triggered()

    def run_net_server_slice(self, fds, conn_state, block_size: int,
                             resp_payload: bytes, worker,
                             max_responses: int = 4096,
                             slice_msecs: int = 500,
                             interrupt_flag=None) -> int:
        """One polling slice of the netbench server loop; returns the
        number of still-open connections (conn_state mutated in place)."""
        import numpy as np
        n = len(fds)
        fds_arr = (ctypes.c_int * n)(*fds)
        lat_arr = (ctypes.c_uint64 * max_responses)()
        bytes_done = ctypes.c_uint64(0)
        responses = ctypes.c_uint64(0)
        open_conns = ctypes.c_uint64(0)
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))
        ret = self._lib.ioengine_net_server_loop(
            fds_arr, n, conn_state, block_size, len(resp_payload),
            resp_payload, max_responses, slice_msecs, lat_arr,
            ctypes.byref(bytes_done), ctypes.byref(responses),
            ctypes.byref(open_conns), ctypes.byref(interrupt))
        if ret < 0:
            raise OSError(-ret, os.strerror(-ret))
        worker.iops_latency_histo.add_latencies_array(
            np.frombuffer(lat_arr, dtype=np.uint64)[:responses.value])
        worker.live_ops.num_iops_done += responses.value
        worker.live_ops.num_bytes_done += bytes_done.value
        worker.create_stonewall_stats_if_triggered()
        return open_conns.value

    def run_mmap_loop(self, map_addr: int, offsets, lengths,
                      is_write: bool, buf_addr: int, worker,
                      interrupt_flag=None, op_is_read=None,
                      verify_salt: int = 0, block_var_pct: int = 0,
                      block_var_seed: int = 0, limit_read_bps: int = 0,
                      limit_write_bps: int = 0, rl_state=None) -> None:
        """--mmap hot loop: memcpy between the mapping and the io buffer
        entirely in C++ (same accounting and block modifiers as
        run_block_loop)."""
        import numpy as np
        n = len(offsets)
        lat_arr = (ctypes.c_uint64 * n)()
        bytes_done = ctypes.c_uint64(0)
        verify_info = (ctypes.c_uint64 * 4)()
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))
        flags_arr = None
        if op_is_read is not None:
            flags_arr = _as_ptr(op_is_read, n, "uint8", ctypes.c_ubyte)
        ret = self._lib.ioengine_run_mmap_loop3(
            ctypes.c_void_p(map_addr), _as_u64_ptr(offsets, n),
            _as_u64_ptr(lengths, n), n, 1 if is_write else 0,
            ctypes.c_void_p(buf_addr), lat_arr, ctypes.byref(bytes_done),
            ctypes.byref(interrupt), flags_arr, verify_salt,
            1 if verify_salt else 0, block_var_pct, block_var_seed,
            verify_info, limit_read_bps, limit_write_bps, rl_state)
        if ret == -_EILSEQ:
            raise NativeVerifyError(int(verify_info[0]),
                                    int(verify_info[1]),
                                    int(verify_info[2]),
                                    int(verify_info[3]))
        if ret < 0:
            raise OSError(-ret, os.strerror(-ret))
        lengths_np = (lengths if isinstance(lengths, np.ndarray)
                      else np.asarray(lengths, dtype=np.uint64))
        _account_chunk(worker, lat_arr, lengths_np, n, bytes_done.value,
                       int(lengths_np.sum()), op_is_read)

    def run_block_loop(self, fd: int, offsets, lengths, is_write: bool,
                       buf_addr: int, iodepth: int, worker,
                       interrupt_flag=None, engine: str = "auto",
                       fds: "list[int] | None" = None,
                       fd_idx: "list[int] | None" = None,
                       op_is_read=None, verify_salt: int = 0,
                       block_var_pct: int = 0,
                       block_var_seed: int = 0,
                       limit_read_bps: int = 0,
                       limit_write_bps: int = 0,
                       rl_state=None, inline_readback: bool = False,
                       flock_mode: int = 0, ops_fd: int = -1,
                       ops_lock: bool = False,
                       worker_rank: int = 0,
                       pool: "NativePool | None" = None,
                       pool_stats=None) -> bool:
        """fds/fd_idx: striped multi-file mode — fd_idx[i] selects the
        file of block i (reference: calcFileIdxAndOffsetStriped). offsets/
        lengths/fd_idx may be numpy uint64/uint32 arrays, passed zero-copy
        (the vectorized offset-generator path).

        In-loop block modifiers (reference LocalWorker.cpp:1741,2124,2242):
        op_is_read — uint8 array, rwmix per-op read flags for a write
        phase (accounting is split into the worker's rwmix-read counters);
        verify_salt — --verify fill-on-write/check-on-read, raising
        NativeVerifyError with the exact mismatch location;
        block_var_pct/seed — --blockvarpct refill of each write block.

        pool: a NativePool — the uring engine then runs this chunk over
        the pool's persistent ring with its once-registered fixed
        buffers (ioengine_run_block_loop5); the caller's staging buffers
        MUST be the pool's slots. pool_stats: the StagingPool whose
        registration/SQPOLL audit counters the chunk's engine stats are
        booked into."""
        import numpy as np
        n = len(offsets)
        off_arr = _as_u64_ptr(offsets, n)
        len_arr = _as_u64_ptr(lengths, n)
        lat_arr = (ctypes.c_uint64 * n)()
        bytes_done = ctypes.c_uint64(0)
        verify_info = (ctypes.c_uint64 * 4)()
        interrupt = (interrupt_flag if interrupt_flag is not None
                     else ctypes.c_int(0))  # c_int(0) is falsy: no `or`!
        buf_size = int(lengths.max() if isinstance(lengths, np.ndarray)
                       else max(lengths))
        if fds is None:
            fds_arr = (ctypes.c_int * 1)(fd)
            idx_arr = None
        else:
            fds_arr = (ctypes.c_int * len(fds))(*fds)
            idx_arr = _as_ptr(fd_idx, n, "uint32", ctypes.c_uint32)
        flags_arr = None
        if op_is_read is not None:
            flags_arr = _as_ptr(op_is_read, n, "uint8", ctypes.c_ubyte)
        loop4_args = (
            fds_arr, idx_arr, off_arr, len_arr, n, 1 if is_write else 0,
            ctypes.c_void_p(buf_addr), buf_size, iodepth,
            lat_arr, ctypes.byref(bytes_done), ctypes.byref(interrupt),
            ENGINE_CODES[engine], flags_arr, verify_salt,
            1 if verify_salt else 0, block_var_pct, block_var_seed,
            verify_info, limit_read_bps, limit_write_bps, rl_state,
            1 if inline_readback else 0, flock_mode, ops_fd,
            1 if ops_lock else 0, worker_rank)
        if pool is not None and pool.handle is not None:
            engine_stats = (ctypes.c_uint64 * 3)()
            ret = self._lib.ioengine_run_block_loop5(
                pool.handle, *loop4_args, engine_stats)
            if pool_stats is not None:
                pool_stats.book_engine_stats(int(engine_stats[0]),
                                             int(engine_stats[1]),
                                             bool(engine_stats[2]))
        else:
            ret = self._lib.ioengine_run_block_loop4(*loop4_args)
        if ret == -_EILSEQ:
            raise NativeVerifyError(int(verify_info[0]),
                                    int(verify_info[1]),
                                    int(verify_info[2]),
                                    int(verify_info[3]))
        if ret < 0:
            raise OSError(-ret, os.strerror(-ret))
        lengths_np = (lengths if isinstance(lengths, np.ndarray)
                      else np.asarray(lengths, dtype=np.uint64))
        _account_chunk(worker, lat_arr, lengths_np, n, bytes_done.value,
                       int(lengths_np.sum()), op_is_read)
        return True


def get_native_engine(try_build: bool = True) -> "_NativeEngine | None":
    """Lazily load the native engine; None if not built or disabled via
    ELBENCHO_TPU_NO_NATIVE=1. try_build=False only loads an existing .so
    (diagnostics paths like --version must not kick off a compile)."""
    global _engine, _engine_checked
    if _engine_checked:
        return _engine
    with _lock:
        if _engine_checked:
            return _engine
        if os.environ.get("ELBENCHO_TPU_NO_NATIVE") != "1":
            # always invoke make in the checkout layout: it is an mtime
            # no-op when the .so is fresh, and it prevents silently
            # benchmarking a stale binary after an ioengine.cpp edit
            if try_build and os.path.exists(
                    os.path.join(os.path.dirname(_SO_PATH), "ioengine.cpp")):
                _try_build()
            for so in (_SO_PATH, _SO_PATH_INSTALLED):
                if os.path.exists(so):
                    try:
                        candidate = _NativeEngine(ctypes.CDLL(so))
                        if candidate.abi_version() != EXPECTED_ABI:
                            # visible refusal: otherwise the silent
                            # pure-Python fallback looks like a storage
                            # slowdown to the user
                            from ..toolkits.logger import log_error
                            log_error(
                                f"ignoring stale native ioengine {so} "
                                f"(ABI {candidate.abi_version()}, expected "
                                f"{EXPECTED_ABI}); falling back to the "
                                f"pure-Python I/O loop unless another "
                                f"build is found")
                            continue
                        _engine = candidate
                        break
                    except (OSError, AttributeError):
                        _engine = None
        # a build-skipping probe must not cache "unavailable" — a later
        # real run still gets its chance to compile the engine
        if _engine is not None or try_build:
            _engine_checked = True
        return _engine


def _try_build() -> None:
    """One-shot best-effort build of the engine (g++ is in the image)."""
    import subprocess
    csrc = os.path.dirname(_SO_PATH)
    try:
        subprocess.run(["make", "-C", csrc], capture_output=True,
                       timeout=120, check=False)
    except (OSError, subprocess.TimeoutExpired):
        pass


def reset_native_engine_cache() -> None:
    global _engine, _engine_checked
    with _lock:
        _engine = None
        _engine_checked = False
