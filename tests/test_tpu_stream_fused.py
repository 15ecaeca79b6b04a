"""Fused native-stream TPU loop (--tpustream): parity with the Python
fallback, plus interrupt-mid-stream and short-read-mid-stream behavior —
all through the real worker path (CLI -> LocalWorker -> engine ring ->
TpuWorkerContext), on the virtual CPU mesh the conftest provides."""

import json

import numpy as np
import pytest

from elbencho_tpu.utils import native as native_mod


def _native_stream_or_skip(monkeypatch):
    monkeypatch.delenv("ELBENCHO_TPU_NO_NATIVE", raising=False)
    native_mod.reset_native_engine_cache()
    native = native_mod.get_native_engine()
    if native is None or not native.stream_supported():
        pytest.skip("native stream engine unavailable "
                    "(no io_uring and no kernel AIO)")
    return native


def _run(args, jf):
    from elbencho_tpu.cli import main
    open(jf, "w").close()
    rc = main([str(a) for a in args] + ["--jsonfile", str(jf)])
    recs = [json.loads(ln) for ln in open(jf) if ln.strip()]
    return rc, recs


def _phase_rec(recs, phase):
    return next(r for r in recs if r["Phase"] == phase)


#: raw per-phase op counters that must be identical between the fused
#: loop and the Python fallback (rates are wall-clock-dependent; these
#: are exact counts)
_PARITY_KEYS = ("TpuH2dStagedOps", "TpuH2dDirectOps", "TpuD2hStagedOps",
                "TpuD2hDirectOps", "TpuHbmBytes")


def test_fused_vs_python_parity_verify_rwmix(tmp_path, monkeypatch):
    """Byte-identical file content and identical op counts between the
    fused stream loop and the Python fallback, with verify + rwmix
    active (same seed: same rank, same rwmix modulo base, verify
    pattern is offset-determined). Block variance rides the separate
    parity test below — the config rejects --verify + --blockvarpct
    repo-wide (verify content wins)."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    # pre-create the verify pattern so the rwmix reads inside the write
    # phase see written data on both paths
    rc, _ = _run(["-w", "-t", "1", "-s", "512K", "-b", "4K",
                  "--verify", "11", "--nolive", target], jf)
    assert rc == 0
    common = ["-w", "-t", "1", "-s", "512K", "-b", "4K", "--verify", "11",
              "--rwmixpct", "30", "--iodepth", "4",
              "--tpuids", "0", "--nolive", target]
    rc, recs = _run(common + ["--tpustream", "off"], jf)
    assert rc == 0
    rec_py = _phase_rec(recs, "WRITE")
    bytes_py = target.read_bytes()
    assert rec_py["TpuStreamFusedOps"] == 0  # python loop ran

    rc, recs = _run(common, jf)  # --tpustream auto -> fused
    assert rc == 0
    rec_fused = _phase_rec(recs, "WRITE")
    bytes_fused = target.read_bytes()
    assert rec_fused["TpuStreamFusedOps"] == 128  # every op went fused
    assert bytes_fused == bytes_py  # byte-identical results
    for key in _PARITY_KEYS:  # identical op counts, path by path
        assert rec_fused[key] == rec_py[key], key
    # the written pattern is the documented verify formula on both
    words = np.frombuffer(bytes_fused, dtype=np.uint64)
    want = np.arange(len(words), dtype=np.uint64) * 8 + np.uint64(11)
    assert (words == want).all()


def test_fused_vs_python_parity_blockvar_rwmix(tmp_path, monkeypatch):
    """Block-variance + rwmix parity: with TPU staging the write source
    is the deterministic on-device fill pool on BOTH paths (seeded by
    chip id), so the written bytes must come out identical too."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "512K", "-b", "4K", "--nolive",
                  target], jf)
    assert rc == 0
    common = ["-w", "-t", "1", "-s", "512K", "-b", "4K",
              "--rwmixpct", "30", "--blockvarpct", "50", "--iodepth", "4",
              "--tpuids", "0", "--nolive", target]
    rc, recs = _run(common + ["--tpustream", "off"], jf)
    assert rc == 0
    rec_py = _phase_rec(recs, "WRITE")
    bytes_py = target.read_bytes()
    rc, recs = _run(common, jf)
    assert rc == 0
    rec_fused = _phase_rec(recs, "WRITE")
    assert rec_fused["TpuStreamFusedOps"] == 128
    assert target.read_bytes() == bytes_py
    for key in _PARITY_KEYS:
        assert rec_fused[key] == rec_py[key], key


def test_fused_read_parity_and_overlap_evidence(tmp_path, monkeypatch):
    """Read-phase parity (host verify active) plus the overlap proof the
    acceptance criteria name: pipe_inflight_hwm > 1 on the fused path."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "1M", "-b", "64K", "--verify",
                  "3", "--nolive", target], jf)
    assert rc == 0
    common = ["-r", "-t", "1", "-s", "1M", "-b", "64K", "--verify", "3",
              "--iodepth", "4", "--tpuids", "0", "--nolive", target]
    rc, recs = _run(common + ["--tpustream", "off"], jf)
    assert rc == 0
    rec_py = _phase_rec(recs, "READ")
    rc, recs = _run(common, jf)
    assert rc == 0
    rec_fused = _phase_rec(recs, "READ")
    assert rec_fused["TpuStreamFusedOps"] == 16
    for key in _PARITY_KEYS:
        assert rec_fused[key] == rec_py[key], key
    # transfers overlapped: the ring actually pipelined
    assert rec_fused["TpuPipeInflightHwm"] > 1
    # the engine ran the storage I/O: dispatch cost no longer contains
    # the storage-read wall time (it is bounded by the H2D submit cost)
    assert rec_fused["TpuDispatchUSec"] >= 0


def test_fused_loop_respects_tpustream_on_blockers(tmp_path, monkeypatch):
    """--tpustream on fails LOUDLY when a per-op Python feature blocks
    the fused loop instead of silently degrading."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "64K", "-b", "16K", "--nolive",
                  target], jf)
    assert rc == 0
    rc, _ = _run(["-r", "-t", "1", "-s", "64K", "-b", "16K",
                  "--tpuids", "0", "--tpustream", "on", "--flock",
                  "range", "--nolive", target], jf)
    assert rc != 0


def test_short_read_mid_stream_fails_loudly(tmp_path, monkeypatch,
                                            capsys):
    """A short read surfacing from the engine ring mid-stream must fail
    the phase with the offset context, exactly like the Python loop's
    short-read error (simulated at the reap seam — the kernel itself
    returns full reads on a healthy file)."""
    _native_stream_or_skip(monkeypatch)
    from elbencho_tpu.utils.native import NativeStream
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "256K", "-b", "16K", "--nolive",
                  target], jf)
    assert rc == 0
    orig = NativeStream.reap
    state = {"fired": False}

    def shortening_reap(self, *a, **kw):
        events = orig(self, *a, **kw)
        if events and not state["fired"]:
            state["fired"] = True
            slot, lat, res = events[0]
            events[0] = (slot, lat, res - 512)  # short by half a KiB
        return events

    monkeypatch.setattr(NativeStream, "reap", shortening_reap)
    rc, _ = _run(["-r", "-t", "1", "-s", "256K", "-b", "16K", "--iodepth",
                  "4", "--tpuids", "0", "--nolive", target], jf)
    assert state["fired"], "fused reap path never ran"
    assert rc != 0
    err = capsys.readouterr().err
    assert "short read" in err, err[-500:]


def test_interrupt_mid_stream_drains_and_books_partial(tmp_path,
                                                       monkeypatch):
    """--timelimit expiry mid-stream: the ring drains cleanly (no hang,
    no use-after-free on the slot buffers) and the partial progress is
    booked — the run completes as a normal timed-out phase."""
    _native_stream_or_skip(monkeypatch)
    import time as time_mod
    from elbencho_tpu.tpu.device import TpuWorkerContext
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "16M", "-b", "64K", "--nolive",
                  target], jf)
    assert rc == 0
    # slow the transfer leg so the 1s limit deterministically fires
    # while the stream ring is loaded (256 ops x 10ms >> 1s), however
    # fast the host is — everything else is the real worker path
    orig = TpuWorkerContext.host_to_device

    def slow_h2d(self, *a, **kw):
        time_mod.sleep(0.01)
        return orig(self, *a, **kw)

    monkeypatch.setattr(TpuWorkerContext, "host_to_device", slow_h2d)
    rc, recs = _run(["-r", "-t", "1", "-s", "16M", "-b", "64K",
                     "--iodepth", "4", "--tpuids", "0", "--timelimit",
                     "1", "--nolive", target], jf)
    assert rc == 0
    rec = _phase_rec(recs, "READ")
    assert rec["TpuStreamFusedOps"] > 0  # the fused loop was mid-stream
    # partial, not full: the interrupt landed before the file was done
    assert rec["TpuHbmBytes"] < 16 * 1024 * 1024
    assert rec["TpuHbmBytes"] > 0


def test_fused_direct_mode_parity_and_holdback(tmp_path, monkeypatch):
    """--tpudirect fused: every op goes zero-bounce AND fused, with the
    holdback discipline releasing slots via the transfer-ring drain
    (content still byte-exact under --verify, so no slot was rewritten
    while its import was live)."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "1M", "-b", "64K", "--verify",
                  "5", "--nolive", target], jf)
    assert rc == 0
    rc, recs = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--verify",
                     "5", "--iodepth", "4", "--tpuids", "0",
                     "--tpudirect", "--nolive", target], jf)
    assert rc == 0  # host verify passed on every reaped block
    rec = _phase_rec(recs, "READ")
    assert rec["TpuStreamFusedOps"] == 16
    assert rec["TpuH2dDirectOps"] == 16
    assert rec["TpuH2dDirectFallbacks"] == 0


class _DeferredPut:
    """What ``jax.device_put`` of a host buffer may give on a TPU: the
    transfer reads the buffer only when it is waited on (PjRt reads it
    until the transfer completes). It has no device buffer to donate, so
    the staged path's slot copy latches off and each ring entry is the
    put itself."""

    def __init__(self, view, device, put):
        self._view, self._device, self._put = view, device, put
        self.shape, self.dtype = view.shape, view.dtype
        self.value = None

    def is_ready(self):
        return self.value is not None

    def block_until_ready(self):
        if self.value is None:
            self.value = self._put(np.array(self._view), self._device)
            self._view = None  # the transfer is done with the buffer
        return self


def _deferred_device_put(monkeypatch):
    import jax
    put = jax.device_put

    def device_put(x, device=None, *args, **kwargs):
        if isinstance(x, np.ndarray):
            return _DeferredPut(x, device, put)
        return put(x, device, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", device_put)


def _record_landed(monkeypatch):
    """(file offset, length, the array the context holds as landed) of
    every host_to_device call."""
    from elbencho_tpu.tpu.device import TpuWorkerContext
    landed = []
    orig = TpuWorkerContext.host_to_device

    def host_to_device(self, buf, length, verify_salt=0, file_offset=0):
        orig(self, buf, length, verify_salt=verify_salt,
             file_offset=file_offset)
        landed.append((file_offset, length, self._last_ingested))

    monkeypatch.setattr(TpuWorkerContext, "host_to_device", host_to_device)
    return landed


def _staged_read_lands_the_bytes_it_read(tmp_path, monkeypatch, flags,
                                         fused_ops):
    """Write 64 KiB blocks of the verify pattern, read them back at
    --iodepth 8 on the staged path with a deferred-read device_put, and
    compare every landed array with the pattern at its offset."""
    _native_stream_or_skip(monkeypatch)
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    salt = 7
    rc, _ = _run(["-w", "-t", "1", "-s", "4M", "-b", "64K", "--verify",
                  salt, "--nolive", target], jf)
    assert rc == 0
    _deferred_device_put(monkeypatch)
    landed = _record_landed(monkeypatch)
    rc, recs = _run(["-r", "-t", "1", "-s", "4M", "-b", "64K", "--iodepth",
                     "8", "--tpuids", "0", "--nolive", target] + flags, jf)
    assert rc == 0
    rec = _phase_rec(recs, "READ")
    assert rec["TpuStreamFusedOps"] == fused_ops
    assert rec["TpuH2dStagedOps"] == 64 and rec["TpuH2dDirectOps"] == 0
    assert len(landed) == 64
    wrong = []
    for offset, length, arr in landed:
        assert isinstance(arr, _DeferredPut) and arr.value is not None, \
            f"block at {offset} was never waited on"
        words = np.asarray(arr.value).view(np.uint64)
        want = (np.arange(length // 8, dtype=np.uint64) * np.uint64(8)
                + np.uint64(offset + salt))
        if not (words == want).all():
            wrong.append(offset)
    assert not wrong, f"{len(wrong)} of 64 blocks landed other bytes"


def test_fused_staged_depth8_lands_the_bytes_it_read(tmp_path, monkeypatch):
    """The README's deployment at CPU size: 64 KiB blocks, --iodepth 8,
    the default (staged) H2D path on the fused ring, with a device_put
    that reads the host buffer only when its transfer is waited on. No
    slot may go back to the engine before the transfer that reads it is
    done, so every landed block is the verify pattern at its offset."""
    _staged_read_lands_the_bytes_it_read(tmp_path, monkeypatch, [], 64)


def test_python_loop_staged_tpudepth_above_iodepth_lands_the_bytes_it_read(
        tmp_path, monkeypatch):
    """The Python block loop rotates --iodepth host buffers. With
    --tpudepth 16 above --iodepth 8, a transfer ring deeper than that
    rotation would let a staged device_put still read a buffer that
    storage already rewrote; the depth is clamped to --iodepth for every
    unbatched transfer, staged as well as direct."""
    _staged_read_lands_the_bytes_it_read(
        tmp_path, monkeypatch, ["--tpudepth", "16", "--tpustream", "off"], 0)


@pytest.mark.parametrize("direct,depth,batch,want", [
    (False, 8, 1, 7), (True, 8, 1, 7), (False, 1, 1, 0), (True, 1, 1, 0),
    (False, 8, 2, 0)], ids=["staged8", "direct8", "staged1", "direct1",
                            "batch8"])
def test_holdback_is_depth_less_one_for_every_unbatched_path(
        direct, depth, batch, want):
    """The fused ring's slot-release rule does not depend on the path: a
    slot is held until the transfer that reads it drained."""
    from elbencho_tpu.tpu.device import TpuWorkerContext
    ctx = TpuWorkerContext(chip_id=0, block_size=64 << 10, direct=direct,
                           pipeline_depth=depth, batch_blocks=batch)
    try:
        assert ctx.pipeline_depth == depth
        assert ctx.holdback_depth() == want
    finally:
        ctx.close()


@pytest.mark.parametrize("slow_storage", [False, True],
                         ids=["fast", "slow_storage"])
def test_fused_staged_books_its_slot_releases(tmp_path, monkeypatch,
                                              slow_storage):
    """Every held slot that goes back to the engine books one
    SlotReleaseWaitOps: the phase's ops less the depth-1 still held at
    its end. With storage slower than the transfer (a non-blocking reap
    finds nothing) slots go back through release drains, each of which
    books its wait in SlotReleaseWaitUSec and one ring span
    ``slot_release``."""
    _native_stream_or_skip(monkeypatch)
    from elbencho_tpu.utils.native import NativeStream
    target = tmp_path / "f"
    jf = tmp_path / "res.json"
    trace = tmp_path / "t.json"
    rc, _ = _run(["-w", "-t", "1", "-s", "1M", "-b", "64K", "--nolive",
                  target], jf)
    assert rc == 0
    orig = NativeStream.reap

    def slow_storage_reap(self, min_complete=1, *args, **kwargs):
        return [] if min_complete == 0 else \
            orig(self, min_complete, *args, **kwargs)

    if slow_storage:
        monkeypatch.setattr(NativeStream, "reap", slow_storage_reap)
    rc, recs = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                     "8", "--tpuids", "0", "--tracefile", trace, "--nolive",
                     target], jf)
    assert rc == 0
    rec = _phase_rec(recs, "READ")
    assert rec["TpuStreamFusedOps"] == rec["TpuH2dStagedOps"] == 16
    assert rec["SlotReleaseWaitOps"] == 16 - 7
    with open(trace) as f:
        spans = [e for e in json.load(f)["traceEvents"]
                 if e["name"] == "slot_release"]
    assert len(spans) <= rec["SlotReleaseWaitOps"]
    assert rec["SlotReleaseWaitUSec"] == sum(e["dur"] for e in spans)
    if slow_storage:
        assert spans


def test_fused_skips_tiny_dir_mode_files(tmp_path, monkeypatch):
    """Dir-mode LOSF with files only a couple ring-fills long falls back
    to the Python loop (per-file ring setup would dominate), logged as
    ineligible rather than engaging a throwaway stream per file."""
    _native_stream_or_skip(monkeypatch)
    jf = tmp_path / "res.json"
    rc, recs = _run(["-w", "-d", "-r", "-t", "1", "-n", "1", "-N", "2",
                     "-s", "32K", "-b", "16K", "--iodepth", "4",
                     "--tpuids", "0", "--nolive", str(tmp_path)], jf)
    assert rc == 0
    rec = _phase_rec(recs, "READ")
    assert rec["TpuStreamFusedOps"] == 0  # python loop served the files
    assert rec["TpuHbmBytes"] == 2 * 32 * 1024  # staging still happened
