"""The pipelined on-device verify (--tpuverify): each block's fingerprint
kernel is dispatched in the call that lands the block and checked up to
depth-1 blocks later, by a later call or by the drain that ends the
phase. A bad block still fails the phase, naming its own offset, at
every depth, at the last block of a phase and when the phase is cut; a
chip failover checks the pending blocks on the host."""

import json
import mmap

import numpy as np
import pytest

from elbencho_tpu.ops.verify import (PendingFingerprint,
                                     expected_fingerprint_host,
                                     fingerprint_block_pallas,
                                     verify_block_on_device)
from elbencho_tpu.tpu.device import TpuWorkerContext, TransferPipeline
from elbencho_tpu.utils import native as native_mod
from elbencho_tpu.workers.local_worker import LocalWorker

BS = 64 << 10
SALT = 11


def _pattern_buf(offset, length=BS, salt=SALT, corrupt=False):
    """A page-aligned buffer holding the verify pattern of one block."""
    mv = memoryview(mmap.mmap(-1, length))
    LocalWorker._fill_verify_pattern(mv, offset, length, salt)
    if corrupt:
        mv[length // 2] ^= 0xFF
    return mv


def _fold(pairs):
    s = x = 0
    for bs, bx in pairs:
        s = (s + bs) & 0xFFFFFFFF
        x ^= bx
    return s, x


def _land(ctx, n, bad=None):
    """Land blocks 0..n-1 (block ``bad`` corrupted), then flush; returns
    how many calls had returned when the ValueError came (n: from the
    flush), or None when nothing raised."""
    bufs = [_pattern_buf(i * BS, corrupt=i == bad) for i in range(n)]
    done = 0
    try:
        for i, buf in enumerate(bufs):
            ctx.host_to_device(buf, BS, verify_salt=SALT, file_offset=i * BS)
            done += 1
        ctx.flush()
    except ValueError as err:
        assert f"block at offset {bad * BS}:" in str(err), err
        return done
    return None


# ---------------------------------------------------------------------------
# the context: deferred check, its window and its counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 4])
@pytest.mark.parametrize("bad", [3, 9], ids=["middle", "last"])
def test_bad_block_raises_naming_its_offset_within_the_window(depth, bad):
    """The check of block k raises in the call that lands block
    k+depth-1 (at depth 1: the call that landed k itself), or at the
    flush when fewer blocks follow; the blocks before k are folded."""
    n = 10
    ctx = TpuWorkerContext(chip_id=0, block_size=BS, verify_on_device=True,
                           pipeline_depth=depth)
    raised_after = _land(ctx, n, bad)
    want = bad + depth - 1
    assert raised_after == (want if want < n else n)
    assert ctx.tpu_fp_blocks == bad
    assert (ctx.tpu_fp_sum, ctx.tpu_fp_xor) == _fold(
        expected_fingerprint_host(i * BS, BS, SALT) for i in range(bad))
    ctx.close()


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_pending_hwm_is_depth_less_one(depth):
    """TpuVerifyPendingHwm: depth-1 once the window filled (0 at depth
    1); every block is checked and folded exactly once."""
    n = 8
    ctx = TpuWorkerContext(chip_id=0, block_size=BS, verify_on_device=True,
                           pipeline_depth=depth)
    assert _land(ctx, n) is None
    assert ctx.verify_pending_hwm == depth - 1
    assert not ctx._verify_pending
    assert ctx.verify_ops == ctx.tpu_fp_blocks == n
    assert (ctx.tpu_fp_sum, ctx.tpu_fp_xor) == _fold(
        expected_fingerprint_host(i * BS, BS, SALT) for i in range(n))
    ctx.reset_path_counters()
    assert ctx.verify_pending_hwm == 0
    ctx.close()


def test_drain_to_resolves_pending_verifies_to_the_same_bound():
    ctx = TpuWorkerContext(chip_id=0, block_size=BS, verify_on_device=True,
                           pipeline_depth=4)
    for i in range(3):
        ctx.host_to_device(_pattern_buf(i * BS), BS, verify_salt=SALT,
                           file_offset=i * BS)
    assert len(ctx._verify_pending) == 3 and ctx.verify_ops == 0
    ctx.drain_to(1)
    assert len(ctx._verify_pending) == 1 and len(ctx._inflight) <= 1
    assert ctx.verify_ops == ctx.tpu_fp_blocks == 2
    ctx.drain_to(0)
    assert not ctx._verify_pending and ctx.tpu_fp_blocks == 3
    ctx.close()


class _Ready:
    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.ready = True
        return self


def test_ring_entry_waits_on_the_work_dispatched_on_its_block():
    """submit(then=...): the caller gets the block, the ring entry waits
    on then's output (the kernel that reads the block), so the drain that
    releases a host buffer also ends the kernel's reads of it."""
    pipe = TransferPipeline(depth=2)
    block, out, seen = _Ready(), _Ready(), []
    got = pipe.submit(lambda: block,
                      then=lambda arr: seen.append(arr) or out)
    assert got is block and seen == [block]
    pipe.flush()
    assert out.ready and not block.ready
    later = _Ready()
    pipe.submit(lambda: _Ready(), then=lambda arr: out)
    pipe.wait_newest_on(later)
    pipe.flush()
    assert later.ready


# ---------------------------------------------------------------------------
# the kernel's pending result
# ---------------------------------------------------------------------------

def test_fingerprint_block_pallas_returns_a_pending_result():
    """The per-block call dispatches the kernel and returns a pending
    fingerprint: it resolves once, to the closed form, and compares to a
    (sum, xor) pair; verify_block_on_device still raises in its call."""
    import jax.numpy as jnp
    offset = 5 * BS
    words = np.frombuffer(bytes(_pattern_buf(offset)), dtype=np.uint32)
    want = expected_fingerprint_host(offset, BS, SALT)
    fp = fingerprint_block_pallas(jnp.asarray(words))
    assert isinstance(fp, PendingFingerprint)
    assert fp == want and not fp != want
    assert fp != (want[0] ^ 1, want[1])
    assert fp.result() == want and fp.array is None
    bad = words.copy()
    bad[7] ^= 1
    with pytest.raises(ValueError, match=f"block at offset {offset}:"):
        verify_block_on_device(jnp.asarray(bad), offset, BS, SALT)


# ---------------------------------------------------------------------------
# chip failover: the pending blocks are checked on the host
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [None, 1], ids=["clean", "bad"])
def test_failover_checks_the_pending_blocks_on_the_host(bad):
    ctx = TpuWorkerContext(chip_id=0, block_size=BS, verify_on_device=True,
                           pipeline_depth=4)
    for i in range(3):
        ctx.host_to_device(_pattern_buf(i * BS, corrupt=i == bad), BS,
                           verify_salt=SALT, file_offset=i * BS)
    assert len(ctx._verify_pending) == 3
    if bad is not None:
        with pytest.raises(ValueError, match=f"block at offset {bad * BS}:"):
            ctx.failover_to_host()
    else:
        ctx.failover_to_host()
        assert ctx.tpu_fp_blocks == 3 and ctx.verify_ops == 0
        assert (ctx.tpu_fp_sum, ctx.tpu_fp_xor) == _fold(
            expected_fingerprint_host(i * BS, BS, SALT) for i in range(3))
        assert not ctx._verify_pending
    ctx.close()


class XlaRuntimeError(Exception):
    """Shape-compatible stand-in; is_device_loss_error matches by name."""


def _run(args, jf):
    from elbencho_tpu.cli import main
    open(jf, "w").close()
    rc = main([str(a) for a in args] + ["--jsonfile", str(jf), "--nolive"])
    recs = [json.loads(ln) for ln in open(jf) if ln.strip()]
    return rc, next((r for r in recs if r["Phase"] == "READ"), None)


def _write(target, size="1M", salt=SALT):
    from elbencho_tpu.cli import main
    assert main(["-w", "-t", "1", "-s", size, "-b", "64K", "--verify",
                 str(salt), "--nolive", str(target)]) == 0


def _file_fingerprint(n):
    return _fold(expected_fingerprint_host(i * BS, BS, SALT)
                 for i in range(n))


@pytest.mark.parametrize("where", ["call", "read"])
def test_chip_failover_with_pending_verifies_checks_each_block_once(
        tmp_path, monkeypatch, where):
    """A chip lost with three verifies pending (--iodepth 4), either as
    a block is landed or as a pending fingerprint is read back: the
    phase completes on the surviving chip and every block of the file is
    folded exactly once."""
    target = tmp_path / "f"
    _write(target)
    state = {"calls": 0, "fired": False}
    if where == "call":
        orig = TpuWorkerContext.host_to_device

        def failing(self, *a, **kw):
            state["calls"] += 1
            if state["calls"] == 6 and not state["fired"]:
                state["fired"] = True
                raise XlaRuntimeError("fake device failure")
            return orig(self, *a, **kw)

        monkeypatch.setattr(TpuWorkerContext, "host_to_device", failing)
    else:
        orig = PendingFingerprint.result

        def failing(self):
            if self._value is None:
                state["calls"] += 1
                if state["calls"] == 4 and not state["fired"]:
                    state["fired"] = True
                    raise XlaRuntimeError("fake device failure")
            return orig(self)

        monkeypatch.setattr(PendingFingerprint, "result", failing)
    rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                    "4", "--tpuids", "0,1", "--tpustream", "off",
                    "--tpufallback", "chip", "--verify", SALT,
                    "--tpuverify", target], tmp_path / "r.json")
    assert state["fired"]
    assert rc == 0
    assert rec["TpuChipFailovers"] == 1
    fp = rec["TpuFingerprint"]
    assert fp["Blocks"] == 16
    assert (fp["Sum"], fp["Xor"]) == _file_fingerprint(16)


# ---------------------------------------------------------------------------
# whole runs: the fused ring and the Python loop
# ---------------------------------------------------------------------------

def _stream_flag(loop, monkeypatch):
    if loop == "python":
        return ["--tpustream", "off"]
    monkeypatch.delenv("ELBENCHO_TPU_NO_NATIVE", raising=False)
    native_mod.reset_native_engine_cache()
    native = native_mod.get_native_engine()
    if native is None or not native.stream_supported():
        pytest.skip("native stream engine unavailable")
    return []


@pytest.mark.parametrize("iodepth", [1, 4])
def test_fused_direct_verify_checks_every_block(tmp_path, monkeypatch,
                                                 iodepth):
    """--tpudirect --verify --tpuverify on the host-backed CPU device,
    where the import aliases the staging slot the kernel reads: every
    block verifies (a slot rewritten under a pending kernel would fail
    its check), and the overlap shows in TpuVerifyPendingHwm."""
    flags = _stream_flag("fused", monkeypatch)
    target = tmp_path / "f"
    _write(target, "4M")
    rc, rec = _run(["-r", "-t", "1", "-s", "4M", "-b", "64K", "--iodepth",
                    iodepth, "--tpuids", "0", "--tpudirect", "--verify",
                    SALT, "--tpuverify", *flags, target],
                   tmp_path / "r.json")
    assert rc == 0
    assert rec["TpuStreamFusedOps"] == rec["TpuH2dDirectOps"] == 64
    assert rec["TpuVerifyOps"] == 64
    assert rec["TpuVerifyPendingHwm"] == iodepth - 1
    fp = rec["TpuFingerprint"]
    assert fp["Blocks"] == 64
    assert (fp["Sum"], fp["Xor"]) == _file_fingerprint(64)
    assert rec["OpResidentLatHisto"]["LatNumValues"] == 64


@pytest.mark.parametrize("loop", ["fused", "python"])
@pytest.mark.parametrize("bad", [5, 15], ids=["middle", "last"])
def test_bad_block_on_disk_fails_the_phase(tmp_path, monkeypatch, capsys,
                                           loop, bad):
    flags = _stream_flag(loop, monkeypatch)
    target = tmp_path / "f"
    _write(target)
    with open(target, "r+b") as f:
        f.seek(bad * BS + 100)
        f.write(b"\xff")
    rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                    "4", "--tpuids", "0", "--verify", SALT, "--tpuverify",
                    *flags, target], tmp_path / "r.json")
    assert rc != 0 and rec is None
    assert f"block at offset {bad * BS}:" in capsys.readouterr().err


@pytest.mark.parametrize("loop", ["fused", "python"])
def test_bad_block_pending_when_the_phase_is_cut_fails_the_phase(
        tmp_path, monkeypatch, capsys, loop):
    """The phase is cut (as by --timelimit) right after the bad block
    landed, its check still pending: the phase-end drain checks it and
    the phase fails."""
    from elbencho_tpu.workers.shared import WorkerInterruptedException
    flags = _stream_flag(loop, monkeypatch)
    target = tmp_path / "f"
    _write(target)
    bad = 5
    with open(target, "r+b") as f:
        f.seek(bad * BS + 100)
        f.write(b"\xff")
    orig = TpuWorkerContext.host_to_device

    def cut_after_bad(self, buf, length, verify_salt=0, file_offset=0):
        orig(self, buf, length, verify_salt=verify_salt,
             file_offset=file_offset)
        if file_offset == bad * BS:
            assert self._verify_pending  # its check is still to come
            raise WorkerInterruptedException("phase cut")

    monkeypatch.setattr(TpuWorkerContext, "host_to_device", cut_after_bad)
    rc, rec = _run(["-r", "-t", "1", "-s", "1M", "-b", "64K", "--iodepth",
                    "4", "--tpuids", "0", "--verify", SALT, "--tpuverify",
                    *flags, target], tmp_path / "r.json")
    assert rc != 0 and rec is None
    assert f"block at offset {bad * BS}:" in capsys.readouterr().err
