"""The per-chip group commit of depth-1 staged transfers (H2dGroupCommit):
threads that land blocks on one chip share list puts, each still gets its
own bytes and its own accounting, an error of a put reaches every block
of it, and the paths it must not take never enter it. All on the virtual
CPU mesh (conftest forces JAX_PLATFORMS=cpu)."""

import json
import mmap
import sys
import threading
import time

import numpy as np
import pytest

from elbencho_tpu.cli import main
from elbencho_tpu.tpu import device as tpu_device
from elbencho_tpu.tpu.device import TpuWorkerContext, h2d_group_commit

BS = 4096


def _buffer(value: int):
    """A page-aligned one-block host buffer holding ``value`` in every
    word, and its uint32 view."""
    mv = memoryview(mmap.mmap(-1, BS))
    words = np.frombuffer(mv, dtype=np.uint32)
    words[:] = value
    return mv, words


def _contexts(n: int, **kw) -> list:
    return [TpuWorkerContext(chip_id=0, block_size=BS, **kw)
            for _ in range(n)]


def _run_threads(n: int, body) -> list:
    """body(i) in n threads, started together; the errors they raised."""
    start = threading.Barrier(n)
    errors = []

    def run(i):
        start.wait()
        try:
            body(i)
        except Exception as err:  # noqa: BLE001 - collected for the test
            errors.append(err)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads), "a thread hung"
    return errors


class _HeldFirstPut:
    """The jax module, but its first ``device_put`` waits until
    ``queued`` more blocks wait on the chip, and every later one raises
    ``fail`` when given: the next put then carries all of them."""

    def __init__(self, group, queued: int, fail=None):
        self._jax = tpu_device._get_jax()
        self._group = group
        self._queued = queued
        self._fail = fail
        self.puts = []

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def device_put(self, x, device=None, **kw):
        self.puts.append(len(x))
        if len(self.puts) == 1:
            deadline = time.monotonic() + 30
            while len(self._group._queue) < self._queued \
                    and time.monotonic() < deadline:
                time.sleep(0.001)
        elif self._fail is not None:
            raise self._fail
        return self._jax.device_put(x, device, **kw)


def test_threads_each_land_their_own_block():
    """N threads at depth 1 on one chip, each landing its own values:
    every context's landed block is its own, after every call."""
    n, rounds = 8, 40
    ctxs = _contexts(n)
    wrong = []

    def body(i):
        mv, words = _buffer(0)
        for k in range(rounds):
            words[:] = i * 1000 + k
            ctxs[i].host_to_device(mv, BS)
            got = np.asarray(ctxs[i]._last_ingested)
            if not (got == i * 1000 + k).all():
                wrong.append((i, k))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # switch threads often: races show
    try:
        assert _run_threads(n, body) == []
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    for ctx in ctxs:
        assert ctx.h2d_coalesced_blocks == ctx.h2d_staged_ops == rounds
        assert ctx.pipe_ready_wait_ops == rounds
        assert ctx._staged_submits == 0  # no slot copy on this path
        ctx.close()


def test_one_put_carries_every_block_queued_behind_it(monkeypatch):
    """While the first put is in flight the other threads queue; the
    next put carries all of them, and its thread books the put."""
    n = 6
    ctxs = _contexts(n)
    held = _HeldFirstPut(h2d_group_commit(ctxs[0].device), n - 1)
    monkeypatch.setattr(tpu_device, "_get_jax", lambda: held)

    def body(i):
        mv, _ = _buffer(i + 1)
        ctxs[i].host_to_device(mv, BS)
        assert (np.asarray(ctxs[i]._last_ingested) == i + 1).all()

    assert _run_threads(n, body) == []
    assert held.puts == [1, n - 1]
    blocks = sum(c.h2d_coalesced_blocks for c in ctxs)
    puts = sum(c.h2d_coalesced_puts for c in ctxs)
    assert (blocks, puts) == (n, 2)
    assert puts < blocks
    for ctx in ctxs:
        ctx.close()


def test_blocks_book_their_own_ops_and_bytes(tmp_path):
    """A read of 4 threads at depth 1 on one chip: every block is one
    staged op and its bytes in the record, whatever puts carried it."""
    path = tmp_path / "f"
    jsonfile = tmp_path / "out.json"
    rc = main(["-w", "-r", "-t", "4", "-s", "1M", "-b", "4K", "--tpuids", "0",
               "--nolive", "--jsonfile", str(jsonfile), str(path)])
    assert rc == 0
    recs = [json.loads(ln) for ln in jsonfile.read_text().splitlines()]
    read = [r for r in recs if r["Phase"] == "READ"][0]
    blocks = (1 << 20) // BS
    assert read["TpuHbmBytes"] == 1 << 20
    assert read["TpuH2dStagedOps"] == blocks
    assert read["TpuH2dCoalescedBlocks"] == blocks
    assert 1 <= read["TpuH2dCoalescedPuts"] <= blocks
    assert read["TpuReadyWaitOps"] == blocks


def test_a_failed_put_raises_in_every_block_of_it(monkeypatch):
    """The put that carries the queued blocks raises: each of their
    threads raises that error, none hangs, and the chip's next put
    goes through."""
    n = 5
    ctxs = _contexts(n)
    group = h2d_group_commit(ctxs[0].device)
    lost = RuntimeError("device lost")
    held = _HeldFirstPut(group, n - 1, fail=lost)
    monkeypatch.setattr(tpu_device, "_get_jax", lambda: held)

    def body(i):
        mv, _ = _buffer(i)
        ctxs[i].host_to_device(mv, BS)

    errors = _run_threads(n, body)
    assert len(errors) == n - 1
    assert all(err is lost for err in errors)
    assert tpu_device.is_device_loss_error(errors[0])
    assert not group._busy and not group._queue
    monkeypatch.undo()
    mv, _ = _buffer(7)
    ctxs[0].host_to_device(mv, BS)
    assert (np.asarray(ctxs[0]._last_ingested) == 7).all()
    for ctx in ctxs:
        ctx.close()


@pytest.mark.parametrize("kw", [
    {"pipeline_depth": 2},
    {"direct": True},
    {"batch_blocks": 2},
    {"verify_on_device": True},
], ids=["depth2", "tpudirect", "tpubatch", "tpuverify"])
def test_other_transfer_paths_never_enter_it(kw):
    """Two contexts share the chip, but their transfers are not
    synchronous staged single blocks: none enters the group commit."""
    ctxs = _contexts(2, **kw)
    for _ in range(3):
        for i, ctx in enumerate(ctxs):
            mv, _ = _buffer(i)
            ctx.host_to_device(mv, BS)
    for ctx in ctxs:
        ctx.flush()
        assert ctx._group is None
        assert ctx.h2d_coalesced_blocks == ctx.h2d_coalesced_puts == 0
        ctx.close()


def test_a_lone_context_lands_through_its_chip_alone():
    """One depth-1 context alone on the chip lands through the group
    commit too, one block per put, with no slot copy; its warm-up puts
    a block and compiles nothing."""
    ctx = TpuWorkerContext(chip_id=0, block_size=BS)
    ctx.warmup_transfer()
    assert ctx._copy_step is None
    assert ctx.h2d_coalesced_blocks == ctx.h2d_staged_ops == 0
    for k in range(3):
        mv, _ = _buffer(k + 3)
        ctx.host_to_device(mv, BS)
        assert (np.asarray(ctx._last_ingested) == k + 3).all()
    assert ctx.h2d_coalesced_blocks == ctx.h2d_coalesced_puts == 3
    assert ctx.h2d_staged_ops == ctx.pipe_ready_wait_ops == 3
    assert ctx._staged_submits == ctx.staging_reuses == 0
    assert ctx.pipe_full_stalls == 0
    ctx.close()


def test_contexts_share_their_own_chips_group_commit():
    """The group commit is per chip: contexts on one chip share one,
    one on another chip has its own, and a failover to another chip
    moves the context to that chip's."""
    a = TpuWorkerContext(chip_id=0, block_size=BS)
    b = TpuWorkerContext(chip_id=0, block_size=BS)
    c = TpuWorkerContext(chip_id=1, block_size=BS)
    assert a._group is b._group is h2d_group_commit(a.device)
    assert c._group is h2d_group_commit(c.device) is not a._group
    a.failover_to_chip(1)
    assert a._group is c._group
    for ctx in (a, b, c):
        ctx.close()
