"""h2d_blocks_per_transfer.iops: blocks landed per list put of the
program's group commit, from the window's record; None where the record
has no such puts, as in a program without the group commit."""

import json
import os
import sys

import pytest

from .helpers import BENCH, REPO

sys.path.insert(0, BENCH)

import run  # noqa: E402

NAME = "h2d_blocks_per_transfer.iops"


def _read(record):
    return run.load_reader(NAME)(run.Run(block_size=4096, record=record))


def test_it_is_declared_for_the_randread_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"],
            entry["workloads"]) == ("blocks", "higher", "program_counter",
                                    "hbm_iops", ["randread-4k.ingest"])
    assert os.path.exists(os.path.join(BENCH, "metrics", f"{NAME}.py"))


@pytest.mark.parametrize("blocks,puts,want", [
    (120, 30, 4.0), (7, 7, 1.0), (16, 1, 16.0)])
def test_it_reads_blocks_over_puts(blocks, puts, want):
    record = {"Phase": "READ", "TpuH2dCoalescedBlocks": blocks,
              "TpuH2dCoalescedPuts": puts}
    assert _read(record) == pytest.approx(want)


@pytest.mark.parametrize("record", [
    None,
    {"Phase": "READ", "TpuHbmBytes": 1 << 20, "TpuH2dStagedOps": 256},
    {"Phase": "READ", "TpuH2dCoalescedBlocks": 0, "TpuH2dCoalescedPuts": 0},
], ids=["no-record", "parent-record", "never-engaged"])
def test_it_reads_none_without_puts(record):
    assert _read(record) is None
