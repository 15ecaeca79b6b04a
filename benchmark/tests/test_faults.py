"""The comparison that decides ``correct``, against a broken timed path.

Each case drives a whole run on the CPU at a small size, skipping the
look for a chip, with one fault of faults.py planted under the timed
path; ``torn`` is the control. Sound runs of the same cells pass.
"""

import pytest

from .helpers import run_cell, tiny_checkout

CASES = [
    ("seqread-16m.direct", ""),
    ("seqread-16m.direct", "torn"),
    ("seqread-16m.direct", "stale"),
    ("seqread-16m.direct", "half"),
    ("seqread-16m.direct", "altered"),
    ("seqread-16m.verify", ""),
    ("seqread-16m.verify", "torn"),
    ("seqread-16m.verify", "altered"),
    ("randread-4k.ingest", ""),
    ("randread-4k.ingest", "torn"),
    ("randread-4k.ingest", "stale"),
    ("randread-4k.ingest", "half"),
]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_comes_out_false_under_each_fault(checkout, cell, fault):
    extra = ("--fault", fault) if fault else ()
    rc, result, err = run_cell(checkout, cell, *extra)
    assert result is not None, err[-3000:]
    failing = {k for k, c in result["checks"].items()
               if c["value"] > c["limit"]}
    if fault:
        assert result["correct"] is False and rc == 1 and failing, err[-2000:]
    else:
        assert result["correct"] is True and rc == 0, (failing, err[-3000:])
