"""The comparison that decides ``correct``, against a broken timed path.

Each case drives a whole run on the CPU at a small size, skipping the
look for a chip, with one fault of faults.py planted under the timed
path; ``torn`` is the control. A fault has to read false through the
landed words themselves (``bad_words``), with the program running to
its end, in a cell checked against the dataset and in one checked
against the host bytes given; sound runs of the same cells pass.
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
    ("h2d-ceiling.tpubench", ""),
    ("h2d-ceiling.tpubench", "torn"),
    ("h2d-ceiling.tpubench", "stale"),
    ("h2d-ceiling.tpubench", "half"),
    ("h2d-ceiling.tpubench", "altered"),
]
#: the program's own --tpuverify fails the phase on a landed block whose
#: fingerprint differs, so these runs may also read false through
#: program_errors: every altered block, and a torn one where the salt's
#: carries reach the high words (a torn 16 MiB block of the pattern
#: keeps its fingerprint for other salts)
PROGRAM_CATCHES = {("seqread-16m.verify", "altered"),
                   ("seqread-16m.verify", "torn")}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_comes_out_false_under_each_fault(checkout, cell, fault):
    extra = ("--fault", fault) if fault else ()
    rc, result, err = run_cell(checkout, cell, *extra)
    assert result is not None, err[-3000:]
    checks = {k: c["value"] for k, c in result["checks"].items()}
    if not fault:
        assert result["correct"] is True and rc == 0, (checks, err[-3000:])
        return
    assert result["correct"] is False and rc == 1, err[-2000:]
    assert checks["bad_words"] > 0, checks
    if (cell, fault) not in PROGRAM_CATCHES:
        assert checks["program_errors"] == 0, (checks, err[-2000:])
