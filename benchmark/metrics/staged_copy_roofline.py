"""Share of its HBM roofline that the staged path's per-block device
program reaches: the donated copy of each block just put on the device
into a recycled staging slot (the program's ``staged_slot_copy``).

The copy reads the block once and writes it once, so its least time is
``2 x block bytes`` over the chip's peak HBM bandwidth; its device time
is that of its program runs in the trace. The byte count stays in this
file, beside the metric that uses it."""

#: the copy's jitted program, as the trace names it
PROGRAM = "jit_staged_slot_copy"


def copy_bytes(block_bytes: int) -> int:
    """HBM bytes one slot copy must touch: the block read and written."""
    return 2 * block_bytes


def read(run):
    """None where the trace holds no run of the program (a program that
    does not name its copy, or one that does not run it)."""
    if run.trace is None or run.peaks is None:
        return None
    runs = [sec for program, sec, _ in run.trace.programs
            if program == PROGRAM]
    seconds = sum(runs)
    if not runs or seconds <= 0:
        return None
    least = len(runs) * copy_bytes(run.block_size) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
