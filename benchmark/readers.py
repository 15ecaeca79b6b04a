"""The metric readers. A metric named ``<base>`` or ``<base>.<cell kind>``
is read by the function ``<base>`` here, unless ``metrics/<name>.py``
exists with a ``read`` of its own: that is how a later PR adds a metric,
as a file. Each reader takes the run (``run.Run``) and returns a number,
or None where it finds nothing to read."""

import kernels


def hbm_ingest_mib_s(run):
    """MiB the program landed in HBM over the window: its record's
    ``TpuHbmBytes`` (summed over the window's phases) over the host-clock
    window (phase start until every worker is done)."""
    landed = (run.record or {}).get("TpuHbmBytes", 0)
    return landed / (1 << 20) / run.window_s if landed else None


#: the verify cell's rate: the same count, every block fingerprinted
verified_mib_s = hbm_ingest_mib_s


def hbm_iops(run):
    """Blocks the program landed in HBM per second over the window: its
    record's ``TpuHbmBytes`` in whole blocks, over the window."""
    landed = (run.record or {}).get("TpuHbmBytes", 0)
    return landed / run.block_size / run.window_s if landed else None


def setup_s(run):
    """Seconds from process start until the window starts: TPU runtime
    start-up, the dataset, the warm-up and the window's worker set-up."""
    return run.setup_s


def storage_lat_p95_us(run):
    """95th percentile of the program's IOLatUSec histogram in the window:
    the storage leg of each op, which ends before the H2D leg."""
    histo = run.results.iops_histo if run.results is not None else None
    if histo is None or not histo.num_values:
        return None
    return float(histo.percentile(95))


def h2d_dispatch_us_per_op(run):
    """The program's TpuDispatchUSec (host time spent submitting
    transfers) per H2D op, in the window."""
    rec = run.record or {}
    ops = rec.get("TpuH2dStagedOps", 0) + rec.get("TpuH2dDirectOps", 0)
    return rec.get("TpuDispatchUSec", 0) / ops if ops else None


def device_idle_pct(run):
    """Share of the traced window in which none of the program's
    operations ran on the device: 1 less the union of their intervals,
    mean over chips. The tap's sample copies are not the program's."""
    trace = run.trace
    if trace is None or not trace.n_devices or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def elbencho_fingerprint_roofline(run):
    """Share of its roofline that the on-device verify step reaches: the
    least time its runs could take over their device time in the trace.

    The step is the jitted program that holds the ``elbencho_fingerprint``
    Pallas kernel: on a v5e, XLA first copies the block from HBM into VMEM
    (a bitcast fusion), then the kernel reduces it there, then two small
    reductions finish. Only the program reads HBM, so its whole device
    time is the time of the work; the kernel's events alone would leave
    out the HBM read. The least time is the HBM bytes the step must touch
    (kernels.fingerprint_bytes) over the chip's peak HBM bandwidth: a few
    integer operations per word, so bytes bound it."""
    if run.trace is None or run.peaks is None:
        return None
    seconds, runs = run.trace.program_time(r"^elbencho_fingerprint")
    if not runs or seconds <= 0:
        return None
    least = runs * kernels.fingerprint_bytes(run.block_size) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
