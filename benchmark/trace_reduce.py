"""From a JAX profiler trace to the per-layer numbers and the breakdown.

``load`` turns an ``.xplane.pb`` into plain data: a list of planes, each
``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}``
(the small recorded trace under ``tests/data`` has the same form).
``reduce`` then works on that data alone:

- the window is the host event named ``WINDOW`` (the harness's own
  ``TraceAnnotation`` around the measured phase);
- a device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, clipped to the window, each named
  ``<program>/<instruction>`` after the ``XLA Modules`` event (the jitted
  program) that holds it, e.g. ``jit_run/elbencho_fingerprint.1``;
- the benchmark's own programs (``jit_bench_*``, the tap's sample
  copies) are not the program's work: they count only in ``busy_all_s``,
  and every other number leaves them out;
- busy time is the union of a device's operation intervals, averaged over
  the devices; idle is the window less that;
- each of a device's ``NAMED_GAPS`` longest idle gaps is named by the
  host event that overlaps it most, leaving out the harness's umbrella
  spans; a gap with no host event over it is ``host:untraced`` (Python
  and native I/O that the tracer does not see), and the shorter gaps are
  ``gaps:short``.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UMBRELLA = re.compile(r"^bench\.")
#: programs the benchmark itself runs on the device
HARNESS = re.compile(r"^jit_bench_")
#: the longest gaps of each device are named; the rest share one name
NAMED_GAPS = 256
SHORT_GAPS = "gaps:short"


@dataclass
class Summary:
    window_s: float
    n_devices: int
    busy_s: float                    # union of op intervals, mean per device
    busy_all_s: float = 0.0          # the same with the harness's ops
    op_s: "dict[str, float]" = field(default_factory=dict)   # sum, all devices
    op_count: "dict[str, int]" = field(default_factory=dict)
    gap_s: "dict[str, float]" = field(default_factory=dict)  # mean per device
    #: (program, seconds, instruction names) of each program run
    programs: list = field(default_factory=list)

    def time_of(self, pattern: str) -> "tuple[float, int]":
        """Summed device seconds and event count of the operations whose
        name matches ``pattern`` (a regular expression), all devices."""
        rx = re.compile(pattern)
        names = [n for n in self.op_s if rx.search(n)]
        return (sum(self.op_s[n] for n in names),
                sum(self.op_count[n] for n in names))

    def program_time(self, pattern: str) -> "tuple[float, int]":
        """Summed device seconds and count of the program runs (whole
        jitted programs, all devices) that hold an operation whose
        instruction name matches ``pattern``."""
        rx = re.compile(pattern)
        runs = [sec for _, sec, ops in self.programs
                if any(rx.search(op) for op in ops)]
        return sum(runs), len(runs)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s / max(self.n_devices, 1)]
                               for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def load(path: str) -> list:
    """Planes of an ``.xplane.pb`` as plain lists (see the module doc)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)]
                                     for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: "list[tuple[float, float]]") -> "list[tuple[float, float]]":
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: "list[list[float]]" = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _short(name: str) -> str:
    """``%copy.3 = u32[...] copy(...)`` -> ``copy.3``; ``jit_run(123)`` ->
    ``jit_run``."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name.split("(", 1)[0]


def _clipped(events: list, lo: float, hi: float) -> list:
    out = []
    for name, start, dur in events:
        start, end = max(start, lo), min(start + dur, hi)
        if end > start:
            out.append((start, end, name))
    out.sort()
    return out


def _window(planes: list) -> "tuple[float, float]":
    found = [(ev[1], ev[1] + ev[2]) for plane in planes
             if not DEVICE_PLANE.match(plane["name"])
             for line in plane["lines"] for ev in line["events"]
             if ev[0] == WINDOW]
    if len(found) != 1:
        raise ValueError(f"expected one {WINDOW!r} host event, found "
                         f"{len(found)}")
    return found[0]


class _HostIndex:
    """Host events inside the window, umbrellas out, for naming gaps."""

    #: events longer than this are few and always scanned
    LONG_NS = 10e6

    def __init__(self, planes: list, lo: float, hi: float):
        short, self.long = [], []
        for plane in planes:
            if DEVICE_PLANE.match(plane["name"]):
                continue
            for line in plane["lines"]:
                for name, start, dur in line["events"]:
                    end = start + dur
                    if dur > 0 and end > lo and start < hi \
                            and not UMBRELLA.match(name):
                        (self.long if dur > self.LONG_NS else short).append(
                            (start, end, name))
        short.sort()
        self.short = short
        self.starts = [ev[0] for ev in short]

    def name(self, lo: float, hi: float) -> str:
        i = bisect.bisect_left(self.starts, lo - self.LONG_NS)
        j = bisect.bisect_left(self.starts, hi)
        best, best_overlap = "host:untraced", 0.0
        for start, end, name in self.short[i:j] + self.long:
            overlap = min(end, hi) - max(start, lo)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best


def reduce(planes: list) -> Summary:
    lo, hi = _window(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    summary = Summary(window_s=(hi - lo) / 1e9, n_devices=len(devices),
                      busy_s=0.0)
    if not devices:
        return summary
    host = _HostIndex(planes, lo, hi)
    busy_ns = all_ns = 0.0
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = _clipped(lines.get(OPS_LINE, []), lo, hi)
        modules = _clipped(lines.get(MODULES_LINE, []), lo, hi)
        held = [[] for _ in modules]
        k = 0
        intervals, harness = [], []
        for start, end, raw in ops:
            while k < len(modules) and modules[k][1] <= start:
                k += 1
            op = _short(raw)
            program = ""
            if k < len(modules) and modules[k][0] <= start:
                program = _short(modules[k][2])
                held[k].append(op)
            if HARNESS.match(program):
                harness.append((start, end))
                continue
            name = f"{program}/{op}" if program else op
            intervals.append((start, end))
            summary.op_s[name] = summary.op_s.get(name, 0.0) \
                + (end - start) / 1e9
            summary.op_count[name] = summary.op_count.get(name, 0) + 1
        summary.programs += [(_short(m[2]), (m[1] - m[0]) / 1e9, tuple(h))
                             for m, h in zip(modules, held)
                             if not HARNESS.match(_short(m[2]))]
        merged = union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        all_ns += sum(e - s for s, e in union(intervals + harness))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                       if b > a), reverse=True)
        for rank, (length, gap_lo, gap_hi) in enumerate(gaps):
            name = host.name(gap_lo, gap_hi) if rank < NAMED_GAPS \
                else SHORT_GAPS
            summary.gap_s[name] = summary.gap_s.get(name, 0.0) \
                + length / 1e9 / len(devices)
    summary.busy_s = busy_ns / 1e9 / len(devices)
    summary.busy_all_s = all_ns / 1e9 / len(devices)
    return summary
