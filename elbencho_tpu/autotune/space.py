"""Knob space: the bounded, typed axes the autotuner may move.

Each axis maps one CLI flag onto a geometric value ladder plus the
config-validation constraints that flag already enforces at
``BenchConfig.check`` time — the tuner must never propose a point the
CLI would reject or clamp (``--tpudepth`` > ``--iodepth`` for an
unbatched transfer,
``--tpubatch`` > 1 next to ``--tpuverify``, a poll interval at/above
the ``--svcleasesecs`` lease, ...). The space is derived from the
EFFECTIVE config, so axes that cannot apply to this run (TPU knobs
without a TPU path, control-plane knobs without a fleet) simply do not
exist rather than being probed and rejected at run time.

The axis set mirrors the doctor's verdict->axis hints
(telemetry/doctor.VERDICT_TUNE_AXES): every axis named by a hint is
defined here, and the search falls back to round-robin over whatever
subset this run's config admits.
"""

from __future__ import annotations

import dataclasses
import os

#: knob-space schema version (the Autotune run-JSON block embeds it)
SPACE_SCHEMA = 1

#: (axis name, BenchConfig attr, value ladder ascending, one-line doc) —
#: appended, never reordered; the ladder is geometric so a handful of
#: probes covers orders of magnitude
AXIS_DEFS = (
    ("threads", "num_threads", (1, 2, 4, 8, 16, 32, 64),
     "I/O worker threads per host (--threads)"),
    ("iodepth", "io_depth", (1, 2, 4, 8, 16, 32, 64),
     "async ops in flight per thread (--iodepth)"),
    ("tpudepth", "tpu_depth", (1, 2, 4, 8, 16, 32),
     "in-flight TPU transfer-ring depth (--tpudepth)"),
    ("tpubatch", "tpu_batch_blocks", (1, 2, 4, 8, 16),
     "blocks coalesced per host->HBM DMA (--tpubatch)"),
    ("svcupint", "svc_update_interval_ms", (100, 250, 500, 1000, 2000),
     "service status poll interval in ms (--svcupint; 'up' = slower "
     "polling, fewer control round-trips)"),
    ("svcfanout", "svc_fanout", (0, 2, 4, 8, 16),
     "aggregation-tree fanout (--svcfanout; 0 = flat)"),
)


@dataclasses.dataclass(frozen=True)
class Axis:
    name: str
    attr: str
    ladder: "tuple[int, ...]"
    doc: str


def _threads_cap() -> int:
    """Threads ladder upper bound: twice the machine's cores — past
    that, more threads only add scheduler pressure on every storage
    backend this benchmark drives."""
    return 2 * max(os.cpu_count() or 1, 1)


class KnobSpace:
    """The axes applicable to one effective config, with constraint-aware
    candidate stepping. Pure over the config snapshot it was built from
    (plus the current value map the search threads through), so the
    search loop and its tests never need a live coordinator."""

    def __init__(self, cfg):
        self.axes: "list[Axis]" = []
        self._cfg = cfg
        tpu_path = bool(getattr(cfg, "tpu_ids", None)
                        or cfg.tpu_ids_str or cfg.run_tpu_bench
                        or cfg.run_tpu_slice)
        for name, attr, ladder, doc in AXIS_DEFS:
            if name == "threads":
                cap = _threads_cap()
                ladder = tuple(v for v in ladder if v <= cap) or (1,)
            elif name == "iodepth":
                # a pinned sync engine locks iodepth to 1; object modes
                # use iodepth for connection parallelism, so the axis
                # stays for them
                if cfg.io_engine == "sync":
                    continue
            elif name in ("tpudepth", "tpubatch"):
                if not tpu_path:
                    continue
                if name == "tpubatch" and (cfg.do_tpu_verify
                                           or cfg.run_tpu_bench
                                           or cfg.run_tpu_slice):
                    # --tpubatch>1 is rejected next to --tpuverify, and
                    # the synthetic/slice phases drive their own batching
                    continue
            elif name in ("svcupint", "svcfanout"):
                if not getattr(cfg, "hosts", None):
                    continue
                if name == "svcfanout" and (
                        not cfg.svc_stream or len(cfg.hosts) < 3):
                    # config check rejects --svcfanout without
                    # --svcstream; a 2-host tree is a flat list anyway
                    continue
            self.axes.append(Axis(name, attr, ladder, doc))
        self._by_name = {a.name: a for a in self.axes}

    # -- value access --------------------------------------------------------

    def axis(self, name: str) -> "Axis | None":
        return self._by_name.get(name)

    def names(self) -> "list[str]":
        return [a.name for a in self.axes]

    def current_values(self) -> "dict[str, int]":
        """The effective starting point. ``tpudepth`` 0 means "ride
        --iodepth", so its effective value is the iodepth it rides."""
        out: "dict[str, int]" = {}
        for a in self.axes:
            val = int(getattr(self._cfg, a.attr))
            if a.name == "tpudepth" and not val:
                val = int(getattr(self._cfg, "io_depth", 1))
            out[a.name] = val
        return out

    # -- constraint validation ----------------------------------------------

    def _tpudepth_clamped(self, values: "dict[str, int]") -> str:
        """Why the worker clamps --tpudepth to --iodepth at this point
        ("" = it does not): every unbatched transfer reads its host
        buffer until the ring drains it (LocalWorker's TPU prep)."""
        cfg = self._cfg
        if cfg.use_tpu_direct:
            return "under --tpudirect"
        batch = values.get("tpubatch", int(getattr(cfg, "tpu_batch_blocks",
                                                   1)))
        if cfg.do_tpu_verify or batch <= 1:
            return "for an unbatched staged transfer"
        return ""

    def invalid_reason(self, values: "dict[str, int]", name: str,
                       candidate: int) -> "str | None":
        """Why ``candidate`` on axis ``name`` cannot combine with the
        rest of ``values`` (None = valid). Mirrors BenchConfig.check so
        the tuner never proposes a config the CLI would refuse."""
        cfg = self._cfg
        if candidate < (0 if name == "svcfanout" else 1):
            return "below the axis minimum"
        if name == "threads":
            if candidate <= cfg.num_rwmix_read_threads:
                return ("--rwmixthr must stay below --threads "
                        "(needs at least one writer)")
        clamped = self._tpudepth_clamped(values)
        if name == "tpudepth" and clamped:
            iodepth = values.get(
                "iodepth", int(getattr(cfg, "io_depth", 1)))
            if candidate > iodepth:
                return f"--tpudepth is clamped to --iodepth {clamped}"
        if name == "iodepth" and clamped:
            # partial value maps (sweep grids) fall back to the PINNED
            # config value, not 0 — a pinned --tpudepth must clamp a
            # swept iodepth exactly like a swept tpudepth would
            tpudepth = values.get("tpudepth",
                                  int(getattr(cfg, "tpu_depth", 0)))
            if tpudepth and candidate < tpudepth:
                return ("--iodepth below the current --tpudepth would "
                        f"silently re-clamp the ring {clamped}")
        if name == "tpubatch" and candidate <= 1 \
                and self._tpudepth_clamped({**values, "tpubatch": candidate}):
            tpudepth = values.get("tpudepth",
                                  int(getattr(cfg, "tpu_depth", 0)))
            if tpudepth > values.get("iodepth",
                                     int(getattr(cfg, "io_depth", 1))):
                return ("--tpubatch 1 would silently clamp the current "
                        "--tpudepth to --iodepth")
        if name == "svcupint":
            lease_ms = cfg.svc_lease_secs * 1000
            if lease_ms and candidate >= lease_ms:
                return ("--svcupint must stay below --svcleasesecs "
                        "(every poll renews the lease)")
        if name == "svcfanout" and candidate >= max(
                len(getattr(cfg, "hosts", []) or []), 1):
            return "fanout at/above the host count is a flat tree"
        return None

    def step(self, values: "dict[str, int]", name: str,
             direction: int) -> "int | None":
        """Next valid ladder value from ``values[name]`` in ``direction``
        (+1 up, -1 down), skipping constraint-invalid rungs. None when
        the ladder (or every remaining rung) is exhausted that way."""
        axis = self._by_name[name]
        cur = values[name]
        if direction > 0:
            rungs = [v for v in axis.ladder if v > cur]
        else:
            rungs = [v for v in reversed(axis.ladder) if v < cur]
        for cand in rungs:
            if self.invalid_reason(values, name, cand) is None:
                return cand
        return None

    def describe(self) -> "list[dict]":
        """JSON-able axis table for the Autotune block / --dryrun."""
        vals = self.current_values()
        return [{"Axis": a.name, "Flag": f"--{a.name}",
                 "Current": vals[a.name], "Ladder": list(a.ladder),
                 "Doc": a.doc} for a in self.axes]


#: BenchConfig attr per axis name (profile emission + probe overlays)
AXIS_ATTRS = {name: attr for name, attr, _l, _d in AXIS_DEFS}

#: CLI flag spelling per axis name (tuned-profile emission: the profile
#: is an ini config file of ``flag = value`` lines --configfile loads)
AXIS_FLAGS = {
    "threads": "threads", "iodepth": "iodepth", "tpudepth": "tpudepth",
    "tpubatch": "tpubatch", "svcupint": "svcupint",
    "svcfanout": "svcfanout",
}
