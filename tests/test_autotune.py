"""Closed-loop autotuning suite (--autotune; docs/autotuning.md).

Covers the subsystem at every layer:
- search: a deterministic fake-doctor harness proves each verdict moves
  the axis its hint names, plateau/budget/probe-cap all stop the climb,
  and repeat-probe MEDIANS reject injected noise;
- space: axis applicability follows the effective config (TPU axes need
  a TPU path, control-plane axes need a streamed fleet) and the
  constraint validators mirror config validation (tpudepth<=iodepth
  for an unbatched transfer, svcupint below the lease);
- config: flag parsing (bare --autotune = 60s), the --autotune-* gate,
  and the scenario/resume/service rejections;
- profile: emit -> load (-c) -> identical knob values;
- doctor: TuneHint hints + InconclusiveWhy gate-naming evidence;
- e2e: a local run emits the Autotune block + profile and stamps the
  tuned phase records; the CHAOS e2e injects a uniform per-op delay
  into an in-process 2-host fleet (slowops.TEST_UNIFORM_OP_DELAY_BY_
  PORT) and proves the tuner beats the defaults by >= 10% AND that
  re-running with the emitted profile (no autotune) reproduces the
  tuned rate — the acceptance criterion;
- tools: summarize-json Tuned/Gain% columns + AUTOTUNE banner, the
  knob-grid sweep tool, and chart --sweep.

Run via `make test-tune` (marker `tune`, lockgraph-armed — the probe
loop exercises repeated master-mode rebuilds); also part of the
default tier-1 pytest sweep and the chaos stage of `make check`.
"""

import json
import os
import subprocess
import sys

import pytest

from elbencho_tpu.autotune import (AUTOTUNE_SCHEMA, KnobSpace, hill_climb,
                                   probe_phase_for, write_profile)
from elbencho_tpu.autotune.search import (STOP_BUDGET, STOP_PLATEAU,
                                          STOP_PROBES, ProbeOutcome)
from elbencho_tpu.config.args import ConfigError, parse_cli
from elbencho_tpu.phases import BenchPhase

pytestmark = pytest.mark.tune

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(extra=(), paths=("/tmp/_tune_cfg",)):
    cfg, _ = parse_cli([*extra, *paths])
    cfg.derive(probe_paths=False)
    return cfg


def _run_main(args):
    from elbencho_tpu.cli import main
    return main(args + ["--nolive"])


def _recs(path):
    return [json.loads(ln) for ln in open(path) if ln.strip()]


#: a config admitting every axis: POSIX read, one TPU chip, a 4-host
#: streamed fleet
_ALL_AXES_ARGS = ("-r", "--tpuids", "0", "--hosts", "h1,h2,h3,h4",
                  "--svcstream")


def _space(extra=_ALL_AXES_ARGS):
    return KnobSpace(_cfg(extra))


# ---------------------------------------------------------------------------
# search: fake-doctor convergence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("verdict,axis", [
    ("storage-bound", "iodepth"),
    ("dispatch-bound", "tpubatch"),
    ("dma-bound", "tpudepth"),
    ("stall-bound", "tpudepth"),
    ("control-bound", "svcfanout"),
])
def test_each_verdict_moves_the_named_axis(verdict, axis):
    """The doctor's hint table steers the FIRST move: the axis probed
    right after the baseline is the one the verdict names."""
    # a pinned --tpudepth below --iodepth leaves the unbatched ring room
    # to deepen (it is clamped to --iodepth)
    space = _space(_ALL_AXES_ARGS + ("--iodepth", "8", "--tpudepth", "2"))

    def run_probe(_values):
        return ProbeOutcome(100.0, verdict=verdict)

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0, max_probes=2)
    assert result.trajectory[1].axis == axis


def test_inconclusive_falls_back_to_round_robin():
    """An unhinted verdict still makes progress: the climb round-robins
    over the axes in space order instead of stalling."""
    space = _space()

    def run_probe(_values):
        return ProbeOutcome(100.0, verdict="inconclusive")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0, max_probes=3)
    moved = [p.axis for p in result.trajectory[1:]]
    assert moved == space.names()[:len(moved)]


def test_convergence_on_constructed_storage_bottleneck():
    """Rate grows with iodepth up to 16 then flattens: the climb must
    land exactly on 16 and stop on plateau, never wandering past it."""
    space = _space(("-r",))  # threads + iodepth only

    def run_probe(values):
        return ProbeOutcome(100.0 * min(values["iodepth"], 16),
                            verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0)
    assert result.best.values["iodepth"] == 16
    assert result.stop_reason == STOP_PLATEAU
    assert result.gain_pct == pytest.approx(1500.0)
    # every accepted step really improved on the incumbent
    accepted = [p for p in result.trajectory if p.accepted]
    rates = [p.rate_mibs for p in accepted]
    assert rates == sorted(rates)


def test_plateau_stops_after_every_move_rejected():
    space = _space(("-r",))  # threads + iodepth

    def run_probe(_values):
        return ProbeOutcome(100.0, verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0)
    assert result.stop_reason == STOP_PLATEAU
    # baseline + one up-probe per axis (down from the ladder floor is
    # exhausted without a probe)
    assert result.probes_used == 1 + len(space.names())
    assert result.best.values == result.baseline.values


def test_budget_stops_the_climb():
    space = _space(("-r",))
    clock = iter([0.0, 10.0, 20.0])

    def run_probe(_values):
        return ProbeOutcome(100.0, verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=8.0,
                        now=lambda: next(clock))
    assert result.stop_reason == STOP_BUDGET
    assert result.probes_used == 1  # baseline only


def test_probe_cap_stops_the_climb():
    space = _space(("-r",))

    def run_probe(values):
        return ProbeOutcome(100.0 * values["iodepth"],
                            verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0, max_probes=3)
    assert result.stop_reason == STOP_PROBES
    assert result.probes_used == 3


def test_repeat_median_rejects_injected_noise():
    """One wild outlier repeat must not buy a candidate acceptance: the
    MEDIAN of the repeats is what competes."""
    space = _space(("-r",))
    calls = {"n": 0}

    def run_probe(_values):
        calls["n"] += 1
        # candidate probes (4..6): two honest repeats + one outlier
        if calls["n"] > 3 and calls["n"] % 3 == 0:
            return ProbeOutcome(10_000.0, verdict="storage-bound")
        return ProbeOutcome(100.0, verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0, repeat=3, max_probes=6)
    candidate = result.trajectory[1]
    assert 10_000.0 in candidate.repeats  # the outlier really happened
    assert candidate.rate_mibs == 100.0   # ...and the median ignored it
    assert not candidate.accepted
    assert result.best.values == result.baseline.values


def test_failed_probes_never_become_the_incumbent():
    space = _space(("-r",))

    def run_probe(values):
        if values["iodepth"] > 1:
            return ProbeOutcome(0.0, ok=False, error="worker died")
        return ProbeOutcome(100.0, verdict="storage-bound")

    result = hill_climb(space, run_probe, budget_secs=1e9,
                        now=lambda: 0.0)
    assert result.best.values["iodepth"] == 1
    assert result.stop_reason == STOP_PLATEAU


# ---------------------------------------------------------------------------
# space: applicability + constraint validation
# ---------------------------------------------------------------------------

def test_axis_applicability_follows_config():
    assert _space(("-r",)).names() == ["threads", "iodepth"]
    assert _space(("-r", "--tpuids", "0")).names() \
        == ["threads", "iodepth", "tpudepth", "tpubatch"]
    # --tpuverify forbids --tpubatch > 1: the axis must not exist
    assert "tpubatch" not in _space(
        ("-r", "--tpuids", "0", "--tpuverify")).names()
    assert _space().names() == ["threads", "iodepth", "tpudepth",
                                "tpubatch", "svcupint", "svcfanout"]
    # a 2-host tree is flat: no fanout axis; no stream, no fanout either
    assert "svcfanout" not in _space(
        ("-r", "--hosts", "h1,h2", "--svcstream")).names()
    assert "svcfanout" not in _space(
        ("-r", "--hosts", "h1,h2,h3,h4")).names()
    assert "svcupint" in _space(("-r", "--hosts", "h1,h2")).names()
    # a pinned sync engine locks iodepth
    assert "iodepth" not in _space(("-r", "--ioengine", "sync")).names()


def test_tpudirect_clamps_tpudepth_to_iodepth():
    space = _space(("-r", "--tpuids", "0", "--tpudirect",
                    "--iodepth", "4"))
    values = {"threads": 1, "iodepth": 4, "tpudepth": 4, "tpubatch": 1}
    assert space.invalid_reason(values, "tpudepth", 8) is not None
    assert space.step(values, "tpudepth", 1) is None  # 8+ all clamped
    assert space.step(values, "tpudepth", -1) == 2
    # and iodepth may not dive under the current tpudepth either
    assert space.invalid_reason(values, "iodepth", 2) is not None
    # the staged path is clamped too while unbatched; --tpubatch copies
    # each block into its own ring, so a batched point may go deeper
    without_direct = _space(("-r", "--tpuids", "0", "--iodepth", "4"))
    assert without_direct.step(values, "tpudepth", 1) is None
    assert "unbatched staged" in without_direct.invalid_reason(
        values, "tpudepth", 8)
    batched = {**values, "tpubatch": 2}
    assert without_direct.step(batched, "tpudepth", 1) == 8
    assert without_direct.invalid_reason(
        {**batched, "tpudepth": 8}, "tpubatch", 1) is not None
    assert without_direct.invalid_reason(batched, "tpubatch", 1) is None
    # partial value maps (sweep grids sweep only SOME axes): the PINNED
    # --tpudepth clamps a swept iodepth even with no tpudepth entry
    pinned = _space(("-r", "--tpuids", "0", "--tpudirect",
                     "--iodepth", "8", "--tpudepth", "8"))
    assert pinned.invalid_reason({"iodepth": 8}, "iodepth", 2) \
        is not None


def test_svcupint_stays_below_the_lease():
    space = _space(("-r", "--hosts", "h1,h2", "--svcleasesecs", "1"))
    values = space.current_values()
    assert space.invalid_reason(values, "svcupint", 1000) is not None
    assert space.step(values, "svcupint", 1) is None  # 1000+ invalid
    no_lease = _space(("-r", "--hosts", "h1,h2"))
    assert no_lease.step(values, "svcupint", 1) == 1000


def test_current_values_tpudepth_rides_iodepth():
    space = _space(("-r", "--tpuids", "0", "--iodepth", "8"))
    assert space.current_values()["tpudepth"] == 8
    pinned = _space(("-r", "--tpuids", "0", "--iodepth", "8",
                     "--tpudepth", "2"))
    assert pinned.current_values()["tpudepth"] == 2


# ---------------------------------------------------------------------------
# config: parsing + validation
# ---------------------------------------------------------------------------

def test_bare_autotune_flag_means_default_budget():
    assert _cfg(("-r", "--autotune")).autotune_secs == 60
    assert _cfg(("-r", "--autotune", "30")).autotune_secs == 30
    assert _cfg(("-r",)).autotune_secs == 0


@pytest.mark.parametrize("argv", [
    ("-r", "--autotune-probesecs", "5"),
    ("-r", "--autotune-repeat", "3"),
    ("-r", "--autotune-probes", "8"),
    ("-r", "--autotune-profile", "/tmp/x.conf"),
])
def test_autotune_subknobs_require_autotune(argv):
    with pytest.raises(ConfigError, match="--autotune"):
        _cfg(argv).check()


@pytest.mark.parametrize("argv,match", [
    (("--autotune", "--stat"), "write or read phase"),
    (("--autotune", "--scenario", "epochs"), "scenario"),
    (("--autotune", "-r", "--service"), "master"),
    (("--autotune", "-r", "--journal", "/tmp/j", "--resume"), "resume"),
])
def test_autotune_rejected_combos(argv, match):
    with pytest.raises(ConfigError, match=match):
        _cfg(argv).check()


def test_autotune_knobs_are_master_only_on_the_wire():
    cfg = _cfg(("-r", "--autotune", "30", "--autotune-repeat", "2"))
    d = cfg.to_service_dict()
    assert d["autotune_secs"] == 0
    assert d["autotune_repeat"] == 1
    # a service rebuilding from the wire dict passes validation
    from elbencho_tpu.config.args import BenchConfig
    svc = BenchConfig.from_service_dict(d, derive=False)
    svc.derive(probe_paths=False)
    svc.check()
    assert svc.autotune_secs == 0


def test_autotune_knobs_never_invalidate_the_fingerprint():
    from elbencho_tpu.journal import config_fingerprint
    plain = _cfg(("-r",))
    tuned = _cfg(("-r", "--autotune", "30", "--autotune-probesecs", "2"))
    assert config_fingerprint(plain) == config_fingerprint(tuned)


def test_probe_phase_selection():
    assert probe_phase_for(_cfg(("-w", "-r"))) == BenchPhase.CREATEFILES
    assert probe_phase_for(_cfg(("-r",))) == BenchPhase.READFILES
    assert probe_phase_for(_cfg(("--stat",))) is None


# ---------------------------------------------------------------------------
# profile round-trip
# ---------------------------------------------------------------------------

def test_profile_round_trip_emit_load_identical(tmp_path):
    """emit -> load (-c) -> identical knob values on the effective
    config, with CLI flags still winning over the profile."""
    chosen = {"threads": 4, "iodepth": 8, "tpudepth": 4, "tpubatch": 2}
    prof = tmp_path / "tuned.conf"
    cfg0 = _cfg(("-r", "--tpuids", "0"))
    write_profile(str(prof), chosen, cfg0, 42.0, "storage-bound")
    cfg, _ = parse_cli(["-r", "--tpuids", "0", "-c", str(prof),
                        "/tmp/_tune_cfg"])
    assert cfg.num_threads == 4
    assert cfg.io_depth == 8
    assert cfg.tpu_depth == 4
    assert cfg.tpu_batch_blocks == 2
    # explicit CLI value beats the profile (config-file merge contract)
    cfg, _ = parse_cli(["-r", "--tpuids", "0", "-t", "2",
                        "-c", str(prof), "/tmp/_tune_cfg"])
    assert cfg.num_threads == 2
    assert cfg.io_depth == 8


# ---------------------------------------------------------------------------
# doctor: hints + inconclusive-why
# ---------------------------------------------------------------------------

def test_doctor_attaches_tune_hints():
    from elbencho_tpu.telemetry.doctor import analyze_phase
    ana = analyze_phase("READ", {"IoBusyUSec": 9_000_000}, 1_000_000, 10)
    assert ana["Verdict"] == "storage-bound"
    assert ana["TuneHint"] == ["iodepth", "threads"]
    assert ana["InconclusiveWhy"] == []


def test_doctor_inconclusive_says_which_gate_failed():
    from elbencho_tpu.telemetry.doctor import analyze_phase
    # a stage recorded time but stays under the dominance gate
    ana = analyze_phase("STAT", {"IoBusyUSec": 100_000}, 1_000_000, 10,
                        series=[(0.5, {"IoBusyUSec": 100_000})])
    assert ana["Verdict"] == "inconclusive"
    assert ana["TuneHint"] == []
    why = " | ".join(ana["InconclusiveWhy"])
    assert "no stage >= 15% of worker time" in why
    assert "max: storage at 1%" in why
    assert "shorter than 2 recorded ticks" in why
    for line in ana["InconclusiveWhy"]:
        assert line in ana["Evidence"]
    # no stages at all names THAT gate instead
    ana = analyze_phase("STAT", {}, 1_000_000, 10)
    assert "no instrumented stage recorded any time" \
        in " | ".join(ana["InconclusiveWhy"])


# ---------------------------------------------------------------------------
# e2e: local run
# ---------------------------------------------------------------------------

def test_autotune_local_e2e_block_profile_and_stamps(tmp_path):
    """A tiny local --autotune run: Autotune block + profile land, the
    measured phase records are stamped, and probe traffic never reaches
    the result files."""
    target = tmp_path / "bench" / "data.bin"
    (tmp_path / "bench").mkdir()
    jf = tmp_path / "r.json"
    prof = tmp_path / "tuned.conf"
    rc = _run_main(["-w", "-r", "-t", "1", "-s", "256K", "-b", "64K",
                    "--autotune", "6", "--autotune-probesecs", "1",
                    "--autotune-probes", "4",
                    "--autotune-profile", str(prof),
                    "--jsonfile", str(jf), str(target)])
    assert rc == 0
    recs = _recs(jf)
    # exactly AUTOTUNE + WRITE + READ: probes never land in results
    assert [r["Phase"] for r in recs] == ["AUTOTUNE", "WRITE", "READ"]
    block = recs[0]["Autotune"]
    assert block["Schema"] == AUTOTUNE_SCHEMA
    assert block["ProbesUsed"] >= 1
    assert block["Default"]["MiBPerSec"] > 0
    assert block["StopReason"] in ("plateau", "budget", "probe-limit")
    assert [p["Probe"] for p in block["Trajectory"]] \
        == list(range(len(block["Trajectory"])))
    assert block["ProfilePath"] == str(prof)
    assert prof.exists()
    # the before/after doctor diff rides the block (proof, not a shrug)
    diff = block["DoctorDiff"]
    assert diff["Default"] is not None
    assert diff["Default"]["Verdict"]
    assert diff["Tuned"]["StagePct"]
    for rec in recs[1:]:
        assert rec["AutotuneTuned"] is True
        assert isinstance(rec["AutotuneGainPct"], (int, float))
    # the emitted profile parses through the normal config-file loader
    cfg, _ = parse_cli(["-r", "-c", str(prof), str(target)])
    assert cfg.num_threads == block["Chosen"]["Values"]["threads"]


def test_failed_baseline_never_reclaims_the_win(tmp_path, monkeypatch):
    """A FAILED (or zero-rate) baseline probe must not drag the run
    back to the defaults when the climb found a point that provably
    worked — the zero-gain fallback only applies against a MEASURED
    baseline."""
    import elbencho_tpu.autotune as at
    from elbencho_tpu.autotune.search import TrajectoryPoint, TuneResult

    def fake_climb(space, _run_probe, budget_secs, now, **_kw):
        base = TrajectoryPoint(0, space.current_values(), 0.0,
                               "inconclusive", [], False,
                               error="worker died")
        best_vals = dict(base.values)
        best_vals["threads"] = 2
        best = TrajectoryPoint(1, best_vals, 500.0, "storage-bound",
                               [500.0], True, axis="threads",
                               accepted=True)
        return TuneResult(base, best, [base, best], "plateau", 2)

    monkeypatch.setattr(at, "hill_climb", fake_climb)
    target = tmp_path / "bench" / "data.bin"
    (tmp_path / "bench").mkdir()
    jf = tmp_path / "r.json"
    rc = _run_main(["-w", "-t", "1", "-s", "128K", "-b", "32K",
                    "--autotune", "5",
                    "--autotune-profile", str(tmp_path / "t.conf"),
                    "--jsonfile", str(jf), str(target)])
    assert rc == 0
    recs = _recs(jf)
    block = recs[0]["Autotune"]
    assert block["Chosen"]["Values"]["threads"] == 2  # the working point
    assert block["GainPct"] == 0  # no measured baseline to compare to
    wrec = next(r for r in recs if r["Phase"] == "WRITE")
    assert int(wrec["Config"]["num_threads"]) == 2


def test_journal_fingerprints_the_tuned_config(tmp_path, monkeypatch):
    """A journaled tuned run writes its fingerprint against the TUNED
    effective config (journal setup is deferred past the tuner), so
    `--resume -c PROFILE` is the working recovery path and resuming
    with the untuned flags is a hard mismatch — never a silent re-run
    of the remaining phases at different knobs."""
    import elbencho_tpu.autotune as at
    from elbencho_tpu.autotune.search import TrajectoryPoint, TuneResult

    def fake_climb(space, _run_probe, budget_secs, now, **_kw):
        base = TrajectoryPoint(0, space.current_values(), 10.0,
                               "storage-bound", [10.0], True,
                               accepted=True)
        best_vals = dict(base.values)
        best_vals["threads"] = 2  # a DIFFERENT tuned point, always
        best = TrajectoryPoint(1, best_vals, 20.0, "storage-bound",
                               [20.0], True, axis="threads",
                               accepted=True)
        return TuneResult(base, best, [base, best], "plateau", 2)

    monkeypatch.setattr(at, "hill_climb", fake_climb)
    target = tmp_path / "bench" / "data.bin"
    (tmp_path / "bench").mkdir()
    journal = tmp_path / "run.journal"
    prof = tmp_path / "tuned.conf"
    base_args = ["-w", "-r", "-t", "1", "-s", "128K", "-b", "32K"]
    rc = _run_main([*base_args, "--autotune", "5",
                    "--autotune-profile", str(prof),
                    "--journal", str(journal), str(target)])
    assert rc == 0
    # recovery path: same flags + the emitted profile, no re-tuning —
    # the fingerprint matches and the complete journal is a no-op
    rc = _run_main([*base_args, "-c", str(prof), "--journal",
                    str(journal), "--resume", str(target)])
    assert rc == 0
    # the UNTUNED flags describe a run the journal never recorded
    rc = _run_main([*base_args, "--journal", str(journal), "--resume",
                    str(target)])
    assert rc == 1


# ---------------------------------------------------------------------------
# e2e: chaos acceptance — injected delay, 2-host fleet
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_autotune_chaos_fleet_beats_defaults_and_reproduces(
        tmp_path, monkeypatch):
    """Acceptance criterion e2e: a uniform 2ms injected per-op delay on
    BOTH hosts of an in-process fleet makes storage delay-dominated, so
    throughput scales with parallelism — the tuner (starting from the
    deliberately bad -t 1 default) must converge to a config >= 10%
    over the default within its budget, and re-running with the
    emitted profile (no autotune) must reproduce the tuned rate."""
    from elbencho_tpu.telemetry import slowops
    from elbencho_tpu.testing.service_harness import in_process_services
    from elbencho_tpu.utils import native
    monkeypatch.setenv("ELBENCHO_TPU_TESTING", "1")
    monkeypatch.setenv("ELBENCHO_TPU_NO_NATIVE", "1")  # Python loop
    # the engine handle is cached process-globally; an earlier in-process
    # test may have loaded it BEFORE the env knob above — drop the cache
    # so the delay seam (Python-loop only) really engages (monkeypatch
    # restores the cached engine afterwards)
    monkeypatch.setattr(native, "_engine", None)
    monkeypatch.setattr(native, "_engine_checked", True)
    bench = tmp_path / "bench"
    bench.mkdir()
    jf = tmp_path / "r.json"
    prof = tmp_path / "tuned.conf"
    shape = ["-d", "-n", "1", "-N", "16", "-s", "512K", "-b", "32K"]
    with in_process_services(2) as ports:
        for port in ports:
            monkeypatch.setitem(
                slowops.TEST_UNIFORM_OP_DELAY_BY_PORT, port, 2000)
        hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
        rc = _run_main(["-w", "--hosts", hosts, "-t", "1", *shape,
                        "--autotune", "25", "--autotune-probesecs", "1",
                        "--autotune-profile", str(prof),
                        "--jsonfile", str(jf), str(bench)])
        assert rc == 0
        recs = _recs(jf)
        block = next(r["Autotune"] for r in recs if r.get("Autotune"))
        assert block["GainPct"] >= 10.0, block
        chosen = block["Chosen"]["Values"]
        assert chosen["threads"] > 1, block  # parallelism beat the delay
        wrec = next(r for r in recs if r["Phase"] == "WRITE")
        assert wrec["AutotuneTuned"] is True
        assert int(wrec["Config"]["num_threads"]) == chosen["threads"]
        assert wrec["NumWorkers"] == 2  # both hosts worked the phase
        # the doctor named the constructed bottleneck along the way,
        # and the before/after diff confirms the improvement
        verdicts = {p["Verdict"] for p in block["Trajectory"]}
        assert "storage-bound" in verdicts
        diff = block["DoctorDiff"]
        assert diff["Default"] is not None and diff["Tuned"] is not None
        # reproduce: the emitted profile, no autotune, same fleet
        jf2 = tmp_path / "r2.json"
        rc = _run_main(["-w", "--hosts", hosts, "-c", str(prof), *shape,
                        "--jsonfile", str(jf2), str(bench)])
        assert rc == 0
        rerun = next(r for r in _recs(jf2) if r["Phase"] == "WRITE")
        assert int(rerun["Config"]["num_threads"]) == chosen["threads"]
        assert rerun["AutotuneTuned"] is False  # no tuning this run
        # the profile run lands at the TUNED rate, not the default one
        assert rerun["MiBPerSecLast"] \
            >= block["Default"]["MiBPerSec"] * 1.05


# ---------------------------------------------------------------------------
# tools: summarize columns/banner, knob sweep, chart --sweep
# ---------------------------------------------------------------------------

def test_summarize_appends_tuned_columns_and_banners(tmp_path):
    jf = tmp_path / "r.json"
    block = {"Schema": 1, "GainPct": 12.5, "StopReason": "plateau",
             "ProbesUsed": 7, "ProfilePath": "/tmp/p.conf",
             "Chosen": {"Values": {"threads": 4, "iodepth": 8}}}
    jf.write_text(
        json.dumps({"Phase": "AUTOTUNE", "Autotune": block}) + "\n"
        + json.dumps({"Phase": "READ", "AutotuneTuned": True,
                      "AutotuneGainPct": 12.5}) + "\n"
        + json.dumps({"Phase": "WRITE"}) + "\n")
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO_DIR, "tools", "elbencho-tpu-summarize-json"),
         str(jf), "--csv"], capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    header = res.stdout.splitlines()[0].split(",")
    # the master-failover Adopt/Takeover pair appends after Tuned/Gain%
    assert header[-4:-2] == ["Tuned", "Gain%"]
    rows = [ln.split(",") for ln in res.stdout.splitlines()[1:]]
    assert all(row[0] != "AUTOTUNE" for row in rows)  # bannered out
    read_row = next(r for r in rows if r[0] == "READ")
    assert read_row[-4:-2] == ["yes", "12.5"]
    write_row = next(r for r in rows if r[0] == "WRITE")
    assert write_row[-4:-2] == ["", ""]
    assert "AUTOTUNE [plateau, 7 probes]: +12.5%" in res.stderr
    assert "threads=4" in res.stderr


def test_knob_sweep_tool_and_chart_surface(tmp_path):
    """The sweep tool's knob-grid mode probes the cross product through
    the same executor and chart --sweep renders the surface."""
    target = tmp_path / "sweep.bin"
    out = tmp_path / "surface.json"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "ELBENCHO_TPU_NO_NATIVE": "1",
                "ELBENCHO_TPU_NO_DEFAULT_RESFILES": "1"})
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO_DIR, "tools", "elbencho-tpu-sweep"),
         "--knob", "threads=1,2", "--knob", "iodepth=1,8",
         "--probesecs", "1", "--out", str(out), "--",
         "-w", "-t", "1", "-s", "128K", "-b", "32K", "--nolive",
         str(target)],
        capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert doc["Mode"] == "knob-grid"
    assert len(doc["Points"]) == 4  # full cross product, none skipped
    assert all(p["Ok"] and p["MiBPerSec"] > 0 for p in doc["Points"])
    assert {a["Axis"] for a in doc["Axes"]} == {"threads", "iodepth"}
    assert doc["Best"]["MiBPerSec"] \
        == max(p["MiBPerSec"] for p in doc["Points"])
    chart = subprocess.run(
        [sys.executable,
         os.path.join(REPO_DIR, "tools", "elbencho-tpu-chart"),
         "--sweep", str(out)],
        capture_output=True, text=True, timeout=60)
    assert chart.returncode == 0, chart.stderr
    assert "sweep surface" in chart.stdout
    assert "*" in chart.stdout  # best cell marked


def test_knob_sweep_records_skipped_invalid_points(tmp_path):
    """Constraint-invalid grid points are SKIPPED with a recorded
    reason, never silently dropped: tpudepth > iodepth under
    --tpudirect."""
    target = tmp_path / "sweep.bin"
    out = tmp_path / "surface.json"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "ELBENCHO_TPU_NO_NATIVE": "1",
                "ELBENCHO_TPU_NO_DEFAULT_RESFILES": "1"})
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO_DIR, "tools", "elbencho-tpu-sweep"),
         "--knob", "tpudepth=1,8", "--probesecs", "1",
         "--out", str(out), "--",
         "-w", "-t", "1", "-s", "128K", "-b", "32K", "--iodepth", "4",
         "--tpuids", "0", "--tpudirect", "--nolive", str(target)],
        capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert len(doc["Points"]) == 1
    assert len(doc["Skipped"]) == 1
    assert doc["Skipped"][0]["Values"] == {"tpudepth": 8}
    assert "tpudirect" in doc["Skipped"][0]["Reason"]
