"""Self-test suite for the project-invariant analyzer.

Two halves, mirroring the subsystem (docs/static-analysis.md):

- the STATIC engine (elbencho_tpu/analysis/): one fixture tree per rule
  that violates it, asserted to fail with the named rule + file:line
  through the real CLI; pure-checker unit tests where the rule's repo
  extraction doesn't apply to fixture trees (flags-parity); and the
  clean-tree assertion — the whole catalog over THIS repo must pass,
  which is the `make lint` gate itself;
- the RUNTIME lock-order detector (testing/lockgraph.py): a deliberate
  ABBA inversion, a route_lock held across a live HTTP request, the
  Condition/RLock integration, and the fleet-union merge that catches an
  order split across two processes' dumps.
"""

import http.server
import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from elbencho_tpu.analysis import core as lint_core  # noqa: E402
from elbencho_tpu.analysis import flags_rules, merge_rules  # noqa: E402
from elbencho_tpu.analysis.cli import main as lint_main  # noqa: E402
from elbencho_tpu.testing import lockgraph  # noqa: E402


# --- fixture-tree machinery -------------------------------------------------

def write_tree(root, files: dict) -> str:
    for rel, content in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(content)
    return str(root)


def run_cli(argv, capsys) -> "tuple[int, str, str]":
    rc = lint_main(argv)
    cap = capsys.readouterr()
    return rc, cap.out, cap.err


# --- rule: merge-rules ------------------------------------------------------

MERGE_FIXTURE = {
    "elbencho_tpu/__init__.py": "",
    "elbencho_tpu/tpu/device.py": (
        'PATH_AUDIT_COUNTERS = (\n'
        '    ("a_attr", "KeyA", "m_a"),\n'
        '    ("b_attr", "KeyB", "m_b"),\n'
        '    ("a2_attr", "KeyA", "m_a2"),\n'   # duplicate wire key
        ')\n'
        'PATH_AUDIT_MAX_KEYS = frozenset({"KeyZ"})\n'  # stale name
        'PATH_AUDIT_WORKER_ATTRS = frozenset(())\n'
        'PATH_AUDIT_POOL_ATTRS = frozenset(())\n'),
    "elbencho_tpu/service/fault_tolerance.py": (
        'CONTROL_AUDIT_COUNTERS = (\n'
        '    ("c_attr", "KeyC", "median"),\n'  # bad merge mode
        ')\n'),
    # a merge site hardcoding a schema wire key
    "elbencho_tpu/stats/statistics.py": 'WANT = "KeyB"\n',
}


def test_merge_rules_fixture_violations(tmp_path, capsys):
    root = write_tree(tmp_path, MERGE_FIXTURE)
    rc, _out, err = run_cli(["--root", root, "--rule", "merge-rules"],
                            capsys)
    assert rc == 1
    assert "elbencho_tpu/tpu/device.py:1: merge-rules:" in err
    assert "'KeyA' appears more than once" in err
    assert "PATH_AUDIT_MAX_KEYS names 'KeyZ'" in err
    assert "merge mode 'median'" in err
    assert "elbencho_tpu/stats/statistics.py:1: merge-rules:" in err
    assert "hardcodes wire key 'KeyB'" in err


def test_merge_rules_fixture_clean(tmp_path, capsys):
    fixture = dict(MERGE_FIXTURE)
    fixture["elbencho_tpu/tpu/device.py"] = (
        'PATH_AUDIT_COUNTERS = (("a_attr", "KeyA", "m_a"),)\n'
        'PATH_AUDIT_MAX_KEYS = frozenset({"KeyA"})\n'
        'PATH_AUDIT_WORKER_ATTRS = frozenset(())\n'
        'PATH_AUDIT_POOL_ATTRS = frozenset(())\n')
    fixture["elbencho_tpu/service/fault_tolerance.py"] = \
        'CONTROL_AUDIT_COUNTERS = (("c_attr", "KeyC", "sum"),)\n'
    fixture["elbencho_tpu/stats/statistics.py"] = 'WANT = "NotAKey"\n'
    root = write_tree(tmp_path, fixture)
    rc, _out, _err = run_cli(["--root", root, "--rule", "merge-rules"],
                             capsys)
    assert rc == 0


def test_merge_rules_cross_checks_on_synthetic_schema():
    """The derived-table cross-checks (stream MAX keys, flightrec
    schema) via a synthetic MergeSchema — fixture trees skip them."""
    ms = merge_rules.MergeSchema(
        path_entries=[("a", "KeyA", "ma"), ("b", "KeyB", "mb")],
        path_file="dev.py", path_line=1,
        max_keys={"KeyA"}, max_keys_line=2,
        worker_attrs=set(), worker_attrs_line=3,
        pool_attrs=set(), pool_attrs_line=4,
        control_entries=[("c", "KeyC", "max")],
        control_file="ctl.py", control_line=1,
        stream_max_keys={"KeyA"},  # missing KeyC
        flightrec_schema={"KeyA": "max", "KeyB": "max"},  # KeyB wrong,
                                                         # KeyC missing
    )
    keys = {f.key for f in merge_rules.check_merge_schema(ms)}
    assert "stream-max-drift" in keys
    assert "flightrec-mode:KeyB" in keys
    assert "flightrec-missing:KeyC" in keys


# --- rule: schema-append-only (the absorbed check-schema) -------------------

SCHEMA_FIXTURE = {
    "elbencho_tpu/tpu/device.py": (
        'PATH_AUDIT_COUNTERS = (("a", "KeyA", "ma"), ("b", "KeyB", "mb"))\n'),
    "elbencho_tpu/service/fault_tolerance.py": (
        'CONTROL_AUDIT_COUNTERS = (("c", "KeyC", "sum"),)\n'),
    "elbencho_tpu/stats/statistics.py": (
        'CSV_RESULT_COLUMNS = ("ColA", "ColB")\n'),
    "tools/elbencho-tpu-summarize-json": 'header = ["H1", "H2"]\n',
    "elbencho_tpu/telemetry/slowops.py": (
        'TAIL_ANALYSIS_KEYS = ("k1", "k2")\n'),
}


def _git(root, *args):
    subprocess.run(
        ["git", "-c", "user.email=lint@test", "-c", "user.name=lint",
         *args], cwd=root, check=True, capture_output=True)


def _schema_git_tree(tmp_path) -> str:
    root = write_tree(tmp_path, SCHEMA_FIXTURE)
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    return root


def test_schema_append_only_catches_reorder(tmp_path, capsys):
    root = _schema_git_tree(tmp_path)
    write_tree(tmp_path, {"elbencho_tpu/tpu/device.py":
                          'PATH_AUDIT_COUNTERS = '
                          '(("b", "KeyB", "mb"), ("a", "KeyA", "ma"))\n'})
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "schema-append-only"], capsys)
    assert rc == 1
    assert "elbencho_tpu/tpu/device.py:1: schema-append-only:" in err
    assert "NOT append-only" in err


def test_schema_append_only_allows_append(tmp_path, capsys):
    root = _schema_git_tree(tmp_path)
    write_tree(tmp_path, {"elbencho_tpu/tpu/device.py":
                          'PATH_AUDIT_COUNTERS = (("a", "KeyA", "ma"), '
                          '("b", "KeyB", "mb"), ("c", "KeyC", "mc"))\n'})
    rc, _out, _err = run_cli(
        ["--root", root, "--rule", "schema-append-only"], capsys)
    assert rc == 0


# --- rule: summarize-columns (+ --fix, mechanical rule 2) -------------------

def test_summarize_columns_drift_and_fix(tmp_path, capsys):
    root = write_tree(tmp_path, {
        "tools/elbencho-tpu-summarize-json": 'header = ["H1", "H2"]\n',
        "tools/summarize-columns.txt": "H1\nHX\n",  # drifted manifest
    })
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "summarize-columns"], capsys)
    assert rc == 1
    assert "tools/summarize-columns.txt:2: summarize-columns:" in err
    assert "drifted from the manifest at index 1" in err
    # --fix rewrites the manifest, then the re-lint inside the same
    # invocation comes back clean
    rc, out, _err = run_cli(
        ["--root", root, "--rule", "summarize-columns", "--fix"], capsys)
    assert rc == 0
    assert "fix: rewrote tools/summarize-columns.txt" in out
    with open(os.path.join(root, "tools/summarize-columns.txt")) as f:
        assert [ln for ln in f.read().splitlines()
                if ln and not ln.startswith("#")] == ["H1", "H2"]


# --- rule: lock-discipline --------------------------------------------------

LOCK_FIXTURE = {
    "elbencho_tpu/__init__.py": "",
    "elbencho_tpu/service/http_service.py": (
        "def _make_handler(state):\n"
        "    class Handler:\n"
        "        def do_GET(self):\n"
        "            state.manager.poke()\n"      # unlocked touch
        "            with state.route_lock:\n"
        "                state.cfg = 1\n"         # locked: fine
        "    return Handler\n"),
    "elbencho_tpu/workers/shared.py": (
        "class WorkersSharedData:\n"
        "    def __init__(self, config):\n"
        "        self.config = config\n"
        "        self.phase = 0\n"
        "        self.workers = []\n"
        "    def bump(self):\n"
        "        self.phase += 1\n"),              # own method: fine
    "elbencho_tpu/workers/manager.py": (
        "def bad(shared):\n"
        "    shared.phase = 1\n"                   # unlocked write
        "def also_bad(shared):\n"
        "    shared.workers.append(1)\n"           # unlocked mutation
        "def good(shared):\n"
        "    with shared.cond:\n"
        "        shared.phase = 2\n"),             # flagged lock: fine
}


def test_lock_discipline_fixture_violations(tmp_path, capsys):
    root = write_tree(tmp_path, LOCK_FIXTURE)
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "lock-discipline"], capsys)
    assert rc == 1
    assert ("elbencho_tpu/service/http_service.py:4: "
            "lock-discipline:") in err
    assert "touches `state.manager` outside" in err
    assert "elbencho_tpu/workers/manager.py:2: lock-discipline:" in err
    assert "assigns WorkersSharedData.phase" in err
    assert "elbencho_tpu/workers/manager.py:4: lock-discipline:" in err
    assert "mutates (.append) WorkersSharedData.workers" in err
    # exactly the three: the locked route write, the class's own
    # method, and the with-cond write stay unflagged
    assert err.count(": lock-discipline:") == 3


# --- rule: off-path-guards --------------------------------------------------

OFFPATH_FIXTURE = {
    "elbencho_tpu/__init__.py": "",
    "elbencho_tpu/workers/local_worker.py": (
        "class Worker:\n"
        "    def hot(self):\n"
        "        self._tracer.record_op(1)\n"      # unguarded
        "    def guarded(self):\n"
        "        if self._tracer is not None:\n"
        "            self._tracer.record_op(2)\n"  # guarded
        "    def early_out(self):\n"
        "        t = getattr(self, '_tracer', None)\n"
        "        if t is None:\n"
        "            return\n"
        "        t.record_op(3)\n"                 # alias + early-out
        "    def ternary(self):\n"
        "        t = self._tracer\n"
        "        return t.now_ns() if t is not None else 0\n"),
}


def test_offpath_guards_fixture(tmp_path, capsys):
    root = write_tree(tmp_path, OFFPATH_FIXTURE)
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "off-path-guards"], capsys)
    assert rc == 1
    assert ("elbencho_tpu/workers/local_worker.py:3: "
            "off-path-guards:") in err
    assert "`self._tracer.record_op` runs without" in err
    assert err.count(": off-path-guards:") == 1  # the guarded forms pass


def test_offpath_guards_patrol_the_stage_span_handles(tmp_path, capsys):
    """The per-phase stage-span handles (worker ``_stages``, context and
    pipeline ``stages``) are patrolled like the tracer."""
    root = write_tree(tmp_path, {
        "elbencho_tpu/__init__.py": "",
        "elbencho_tpu/tpu/device.py": (
            "class Pipe:\n"
            "    def drain(self):\n"
            "        self.stages.record('h2d_wait')\n"       # unguarded
            "    def submit(self):\n"
            "        st = self.stages\n"
            "        tok = st.begin('x') if st is not None else None\n"
            "        if st is not None:\n"
            "            st.end(tok, 'x')\n"),
        "elbencho_tpu/workers/local_worker.py": (
            "class Worker:\n"
            "    def loop(self):\n"
            "        return self._stages.next_op()\n"),    # unguarded
    })
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "off-path-guards"], capsys)
    assert rc == 1
    assert "elbencho_tpu/tpu/device.py:3: off-path-guards:" in err
    assert "elbencho_tpu/workers/local_worker.py:3: off-path-guards:" in err
    assert err.count(": off-path-guards:") == 2


# --- rule: wire-hygiene -----------------------------------------------------

WIRE_FIXTURE = {
    "elbencho_tpu/__init__.py": "",
    "elbencho_tpu/config/args.py": (
        'FLAG_DEFS = (\n'
        '    ("alpha", "", "alpha", "str", "", "misc", "a"),\n'
        '    ("beta", "", "beta", "str", "", "misc", "b"),\n'
        '    ("gamma", "", "gamma", "str", "", "misc", "g"),\n'
        ')\n'
        'class BenchConfig:\n'
        '    def to_service_dict(self):\n'
        '        d = {}\n'
        '        d["alpha"] = None\n'
        '        d["gamma"] = None\n'   # strips a field its class ships
        '        return d\n'),
    "elbencho_tpu/journal.py": (
        'FINGERPRINT_EXCLUDE = frozenset({"alpha"})\n'),  # beta missing
    "elbencho_tpu/config/wire_policy.py": (
        'MASTER_ONLY = frozenset({"alpha"})\n'
        'MASTER_FINGERPRINTED = frozenset(())\n'
        'PER_HOST = frozenset(())\n'
        'WIRE_OBSERVABILITY = frozenset({"beta"})\n'
        'WIRE = frozenset({"paths"})\n'),  # gamma: unclassified
}


def test_wire_hygiene_fixture(tmp_path, capsys):
    root = write_tree(tmp_path, WIRE_FIXTURE)
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "wire-hygiene"], capsys)
    assert rc == 1
    assert "config field 'gamma' has no wire_policy class" in err
    assert "to_service_dict assigns 'gamma'" in err
    assert ("classifies 'beta' as observability/master-only but "
            "FINGERPRINT_EXCLUDE does not list it") in err
    assert "elbencho_tpu/config/wire_policy.py:1: wire-hygiene:" in err


def test_wire_hygiene_engine_error_when_policy_missing(tmp_path, capsys):
    fixture = {k: v for k, v in WIRE_FIXTURE.items()
               if "wire_policy" not in k}
    root = write_tree(tmp_path, fixture)
    rc, _out, err = run_cli(
        ["--root", root, "--rule", "wire-hygiene"], capsys)
    assert rc == 2  # the engine cannot run: that is the contract
    assert "wire_policy" in err


# --- rule: flags-parity (pure checkers; repo extraction is repo-only) -------

def test_flags_parity_pure_checkers():
    flag_defs = [
        ("known", "", "known", "str", "", "misc", "documented flag"),
        ("newflag", "", "newflag", "str", "", "misc", "fresh flag"),
    ]
    parity = ("| `--known` | maps |\n"
              "## Beyond the reference\n"
              "| `--ghost` | stale row |\n")
    keys = {f.key for f in flags_rules.check_parity(flag_defs, parity)}
    assert "unaccounted:newflag" in keys
    assert "stale-beyond:ghost" in keys
    assert "unaccounted:known" not in keys
    # generated pages: drift + missing detection against the generator
    pages = flags_rules.generate_usage_pages(flag_defs)
    assert any(p.endswith("help-misc.md") for p in pages)

    class FakeProj:
        def source(self, rel):
            if rel.endswith("help-misc.md"):
                return "hand-edited\n"
            return None
    findings = flags_rules.check_usage_docs(FakeProj(), pages)
    keys = {f.key.split(":", 1)[0] for f in findings}
    assert {"usage-drift", "usage-missing"} <= keys


def test_flags_parity_fix_inserts_inside_beyond_table():
    """Stub rows land in the Beyond-the-reference TABLE, not after
    whatever section happens to be last — otherwise the inserted row
    would be invisible to beyond_table_flags() and gen-flags-parity."""
    parity = ("| `--known` | maps |\n"
              "## Beyond the reference\n"
              "| `--extra` | real row |\n"
              "\n"
              "## Internal wire flags (no user surface)\n"
              "| `--plumbing` | master-set |\n")
    fixed = flags_rules.insert_beyond_stub_rows(
        parity, ["| `--newflag` | (lint --fix stub) fresh |"])
    assert [f for _ln, f in flags_rules.beyond_table_flags(fixed)] \
        == ["extra", "newflag"]


def test_flags_parity_fix_is_idempotent_on_clean_repo():
    """--fix on the clean tree rewrites nothing: the committed usage
    pages and parity doc already match the generator."""
    lint_core.load_all_rules()
    msgs = lint_core.RULES["flags-parity"].fix(lint_core.Project(REPO))
    assert msgs == []


# --- allowlist contract -----------------------------------------------------

def test_allowlist_requires_reason_and_freshness(tmp_path):
    root = write_tree(tmp_path, {
        "tools/lint-allowlist": (
            "# audited exceptions\n"
            "some-rule | live:key | this one is used\n"
            "some-rule | no-reason-key |\n"
            "some-rule | stale-key | was fixed long ago\n"),
    })
    project = lint_core.Project(root)
    allow = lint_core.Allowlist.load(project)
    findings = [lint_core.Finding("some-rule", "f.py", 3, "live:key",
                                  "msg")]
    allow.apply(findings)
    assert findings[0].allowed
    hygiene = {f.key for f in allow.hygiene_findings()}
    assert "no-reason:some-rule:no-reason-key" in hygiene
    assert "stale:some-rule:stale-key" in hygiene
    assert not any(k.startswith("stale:some-rule:live") for k in hygiene)


# --- CLI surface ------------------------------------------------------------

def test_cli_unknown_rule_is_engine_error(capsys):
    rc, _out, err = run_cli(["--rule", "no-such-rule"], capsys)
    assert rc == 2
    assert "unknown rule" in err


def test_cli_json_output_on_fixture(tmp_path, capsys):
    root = write_tree(tmp_path, dict(MERGE_FIXTURE))
    rc, out, _err = run_cli(
        ["--root", root, "--rule", "merge-rules", "--json"], capsys)
    assert rc == 1
    payload = json.loads(out)
    assert payload["clean"] is False
    assert all({"rule", "file", "line", "key", "message"}
               <= set(f) for f in payload["findings"])
    assert any(f["key"] == "dup-key:KeyA" for f in payload["findings"])


def test_clean_tree_whole_catalog_passes():
    """THE gate: the full rule catalog over this repo is clean (modulo
    the audited allowlist) — exactly what `make lint` runs."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "elbencho-tpu-lint")],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "elbencho-tpu-lint: clean" in out.stdout


def test_clean_tree_json_records_allowlisted_findings():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "elbencho-tpu-lint"),
         "--json"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert all(f["allowed"] and f.get("allowReason")
               for f in payload["findings"])


# --- runtime lock-order detector -------------------------------------------

@pytest.fixture
def armed():
    """Arm lockgraph for one test; leave a pre-armed session detector
    (ELBENCHO_TPU_LOCKGRAPH=1 runs) armed but scrub the deliberate
    violations either way so the session-level merge stays green."""
    was_installed = lockgraph.installed()
    if not was_installed:
        lockgraph.install()
    yield lockgraph
    lockgraph.reset()
    if not was_installed:
        lockgraph.uninstall()


def test_lockgraph_catches_deliberate_abba_inversion(armed):
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    first_done = threading.Event()

    def t1():
        with lock_a:
            with lock_b:
                pass
        first_done.set()

    def t2():
        first_done.wait(5)
        with lock_b:      # deliberate inversion — sequenced, so it
            with lock_a:  # records the cycle without deadlocking
                pass

    threads = [threading.Thread(target=t1), threading.Thread(target=t2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    cycles = [v for v in armed.violations()
              if v["kind"] == "lock-order-cycle"]
    assert cycles, "ABBA inversion not detected"
    assert any(len(set(v["cycle"])) == 2 for v in cycles)
    with pytest.raises(lockgraph.LockOrderError):
        armed.merge_check(strict=True)


def test_lockgraph_ignores_consistent_order_and_reentrancy(armed):
    lock_a = threading.Lock()
    rlock = threading.RLock()
    for _ in range(3):
        with lock_a:
            with rlock:
                with rlock:  # reentrant: no self-edge, no cycle
                    pass
    assert armed.violations() == []
    assert (any("lock_a" in a and "rlock" in b
                for a, b in armed.edges()))


def test_lockgraph_condition_wait_notify_still_works(armed):
    """threading.Condition rides the wrapped RLock (the wrapper forwards
    _release_save/_acquire_restore/_is_owned) — a wait/notify round trip
    must behave normally while armed."""
    cond = threading.Condition()
    got = []

    def waiter():
        with cond:
            cond.wait(timeout=10)
            got.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    import time
    for _ in range(100):
        with cond:
            cond.notify_all()
        if got:
            break
        time.sleep(0.05)
    t.join(10)
    assert got and not t.is_alive()
    assert armed.violations() == []


def test_lockgraph_route_lock_across_live_request(armed):
    class Quiet(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *a):  # noqa: A002
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Quiet)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        route_lock = threading.Lock()
        armed.mark_route_lock(route_lock)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ok", timeout=5) as r:
            r.read()  # outside the lock: no violation
        assert armed.violations() == []
        with route_lock:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status", timeout=5) as r:
                r.read()
    finally:
        srv.shutdown()
    hits = [v for v in armed.violations()
            if v["kind"] == "route-lock-across-request"]
    assert len(hits) == 1
    assert hits[0]["request"] == "GET /status"


def test_lockgraph_handoff_reacquire_stays_visible(armed):
    """A plain Lock released by ANOTHER thread (handoff) then
    re-acquired by the original holder must register as a fresh hold —
    the stale depth entry used to make the re-acquire look reentrant,
    leaving the hold invisible to the route-lock check."""
    lk = threading.Lock()
    armed.mark_route_lock(lk)
    lk.acquire()
    t = threading.Thread(target=lk.release)
    t.start()
    t.join(5)
    lk.acquire()  # re-acquire after the cross-thread release
    try:
        assert armed._route_lock_held() is not None
    finally:
        lk.release()
    assert armed._route_lock_held() is None


def test_lockgraph_fleet_union_merge(tmp_path, armed):
    """An order split across two processes — A->B in one dump, B->A in
    the other — is a cycle only the fleet-wide union exhibits."""
    for name, edges in (("lockgraph-101-a.json", [["svc.py:10 (a)",
                                                   "svc.py:20 (b)"]]),
                        ("lockgraph-102-b.json", [["svc.py:20 (b)",
                                                   "svc.py:10 (a)"]])):
        with open(tmp_path / name, "w") as f:
            json.dump({"pid": 0, "edges": edges, "violations": []}, f)
    problems = armed.merge_check(str(tmp_path))
    assert any(v["kind"] == "lock-order-cycle"
               and v.get("source") == "fleet-union" for v in problems)


def test_lockgraph_dump_and_main_arming(tmp_path):
    """python -m elbencho_tpu under the two env vars arms the detector
    and leaves a per-process dump — the seam that makes chaos-suite
    service subprocesses report into the fleet union."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELBENCHO_TPU_TESTING"] = "1"
    env["ELBENCHO_TPU_LOCKGRAPH_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "elbencho_tpu", "--help"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr
    dumps = [n for n in os.listdir(tmp_path)
             if n.startswith("lockgraph-") and n.endswith(".json")]
    assert dumps, "armed subprocess wrote no lockgraph dump"
    with open(tmp_path / dumps[0]) as f:
        payload = json.load(f)
    assert payload["violations"] == []
