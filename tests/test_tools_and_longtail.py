"""Tests for --treescan, the tools suite, flock, statinline, netbench
config, and fullscreen-stats plumbing."""

import json
import os
import subprocess
import sys

import pytest

from elbencho_tpu.cli import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


@pytest.fixture(autouse=True)
def _no_native(monkeypatch):
    monkeypatch.setenv("ELBENCHO_TPU_NO_NATIVE", "1")
    from elbencho_tpu.utils.native import reset_native_engine_cache
    reset_native_engine_cache()


def _tool(name, args):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(TOOLS, name)] + args,
        capture_output=True, text=True, env=env, timeout=180)


def test_treescan_roundtrip(tmp_path):
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.bin").write_bytes(b"x" * 1000)
    (src / "sub" / "b.bin").write_bytes(b"y" * 2500)
    treefile = tmp_path / "tree.txt"
    rc = main(["--treescan", str(src), "--treefile", str(treefile),
               "--nolive"])
    assert rc == 0
    content = treefile.read_text()
    assert "d sub" in content
    assert "f 1000 a.bin" in content
    assert "f 2500 sub/b.bin" in content
    # and the treefile drives a benchmark
    bench = tmp_path / "bench"
    bench.mkdir()
    rc = main(["-w", "-r", "-F", "-t", "2", "-b", "1K", "--treefile",
               str(treefile), "--nolive", str(bench)])
    assert rc == 0


def test_bucket_treescan_s3_and_gcs(tmp_path):
    """--treescan s3://bucket[/prefix] lists the bucket into a treefile
    (reference: ProgArgs::scanCustomTree S3 branch + S3Tk::scanCustomTree)
    and the same front-end serves gs:// via the GCS-native client."""
    from elbencho_tpu.testing.mock_s3 import MockS3Server
    from elbencho_tpu.testing.mock_gcs import MockGcsServer
    from elbencho_tpu.toolkits.path_store import PathStore

    s3 = MockS3Server().start()
    try:
        bench = tmp_path / "bench"
        bench.mkdir()
        s3_args = ["--s3endpoints", s3.endpoint, "--s3key", "k",
                   "--s3secret", "s", "--nolive"]
        # populate: 1 dir x 3 files of 2K, plus objects under a prefix
        assert main(["-w", "-d", "-t", "1", "-n", "1", "-N", "3",
                     "-s", "2K", "-b", "2K"] + s3_args + ["scanbkt"]) == 0
        assert main(["-w", "-d", "-t", "1", "-n", "1", "-N", "2",
                     "-s", "1K", "-b", "1K", "--s3objprefix", "pre/"]
                    + s3_args + ["scanbkt"]) == 0
        # full-bucket scan
        treefile = tmp_path / "bucket.tree"
        rc = main(["--treescan", "s3://scanbkt",
                   "--treefile", str(treefile)] + s3_args)
        assert rc == 0
        store = PathStore()
        store.load_files_from_text(treefile.read_text())
        assert store.num_paths == 5
        assert all(e.total_len in (1024, 2048) for e in store.elems)
        # prefix-restricted scan sees only the prefixed objects
        pre_tree = tmp_path / "prefix.tree"
        rc = main(["--treescan", "s3://scanbkt/pre/",
                   "--treefile", str(pre_tree)] + s3_args)
        assert rc == 0
        store = PathStore()
        store.load_files_from_text(pre_tree.read_text())
        assert store.num_paths == 2
        assert all(e.path.startswith("pre/") for e in store.elems)
        # the treefile drives a custom-tree S3 read phase
        rc = main(["-r", "-t", "1", "-b", "2K", "--treefile",
                   str(treefile)] + s3_args + ["scanbkt"])
        assert rc == 0
        # a missing bucket is a clean error
        rc = main(["--treescan", "s3://nosuchbkt",
                   "--treefile", str(tmp_path / "x.tree")] + s3_args)
        assert rc == 1
        # gs:// scan while the flags configured the s3 backend: the
        # same ambiguity bench paths reject -> clean error
        rc = main(["--treescan", "gs://scanbkt",
                   "--treefile", str(tmp_path / "y.tree")] + s3_args)
        assert rc == 1
        # keys a treefile text line could corrupt (newline / edge
        # whitespace) survive via the base64 treefile encoding
        from elbencho_tpu.toolkits.s3_tk import S3Client
        client = S3Client(s3.endpoint, access_key="k", secret_key="s")
        client.put_object("scanbkt", "weird\nkey", b"abc")
        client.close()
        weird_tree = tmp_path / "weird.tree"
        rc = main(["--treescan", "s3://scanbkt",
                   "--treefile", str(weird_tree)] + s3_args)
        assert rc == 0
        store = PathStore()
        store.load_files_from_text(weird_tree.read_text())
        assert any(e.path == "weird\nkey" and e.total_len == 3
                   for e in store.elems)
    finally:
        s3.stop()
    # no endpoints configured at all: clean error, not a traceback
    rc = main(["--treescan", "s3://scanbkt",
               "--treefile", str(tmp_path / "z.tree"), "--nolive"])
    assert rc == 1

    gcs = MockGcsServer().start()
    try:
        gcs_args = ["--gcsendpoint", gcs.endpoint, "--gcsanon", "--nolive"]
        assert main(["-w", "-d", "-t", "1", "-n", "1", "-N", "2",
                     "-s", "4K", "-b", "4K"] + gcs_args
                    + ["gs://gscanbkt"]) == 0
        treefile = tmp_path / "gbucket.tree"
        rc = main(["--treescan", "gs://gscanbkt",
                   "--treefile", str(treefile)] + gcs_args)
        assert rc == 0
        store = PathStore()
        store.load_files_from_text(treefile.read_text())
        assert store.num_paths == 2
        assert all(e.total_len == 4096 for e in store.elems)
    finally:
        gcs.stop()


def test_scan_path_tool(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "f.dat").write_bytes(b"z" * 123)
    out = tmp_path / "out.tree"
    res = _tool("elbencho-tpu-scan-path", [str(src), str(out)])
    assert res.returncode == 0, res.stderr
    assert "f 123 f.dat" in out.read_text()


def test_summarize_json_tool(tmp_path):
    bench = tmp_path / "bench"
    bench.mkdir()
    jsonfile = tmp_path / "res.json"
    assert main(["-w", "-d", "-r", "-t", "1", "-n", "1", "-N", "2",
                 "-s", "8K", "-b", "8K", "--jsonfile", str(jsonfile),
                 "--label", "L1", "--nolive", str(bench)]) == 0
    res = _tool("elbencho-tpu-summarize-json",
                [str(jsonfile), "--group", "bench_label"])
    assert res.returncode == 0, res.stderr
    assert "WRITE" in res.stdout and "READ" in res.stdout
    assert "L1" in res.stdout
    res_csv = _tool("elbencho-tpu-summarize-json", [str(jsonfile), "--csv"])
    assert res_csv.returncode == 0
    assert res_csv.stdout.splitlines()[0].startswith("Phase,")


def test_chart_tool(tmp_path):
    bench = tmp_path / "bench"
    bench.mkdir()
    csvfile = tmp_path / "res.csv"
    assert main(["-w", "-d", "-r", "-t", "1", "-n", "1", "-N", "2",
                 "-s", "8K", "-b", "8K", "--csvfile", str(csvfile),
                 "--nolive", str(bench)]) == 0
    res = _tool("elbencho-tpu-chart", [str(csvfile)])
    assert res.returncode == 0, res.stderr
    assert "#" in res.stdout  # bars rendered


def _sweep_csv(tmp_path):
    """Two-point block-size sweep CSV for the chart tests."""
    csvfile = tmp_path / "res.csv"
    target = tmp_path / "f"
    for block in ("4K", "8K"):
        assert main(["-w", "-r", "-t", "1", "-s", "16K", "-b", block,
                     "--csvfile", str(csvfile), "--nolive",
                     str(target)]) == 0
    return csvfile


def test_chart_tool_listings_and_series(tmp_path):
    """-c/-o listings and explicit -x/-y/-Y series selection
    (reference surface: tools/elbencho-chart:42-58)."""
    csvfile = _sweep_csv(tmp_path)
    res = _tool("elbencho-tpu-chart", ["-c", str(csvfile)])
    assert res.returncode == 0
    assert "MiBPerSecLast" in res.stdout and "block_size" in res.stdout
    res = _tool("elbencho-tpu-chart", ["-o", str(csvfile)])
    assert res.returncode == 0
    assert res.stdout.split() == ["WRITE", "READ"]
    res = _tool("elbencho-tpu-chart",
                ["-x", "block_size", "-y", "MiBPerSecLast:READ",
                 str(csvfile)])
    assert res.returncode == 0
    assert "MiBPerSecLast [READ]" in res.stdout
    # unknown column / op are clean errors
    res = _tool("elbencho-tpu-chart", ["-y", "NoSuchCol", str(csvfile)])
    assert res.returncode != 0 and "not in csv" in res.stderr
    res = _tool("elbencho-tpu-chart",
                ["-y", "MiBPerSecLast:NOSUCHOP", str(csvfile)])
    assert res.returncode != 0 and "not in csv" in res.stderr


def test_chart_tool_dual_axis_line_png(tmp_path):
    """A sweep CSV charts as a dual-axis line image: MiB/s on the left
    axis, IOPS on the right (round-4 verdict item 8)."""
    csvfile = _sweep_csv(tmp_path)
    png = tmp_path / "chart.png"
    res = _tool("elbencho-tpu-chart",
                ["-x", "block_size", "-y", "MiBPerSecLast:READ",
                 "-Y", "IOPSLast:READ", "--imgfile", str(png),
                 "--title", "t", str(csvfile)])
    assert res.returncode == 0, res.stderr
    assert png.exists() and png.stat().st_size > 1000
    assert png.read_bytes()[:8] == b"\x89PNG\r\n\x1a\n"


def test_chart_tool_auto_selection(tmp_path):
    """No -x/-y: one MiBPerSecLast series per op, x = the varying config
    column (the sweep variable)."""
    csvfile = _sweep_csv(tmp_path)
    res = _tool("elbencho-tpu-chart", [str(csvfile)])
    assert res.returncode == 0, res.stderr
    assert "MiBPerSecLast [WRITE]" in res.stdout
    assert "MiBPerSecLast [READ]" in res.stdout
    assert "block_size" in res.stdout  # auto-picked sweep variable


def test_dgen_and_sweep_with_baseline(tmp_path):
    """dgen generates the named datasets; the sweep consumes them with
    --use-existing; --write-baseline/--baseline implement the committed
    regression flow (reference: contrib/storage_sweep/)."""
    root = tmp_path / "root"
    root.mkdir()
    # dry run prints commands, writes nothing
    res = _tool("elbencho-tpu-dgen",
                ["-r", "losf", "-n", "--dataset-size", "64K", str(root)])
    assert res.returncode == 0
    assert "sweep_1K" in res.stdout and not list(root.iterdir())
    # generate one dataset, then a single-point read-only sweep over it
    res = _tool("elbencho-tpu-dgen",
                ["-f", "1K", "--dataset-size", "16K", "-t", "1",
                 str(root)])
    assert res.returncode == 0, res.stderr
    assert (root / "sweep_1K" / "r0").is_dir()
    # missing datasets in --use-existing mode are a clean actionable error
    res = _tool("elbencho-tpu-sweep",
                [str(root), "--range", "losf", "--use-existing",
                 "--dataset-size", "16K", "-t", "1",
                 "--csv", str(tmp_path / "partial.csv")])
    assert res.returncode == 2
    assert "elbencho-tpu-dgen -f 2K" in res.stderr
    # full write+read sweep (tiny range via dataset-size) + baseline
    work = tmp_path / "work"
    work.mkdir()
    csvfile = tmp_path / "sweep.csv"
    base = tmp_path / "base.json"
    args = [str(work), "--range", "losf", "--dataset-size", "4K",
            "-t", "1", "--csv", str(csvfile)]
    res = _tool("elbencho-tpu-sweep",
                args + ["--write-baseline", str(base)])
    assert res.returncode == 0, res.stderr
    rec = json.loads(base.read_text())
    assert len(rec["points"]) == 11  # 1K..1M
    assert all("read_mibs" in p and "write_mibs" in p
               for p in rec["points"].values())
    # same run regresses clean against its own baseline (tolerance
    # widened: these 4K points are sub-ms and wildly noisy — the
    # inflated-baseline leg below proves detection)
    csv2 = tmp_path / "sweep2.csv"
    res = _tool("elbencho-tpu-sweep",
                [str(work), "--range", "losf", "--dataset-size", "4K",
                 "-t", "1", "--csv", str(csv2), "--tolerance", "99",
                 "--baseline", str(base)])
    assert res.returncode == 0, res.stderr
    assert "no regressions" in res.stdout
    # ...and an inflated baseline is caught
    for p in rec["points"].values():
        p["read_mibs"] *= 1000
    base.write_text(json.dumps(rec))
    csv3 = tmp_path / "sweep3.csv"
    res = _tool("elbencho-tpu-sweep",
                [str(work), "--range", "losf", "--dataset-size", "4K",
                 "-t", "1", "--csv", str(csv3),
                 "--baseline", str(base)])
    assert res.returncode == 3
    assert "REGRESSED" in res.stdout


def test_committed_losf_baseline_is_valid():
    """The committed baseline artifact (docs/sweeps/) parses and has the
    full losf range with nonzero read throughput per point."""
    path = os.path.join(REPO, "docs", "sweeps",
                        "losf_vm_2026-07-29.json")
    with open(path) as f:
        rec = json.load(f)
    assert rec["range"] == "losf" and len(rec["points"]) == 11
    assert all(p["read_mibs"] > 0 for p in rec["points"].values())


def test_blockdev_rand_wrapper_arg_validation():
    """Usage/arg errors are clean (reference: tools/blockdev-rand.sh).
    The happy path needs a real block device: tools/test-examples runs
    the wrapper twice against a loop device (rwmix + pure read) in its
    loopdev section when /dev access exists."""
    res = _tool("elbencho-tpu-blockdev-rand", [])
    assert res.returncode == 2 and "Usage" in res.stderr
    res = _tool("elbencho-tpu-blockdev-rand",
                ["nosuchdev", "4", "1", "100", "4K", "2"])
    assert res.returncode == 2 and "device not found" in res.stderr
    res = _tool("elbencho-tpu-blockdev-rand",
                ["loop0", "4", "1", "142", "4K", "2"])
    assert res.returncode == 2 and "READPERCENT" in res.stderr


def test_fuzz_sweep_quick_posix(tmp_path):
    """The checked-in fuzz harness (make check gate): a seeded quick
    posix sweep runs clean — no uncaught tracebacks."""
    res = _tool("fuzz-sweep", ["--suite", "posix", "--combos", "5",
                               "--seed", "7"])
    assert res.returncode == 0, res.stderr + res.stdout
    assert "clean" in res.stdout


def test_flock_modes(tmp_path):
    target = tmp_path / "f"
    for mode in ("range", "full"):
        rc = main(["-w", "-r", "-t", "2", "-s", "128K", "-b", "32K",
                   "--flock", mode, "--nolive", str(target)])
        assert rc == 0


def test_statinline(tmp_path):
    rc = main(["-w", "-d", "-r", "--statinline", "-t", "1", "-n", "1",
               "-N", "2", "-s", "8K", "-b", "8K", "--nolive",
               str(tmp_path)])
    assert rc == 0


def test_cleanup_mpu_tool(tmp_path):
    """elbencho-tpu-cleanup-mpu lists and aborts leftover multipart
    uploads (reference: tools/s3-cleanup-mpu.py)."""
    from elbencho_tpu.testing.mock_s3 import MockS3Server
    from elbencho_tpu.toolkits.s3_tk import S3Client
    server = MockS3Server().start()
    try:
        client = S3Client(server.endpoint)
        client.create_bucket("leftovers")
        up1 = client.create_multipart_upload("leftovers", "obj1")
        up2 = client.create_multipart_upload("leftovers", "obj2")
        uploads, _, _ = client.list_multipart_uploads("leftovers")
        assert sorted(k for k, _ in uploads) == ["obj1", "obj2"]
        assert {u for _, u in uploads} == {up1, up2}
        # dry run aborts nothing
        res = _tool("elbencho-tpu-cleanup-mpu",
                    ["--endpoint", server.endpoint, "--bucket", "leftovers",
                     "--dry-run"])
        assert res.returncode == 0, res.stderr
        assert "would abort" in res.stdout
        assert len(client.list_multipart_uploads("leftovers")[0]) == 2
        # real run aborts both
        res = _tool("elbencho-tpu-cleanup-mpu",
                    ["--endpoint", server.endpoint, "--bucket", "leftovers"])
        assert res.returncode == 0, res.stderr
        assert "2 upload(s) aborted" in res.stdout
        assert client.list_multipart_uploads("leftovers")[0] == []
    finally:
        server.stop()


def test_netbench_requires_hosts_config_error(capsys):
    rc = main(["--netbench", "--nolive"])
    assert rc == 1
    assert "netbench requires distributed" in capsys.readouterr().err


def test_treescan_requires_treefile(tmp_path, capsys):
    rc = main(["--treescan", str(tmp_path), "--nolive"])
    assert rc == 1


def test_summarize_json_dispatch_split_columns(tmp_path):
    """Phase records report the per-op dispatch-vs-DMA split as columns
    (the --tpubudget observable, chartable per sweep point)."""
    jsonfile = tmp_path / "res.json"
    assert main(["--tpubench", "-s", "512K", "-b", "128K", "--iodepth",
                 "4", "--jsonfile", str(jsonfile), "--nolive"]) == 0
    res = _tool("elbencho-tpu-summarize-json", [str(jsonfile), "--csv"])
    assert res.returncode == 0, res.stderr
    header = res.stdout.splitlines()[0].split(",")
    data = res.stdout.splitlines()[1].split(",")
    assert "HBMdisp us/op" in header and "HBMdma us/op" in header
    assert float(data[header.index("HBMdisp us/op")]) > 0


def test_chart_tool_rejects_phase_records_cleanly(tmp_path):
    """Ordinary --jsonfile phase records are not chartable — the tool
    must say so instead of misrouting them into the bench-capture path
    and complaining about a missing A/B."""
    jsonfile = tmp_path / "res.json"
    assert main(["--tpubench", "-s", "256K", "-b", "128K", "--jsonfile",
                 str(jsonfile), "--nolive"]) == 0
    res = _tool("elbencho-tpu-chart", [str(jsonfile)])
    assert res.returncode != 0
    assert "phase-record output" in res.stderr


@pytest.mark.parametrize("tool, expects", [
    ("elbencho-tpu-chart", "results CSV (--csvfile)"),
    ("elbencho-tpu-summarize-json", "--jsonfile phase-record output"),
])
def test_tools_refuse_non_phase_json_lines(tmp_path, tool, expects):
    """JSON lines that are not phase records are refused with a one-line
    error naming the input the tool reads — no traceback, no table."""
    path = tmp_path / "metrics.json"
    path.write_text(json.dumps({"metric": "seq read", "value": 900.0})
                    + "\n" + json.dumps({"metric": "x", "value": None})
                    + "\n")
    res = _tool(tool, [str(path)])
    assert res.returncode != 0
    assert expects in res.stderr
    assert "Traceback" not in res.stderr
    assert len(res.stderr.strip().splitlines()) == 1, res.stderr
    assert res.stdout == ""


def test_summarize_json_degraded_banner(tmp_path):
    """A --svctolerant degraded record must never tabulate silently next
    to clean ones: stderr banner + a Degr column (docs/fault-tolerance.md)."""
    jsonfile = tmp_path / "res.json"
    clean = {"Phase": "WRITE", "EntriesLast": 8, "NumHostsDegraded": 0,
             "DegradedHosts": []}
    degraded = {"Phase": "READ", "EntriesLast": 4, "NumHostsDegraded": 1,
                "DegradedHosts": ["10.0.0.2:1611"]}
    jsonfile.write_text(json.dumps(clean) + "\n" + json.dumps(degraded)
                        + "\n")
    res = _tool("elbencho-tpu-summarize-json", [str(jsonfile)])
    assert res.returncode == 0, res.stderr
    assert "DEGRADED" in res.stderr and "10.0.0.2:1611" in res.stderr
    header, _sep, clean_row, degr_row = res.stdout.splitlines()[:4]
    assert "Degr" in header
    assert "DEGRADED" in degr_row and "DEGRADED" not in clean_row
    # an all-clean file keeps the old schema: no banner, no Degr column
    jsonfile.write_text(json.dumps(clean) + "\n")
    res = _tool("elbencho-tpu-summarize-json", [str(jsonfile)])
    assert res.returncode == 0 and "DEGRADED" not in res.stderr
    assert "Degr" not in res.stdout.splitlines()[0]


# ---------------------------------------------------------------------------
# toolkits/signals.py: fault-trace registration
# ---------------------------------------------------------------------------


@pytest.fixture
def _fresh_fault_handlers(monkeypatch):
    """Reset signals.py module state and restore faulthandler afterwards."""
    import faulthandler

    from elbencho_tpu.toolkits import signals
    monkeypatch.setattr(signals, "_trace_file", None)
    yield signals
    faulthandler.disable()
    if signals._trace_file is not None:
        signals._trace_file.close()
        signals._trace_file = None


def test_fault_trace_registration_returns_per_user_path(
        tmp_path, monkeypatch, _fresh_fault_handlers):
    import faulthandler
    import getpass
    signals = _fresh_fault_handlers
    monkeypatch.setattr(signals, "FAULT_TRACE_PATH_TEMPLATE",
                        str(tmp_path / "trace_{user}.txt"))
    path = signals.register_fault_handlers()
    assert path == str(tmp_path / f"trace_{getpass.getuser()}.txt")
    assert os.path.exists(path)
    assert faulthandler.is_enabled()
    # idempotent: a second call keeps the existing sink, same path
    assert signals.register_fault_handlers() == path


def test_fault_trace_falls_back_to_stderr_when_unwritable(
        tmp_path, monkeypatch, _fresh_fault_handlers):
    """An unwritable trace path must not kill startup: faulthandler still
    arms (stderr sink) and the intended path is still returned so the
    startup log points somewhere."""
    import faulthandler
    signals = _fresh_fault_handlers
    monkeypatch.setattr(signals, "FAULT_TRACE_PATH_TEMPLATE",
                        str(tmp_path / "no" / "such" / "dir" / "{user}.txt"))
    path = signals.register_fault_handlers()
    assert path.startswith(str(tmp_path))
    assert not os.path.exists(path)
    assert faulthandler.is_enabled()  # stderr fallback
    assert signals._trace_file is None  # no half-open sink left behind
