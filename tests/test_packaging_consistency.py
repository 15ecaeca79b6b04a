"""Packaging consistency checks that run without rpmbuild/docker
(round-2 verdict item 9: the rpm spec had only ever been cross-checked
by hand — this encodes the spec-vs-tree contract as tests, so drift
between the spec, the Makefile version plumbing, and the repo layout is
caught in CI even though this image cannot execute rpmbuild).
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(REPO, "packaging", "elbencho-tpu.spec")


def _spec_text() -> str:
    with open(SPEC) as f:
        return f.read()


def _pyproject_version() -> str:
    with open(os.path.join(REPO, "pyproject.toml")) as f:
        m = re.search(r'^version = "(.*)"$', f.read(), re.M)
    assert m, "pyproject.toml has no version"
    return m.group(1)


def test_spec_fallback_version_matches_pyproject():
    """The %{!?pkg_version:...} fallback must track pyproject so a bare
    rpmbuild (without make rpm's --define) still stamps the right
    version."""
    m = re.search(r"%\{!\?pkg_version:([^}]+)\}", _spec_text())
    assert m, "spec has no pkg_version fallback"
    assert m.group(1) == _pyproject_version()


def test_make_rpm_version_extraction_works():
    """The sed one-liner in the Makefile's rpm target must actually
    extract the version from pyproject.toml (quoting drift here would
    produce an empty --define)."""
    with open(os.path.join(REPO, "Makefile")) as f:
        make_text = f.read()
    m = re.search(r"sed -n '([^']+)'", make_text)
    assert m, "rpm target's sed expression not found"
    sed_expr = m.group(1).replace("$$", "$")  # make escaping
    out = subprocess.run(
        ["sed", "-n", sed_expr, os.path.join(REPO, "pyproject.toml")],
        capture_output=True, text=True, check=True).stdout.strip()
    assert out == _pyproject_version(), (sed_expr, out)


def test_spec_install_sources_exist():
    """Every %{_sourcedir}-relative path the %install section copies must
    exist in the tree (make rpm passes the repo root as _sourcedir).
    libioengine.so is produced by %build from csrc, so the build recipe
    is checked instead of the artifact."""
    text = _spec_text()
    refs = set(re.findall(r"%\{_sourcedir\}/([\w./-]+)", text))
    assert refs, "no _sourcedir references found in spec"
    for ref in refs:
        if ref.endswith("libioengine.so"):
            with open(os.path.join(REPO, "csrc", "Makefile")) as f:
                assert "libioengine.so" in f.read()
            continue
        if ref.endswith("$tool"):  # shell-loop variable, expanded below
            continue
        assert os.path.exists(os.path.join(REPO, ref)), (
            f"spec %install references missing source: {ref}")


def test_spec_tool_list_matches_tools_dir():
    """The for-loop of installed tools must name real executable scripts
    (and stay in sync with the user-facing tools in tools/)."""
    m = re.search(r"for tool in ([^;]+);", _spec_text())
    assert m, "spec tool install loop not found"
    tools = m.group(1).replace("\\", " ").split()
    assert len(tools) >= 5
    for tool in tools:
        path = os.path.join(REPO, "tools", tool)
        assert os.path.isfile(path), f"spec installs missing tool {tool}"
        assert os.access(path, os.X_OK), f"tool {tool} not executable"
    # every user-facing elbencho-tpu-* tool ships; internal/dev tools
    # (generate-usage-docs, gen-flags-parity, test-examples) do not
    shipped = set(tools)
    user_tools = {t for t in os.listdir(os.path.join(REPO, "tools"))
                  if t.startswith("elbencho-tpu-")}
    assert shipped == user_tools, (shipped, user_tools)


def test_spec_files_section_covers_installed_paths():
    """%files must claim exactly what %install lays down (unclaimed
    files fail rpmbuild; claiming nonexistent files fails it too)."""
    text = _spec_text()
    files_section = text.split("%files", 1)[1]
    for needed in ("%{python3_sitelib}/elbencho_tpu",
                   "%{_bindir}/elbencho-tpu",
                   "%{_bindir}/elbencho-tpu-*",
                   "%{_datadir}/bash-completion/completions/elbencho-tpu"):
        assert needed in files_section, f"%files misses {needed}"


def test_deb_and_docker_reference_existing_paths():
    """Same path-consistency check for the deb script and Dockerfile
    (rpmbuild/docker are absent in this image; the references must at
    least point at real tree paths)."""
    with open(os.path.join(REPO, "packaging", "make-deb.sh")) as f:
        deb = f.read()
    for rel in re.findall(r"\"\$REPO\"/([\w./-]+)", deb):
        assert os.path.exists(os.path.join(REPO, rel)), (
            f"make-deb.sh references missing path {rel}")
    with open(os.path.join(REPO, "Dockerfile")) as f:
        docker = f.read()
    for m in re.finditer(r"^COPY\s+([^\s]+)\s", docker, re.M):
        src = m.group(1)
        if src.startswith("--"):  # COPY --from=... stage copies
            continue
        assert os.path.exists(os.path.join(REPO, src)), (
            f"Dockerfile COPY references missing path {src}")


def _documented_paths() -> "list[str]":
    """Repo-relative scripts and tools named by a command line in a
    README.md code block or a Makefile recipe."""
    with open(os.path.join(REPO, "README.md")) as f:
        blocks = re.findall(r"^```[^\n]*\n(.*?)^```", f.read(), re.M | re.S)
    with open(os.path.join(REPO, "Makefile")) as f:
        recipes = re.findall(r"^\t(.*)$", f.read(), re.M)
    paths = set()
    for line in "\n".join(blocks).splitlines() + recipes:
        for tok in line.split("#", 1)[0].split():
            tok = tok.rpartition("=")[2]  # --ignore=tests/x.py
            if tok.startswith("tools/") or tok.endswith((".py", ".sh")):
                paths.add(tok)
    return sorted(paths)


@pytest.mark.parametrize("path", _documented_paths())
def test_documented_commands_name_existing_paths(path):
    """A documented command must not point at a script that is gone."""
    assert os.path.isfile(os.path.join(REPO, path)), (
        f"README.md or the Makefile runs missing path {path}")


def test_benchmark_entry_point_help():
    """The cell command README.md documents starts and takes the options
    it is documented with."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--help"], capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    for opt in ("--workload", "--seed", "--seconds"):
        assert opt in out.stdout
