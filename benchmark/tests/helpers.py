"""A copy of the benchmark at CPU size, and a way to run it there."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

#: file, block and warm-up sizes a CPU run can hold
TINY = {
    "seqread-16m": (256 << 20, ["-s", str(48 << 20)]),
    "randread-4k": (64 << 20, ["--randamount", str(1 << 20)]),
    "h2d-ceiling": (256 << 20, ["-s", str(48 << 20)]),
}


def tiny_checkout(dest: str) -> str:
    """BENCHMARK.json and benchmark/ copied to dest, datasets shrunk."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(BENCH, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, (size, warmup) in TINY.items():
        path = os.path.join(dest, "benchmark", "configs", f"{name}.json")
        with open(path) as f:
            config = json.load(f)
        config["file_size"], config["warmup_flags"] = size, warmup
        with open(path, "w") as f:
            json.dump(config, f)
    return dest


def run_cell(checkout: str, cell: str, *extra: str, seed: int = 2 ** 31 + 7,
             seconds: int = 2, trace: int = 0, cpu: bool = True,
             program: bool = True, devices: int = 4):
    """(rc, parsed last stdout line or None, stderr) of one run."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    if program:
        env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(checkout, ".jax_cache")
    cmd = [sys.executable, "benchmark/run.py", "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), *(["--allow-cpu"] if cpu else []), *extra]
    proc = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                          text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return proc.returncode, result, proc.stderr
