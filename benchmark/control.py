"""Run the control and the faults of faults.py on the chip, at each cell's
own size, and print the numbers compared in each run.

    python3 benchmark/control.py --cells <cell>,... --seeds <n>,... \
        [--faults torn,...] [--seconds 5] [--out DIR]

Each run is its own ``run.py --fault`` process, so one process at a time
holds the chip. The benchmark's own runs never plant a fault. A fault is
caught where the run reads ``correct`` false through ``bad_words``: the
words landed, not only an error of the program; the exit code is 0 only
where every run was caught.
"""

import argparse
import json
import os
import subprocess
import sys

import faults

BENCH = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="torn")
    ap.add_argument("--seconds", type=int, default=5)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    caught = True
    for cell in args.cells.split(","):
        for fault in args.faults.split(","):
            if fault not in faults.FAULTS:
                raise SystemExit(f"no fault {fault!r}")
            for seed in args.seeds.split(","):
                proc = subprocess.run(
                    [sys.executable, os.path.join(BENCH, "run.py"),
                     "--workload", cell, "--seed", seed, "--seconds",
                     str(args.seconds), "--fault", fault],
                    capture_output=True, text=True, timeout=600)
                if args.out:
                    base = os.path.join(args.out, f"{cell}.{fault}.{seed}")
                    with open(base + ".err", "w") as f:
                        f.write(proc.stderr)
                lines = proc.stdout.strip().splitlines()
                result = json.loads(lines[-1]) if lines else {}
                checks = result.get("checks", {})
                caught &= result.get("correct") is False \
                    and checks.get("bad_words", {}).get("value", 0) > 0
                print(json.dumps({"cell": cell, "fault": fault, "seed": seed,
                                  "rc": proc.returncode,
                                  "correct": result.get("correct"),
                                  "checks": {k: c["value"]
                                             for k, c in checks.items()}}),
                      flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
