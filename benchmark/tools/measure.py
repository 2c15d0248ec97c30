#!/usr/bin/env python3
"""Run one cell several times in one call and print what the bounds are
set from: each metric's values, median and spread (the distance between
the first and third quartile of statistics.quantiles(values, n=4) as a
share of the median).

    python3 benchmark/tools/measure.py --workload <cell> --seeds 1,2,3 \
        [--seconds S] [--trace 0|1] [--out chiprun_out/<name>.jsonl] [-- extra run.py args]

Every run is a new process of benchmark/run.py, as the driver's are; the
result lines are appended to --out.  The first run of a cell in a
checkout compiles: its set-up is printed apart.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default="")
    ap.add_argument("extra", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    args.extra = [a for a in args.extra if a != "--"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        seconds = args.seconds or str(json.load(f)["run_seconds"])
    lines = []
    for seed in args.seeds.split(","):
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", seconds, "--trace", args.trace, *args.extra],
            cwd=REPO, capture_output=True, text=True)
        took = time.monotonic() - t0
        last = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
        if r.returncode != 0 or not last.startswith("{"):
            print(f"seed {seed}: exit {r.returncode} after {took:.0f} s\n"
                  + r.stdout[-1500:] + r.stderr[-2500:], flush=True)
            continue
        line = json.loads(last)
        line["run_took_s"] = took
        lines.append(line)
        shown = {k: round(v["value"], 4) for k, v in line["metrics"].items()
                 if v["value"] is not None}
        info = [ln for ln in r.stdout.splitlines()
                if ln.startswith(("set-up:", "window:", "check:", "pin +",
                                  "loaded:", "volume "))]
        print(f"seed {seed}: correct={line['correct']} attempted="
              f"{line['attempted']} failed={line['failed']} run {took:.0f} s "
              f"{shown}\n    " + "\n    ".join(info), flush=True)
        if not line["correct"]:
            print("    compared: " + json.dumps(line["compared"]), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a", encoding="utf-8") as f:
                f.write(json.dumps(line) + "\n")
    names = sorted({n for ln in lines for n in ln["metrics"]})
    for name in names:
        values = [ln["metrics"][name]["value"] for ln in lines
                  if name in ln["metrics"]
                  and ln["metrics"][name]["value"] is not None]
        if name == "setup_s" and len(values) > 1:
            print(f"setup_s first run {values[0]:.1f}")
            values = values[1:]
        if len(values) >= 2:
            print(f"{name}: n={len(values)} median "
                  f"{statistics.median(values):.4f} spread "
                  f"{100 * spread(values):.2f} % min {min(values):.4f} "
                  f"max {max(values):.4f}")
        elif values:
            print(f"{name}: {values[0]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
