"""Run one cell several times, each run its own process, and report each
metric's spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python chipbench/tools/sets.py --workload <cell> --seconds S \
        --seeds 1,2,3,4,5,6 [--trace 0|1] [--rate R] [--control 1] \
        [--out file.jsonl]

With ``--out`` each result line is appended there and each run's
standard error kept beside it.  ``--rate`` and ``--control`` are passed
to ``run.py``: the knee sweep
is one call of this per rate, and the control's readings one call with
``--control 1``.

This process never touches JAX, so each run has the chip to itself.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--rate", default="")
    ap.add_argument("--control", default="0")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    runs = []
    for seed in args.seeds.split(","):
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", args.seconds, "--trace", args.trace,
             "--control", args.control,
             *(["--rate", args.rate] if args.rate else [])],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t
        lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
        if p.returncode != 0 or not lines:
            print(json.dumps({"seed": seed, "rc": p.returncode,
                              "stderr": p.stderr[-1500:]}), flush=True)
            continue
        res = json.loads(lines[-1])
        res.update(seed=seed, wall_s=wall)
        runs.append(res)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")
            with open(f"{args.out}.{seed}.err", "w") as f:
                f.write(p.stderr)
        print(json.dumps({"seed": seed, "wall_s": round(wall, 1),
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()},
                          "checks": {k: v["value"]
                                     for k, v in res["checks"].items()},
                          "window": res.get("window"),
                          "device": res["device"]}), flush=True)
    names = sorted({k for r in runs for k in r["metrics"]})
    for k in names:
        vals = [r["metrics"][k]["value"] for r in runs if k in r["metrics"]]
        print(f"{k}: median {statistics.median(vals)!r} spread "
              f"{spread(vals)!r} over {len(vals)} runs "
              f"(first run {vals[0]!r})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
