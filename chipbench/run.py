"""Run one cell of the benchmark on the TPU this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Exits non-zero, with no result, where JAX finds no TPU, a device kind not
in ``chipbench/peaks.json``, or fewer chips than the cell asks for.
Otherwise the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, a ``window`` of diagnostics (requests due and finished,
the queue's trend, preemptions, gap quantiles), and last the ``checks``
that decided ``correct``, each number beside its limit.  The same checks are the last lines of standard
error.

Two options are for the benchmark's own measurements, never for a timed
run: ``--rate R`` offers R requests per second in place of the mix's rate
(the knee sweep), and ``--control 1`` puts the check's control in the
program's place, so the run has to come out not correct.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, rate=args.rate,
                           control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
