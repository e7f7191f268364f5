"""Run ``tools/engine_view.py`` on a cell cut to the CPU size of
``cpu_cell.py``, for the tests.

    JAX_PLATFORMS=cpu python chipbench/tests/cpu_engine_view.py <cell> \
        [--seconds S] [--seed N]

The cut cell traces under its own name, ``<cell>.cpu-view``, apart from
the traces of ``cpu_cell.py``'s runs.  The look for a TPU is skipped;
nothing printed here is a device number.  The last line of standard
output is the run's result with the tool's ``engine`` view.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=2 ** 33 + 5)
    args = ap.parse_args(argv)
    import cpu_cell
    from chipbench.tools import engine_view
    cell = cpu_cell.small_cell(args.cell, "open")
    cell.name += ".cpu-view"
    out = engine_view.run_view(cell, args.seed, args.seconds,
                               t_start=T_START, cpu_kind="TPU v5 lite")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
