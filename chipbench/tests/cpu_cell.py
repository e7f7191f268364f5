"""Run a cell end to end on the CPU at a reduced size, for the tests.

    JAX_PLATFORMS=cpu python chipbench/tests/cpu_cell.py <cell> \
        [--seconds S] [--trace 0|1] [--seed N] [--fault token|frozen|half] \
        [--loop open|backlog] [--control]

The cell's own files are read, then cut: 2 layers of width 512, a
1024-token vocabulary, 4 slots of 512 tokens, chunk 64, traffic of short
prompts at 4 requests/s, and the check's limit set for this size.  The look for a TPU is skipped and the
cost model and rooflines are priced as a TPU v5e; nothing printed here is
a device number.  The last line of standard output is the run's result.

``--fault`` breaks the timed path under the harness:

* ``token``: each decode step's sampled ids come out altered by one.
* ``frozen``: each step, prefill and decode, returns the pools it was
  given, so no K or V is ever written.
* ``half``: the decode step leaves out the odd slots: their K and V are not
  written and their ids come out 0.

``--control`` puts the check's control (the reference in float8) in the
program's place, as ``run.py --control 1`` does.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SMALL_MODEL = dict(num_layers=2, d_model=512, num_heads=4, num_kv_heads=2,
                   head_dim=128, d_ff=1024, vocab_size=1024,
                   vocab_pad_multiple=256)
SMALL_ENGINE = dict(nslots=4, cache_len=512, chunk=64, min_bucket=16)
# The check's limit at this size.  A widest logit gap grows with the
# width, so the cell's own limit does not carry over: here the program
# reads 0.0059 to 0.0185 and the control 0.200 to 0.321 over five seeds,
# and the planted faults 0.88 and more.
SMALL_LOGIT_GAP_MAX = 0.08


def small_cell(name: str, loop: str):
    from chipbench import harness
    cell = harness.load_cell(name)
    cell.config["model"].update(SMALL_MODEL)
    cell.config["correct"]["logit_gap_max"] = SMALL_LOGIT_GAP_MAX
    es = cell.config["engine"]
    # the same pool-to-demand ratio as the cell's own deployment
    ratio = es["pool_tokens"] / (es["nslots"] * es["cache_len"])
    es.update(SMALL_ENGINE)
    es["pool_tokens"] = int(ratio * es["nslots"] * es["cache_len"]) // 16 * 16
    mix = cell.mix
    pr = mix["prompt"]
    if pr["kind"] == "templates":
        pr.update(count=6, len={"uniform": [100, 200]},
                  suffix_len={"uniform": [8, 32]})
        mix["output_len"] = {"uniform": [8, 24]}
    else:
        pr["len"] = {"lognormal": {"mean": 150, "sigma": 1.0, "min": 1,
                                   "max": 300}}
        mix["output_len"] = {"lognormal": {"mean": 24, "sigma": 1.0,
                                           "min": 1, "max": 64}}
    mix["rate_rps"] = 4.0
    mix["loop"] = loop
    return cell


def break_engine(fault: str):
    import jax.numpy as jnp

    def hook(eng):
        decode, prefill = eng._paged_decode, eng._paged_prefill
        vocab = eng.cfg.vocab_size

        def frozen_prefill(params, kp, vp, grid, bt):
            return prefill(params, kp, vp, grid, bt)[0], kp, vp

        def broken(params, kp, vp, toks, ctx, bt, active):
            if fault == "half":
                odd = jnp.arange(active.shape[0]) % 2 == 1
                out, nk, nv = decode(params, kp, vp, toks, ctx, bt,
                                     active & ~odd)
                return jnp.where(odd, 0, out), nk, nv
            out, nk, nv = decode(params, kp, vp, toks, ctx, bt, active)
            if fault == "token":
                return (out + 1) % vocab, nk, nv
            return out, kp, vp                  # "frozen"
        eng._paged_decode = broken
        if fault == "frozen":
            eng._paged_prefill = frozen_prefill
    return hook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2 ** 33 + 1)
    ap.add_argument("--fault", choices=("token", "frozen", "half"))
    ap.add_argument("--loop", choices=("open", "backlog"), default="open")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = small_cell(args.cell, args.loop)
    hook = break_engine(args.fault) if args.fault else None
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, cpu_kind="TPU v5 lite",
                           hook=hook, control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
