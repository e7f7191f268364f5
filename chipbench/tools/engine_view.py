"""Run one cell traced, as ``run.py --trace 1`` does, and print beside its
result what the result line does not carry yet: the engine's counters,
the requests' queue waits, the host tier's bytes per second, the parts
of set-up, and the idle gaps and host time of each part of
``Engine.step``.

    python chipbench/tools/engine_view.py --workload <cell> --seed <n> \
        --seconds <s>

The last line of standard output is the run's result line with one more
key, ``engine``:

* ``counters``: window deltas of ``Engine.phase_stats``;
  ``decode_occupancy`` is ``decode_rows / (decode_calls x nslots)`` and
  ``prefill_grid_fill`` is ``prefill_real_tokens / prefill_grid_tokens``;
* ``queue_wait_ms``: quantiles of ``wall_admitted - wall_submitted`` over
  the requests the window admitted (host clock);
* ``host_bytes_per_s``: KV bytes demoted, promoted, swapped out and
  swapped in per second of the window, from the engine's KV-token counts
  times the bytes of one token's K and V in every layer;
* ``setup_s``: set-up on the host clock, split into imports (process
  start to the run), weights, engine build, warm-up, priming and the
  rest; the parts sum to ``total`` (process start to the window, within
  microseconds of the harness's ``setup_s``);
* ``idle_gaps``: device idle seconds of the traced tail by the innermost
  span open, the harness's or the engine's (``repro.engine.*``), and the
  share of the idle time inside step spans that an engine span labels;
* ``step_self_s``: seconds of the traced tail in which each engine span
  is the innermost one open;
* ``decode_step_wall_ms``: mean host wall of the steps that ran only a
  decode, before the traced tail and inside it: what the open profiler
  costs a step.

Like ``run.py`` it needs a TPU.  It patches the harness's set-up
functions to time them; the window is driven unchanged.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

KV_KEYS = ("kv_demoted", "kv_promoted", "kv_out", "kv_in")


def _timed(parts: dict, name: str, fn):
    def wrapper(*args, **kwargs):
        t = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            parts[name] = parts.get(name, 0.0) + time.perf_counter() - t
    return wrapper


def engine_counters(eng) -> dict:
    out = dict(eng.phase_stats)
    out.update({k: eng.swap_stats[k] for k in KV_KEYS})
    return out


def kv_bytes_per_token(cfg) -> int:
    import jax.numpy as jnp
    return (2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def _mean_ms(steps) -> dict:
    wall = [s.t1 - s.t0 for s in steps]
    return {"mean": 1e3 * sum(wall) / len(wall) if wall else None,
            "n": len(wall)}


def run_view(cell, seed: int, seconds: float, *, t_start: float,
             cpu_kind=None) -> dict:
    import jax
    import numpy as np
    from chipbench import engine_trace, harness
    parts: dict = {}
    seen: dict = {}
    orig = {n: getattr(harness, n) for n in
            ("drive", "load_reference", "build_engine", "warm", "prime")}

    def hook(eng):
        seen["t_setup"] = time.perf_counter()
        seen["eng"] = eng
        seen["base"] = engine_counters(eng)

    def drive(eng, *args, **kwargs):
        seen["w"] = orig["drive"](eng, *args, **kwargs)
        seen["end"] = engine_counters(eng)
        return seen["w"]

    def load_reference(name, root):
        ref = orig["load_reference"](name, root)
        make = ref.make_params
        ref.make_params = _timed(
            parts, "weights",
            lambda *a, **k: jax.block_until_ready(make(*a, **k)))
        return ref

    patch = {"drive": drive, "load_reference": load_reference,
             "build_engine": _timed(parts, "engine", orig["build_engine"]),
             "warm": _timed(parts, "warm", orig["warm"]),
             "prime": _timed(parts, "prime", orig["prime"])}
    t_run = time.perf_counter()
    for name, fn in patch.items():
        setattr(harness, name, fn)
    try:
        out = harness.run_cell(cell, seed, seconds, True, t_start=t_start,
                               cpu_kind=cpu_kind, hook=hook)
    finally:
        for name, fn in orig.items():
            setattr(harness, name, fn)

    w, eng = seen["w"], seen["eng"]
    d = {k: seen["end"][k] - v for k, v in seen["base"].items()}
    span = w.t_end - w.t0
    calls, grid = d["decode_calls"], d["prefill_grid_tokens"]
    counters = dict(d)
    counters["decode_occupancy"] = (
        d["decode_rows"] / (calls * eng.ecfg.nslots) if calls else None)
    counters["prefill_grid_fill"] = (
        d["prefill_real_tokens"] / grid if grid else None)
    waits = [1e3 * (r.wall_admitted - r.wall_submitted)
             for r in w.reqs.values() if r.wall_admitted is not None]
    per_tok = kv_bytes_per_token(harness.model_config(cell))
    setup = {"imports": t_run - t_start, **parts}
    total = seen["t_setup"] - t_start
    setup["other"] = total - sum(setup.values())
    setup["total"] = total
    view = {
        "counters": counters,
        "queue_wait_ms": ({f"p{q}": float(np.percentile(waits, q))
                           for q in (50, 90, 99)} | {"n": len(waits)}
                          if waits else {}),
        "host_bytes_per_s": {k: d[k] * per_tok / span for k in KV_KEYS},
        "setup_s": setup,
    }
    lo, hi = w.trace_t
    decode = [s for s in w.steps if s.decode_ctxs and not s.prefill_calls]
    view["decode_step_wall_ms"] = {
        "untraced": _mean_ms([s for s in decode if s.t1 < lo]),
        "traced": _mean_ms([s for s in decode
                            if s.t0 >= lo and s.t1 <= hi])}
    loaded = engine_trace.find(out["device"]["window_s"])
    if loaded is not None:
        red = engine_trace.reduce(loaded)
        idle = red.idle_by_label
        ours = sum(v for k, v in idle.items() if k.startswith("repro."))
        harness_step = sum(v for k, v in idle.items()
                           if k == "step" or k.startswith("step."))
        view["idle_gaps"] = {
            "by_label": sorted(([k, v] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
            "in_steps_s": ours + harness_step,
            "engine_labelled_share": (ours / (ours + harness_step)
                                      if ours + harness_step else None)}
        view["step_self_s"] = sorted(
            ([k, v] for k, v in engine_trace.self_seconds(
                loaded.program, *loaded.window).items()),
            key=lambda kv: -kv[1])
    out["engine"] = view
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    out = run_view(cell, args.seed, args.seconds, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
