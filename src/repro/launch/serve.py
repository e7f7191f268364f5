"""Serving launcher: ``python -m repro.launch.serve --arch tinyllama-1.1b``.

Runs the continuous-batching engine on a workload with the selected
scheduling / cache-replacement policy (the paper's deployment path) and
prints the §5.1 metrics.  Reduced configs by default, ``--full`` for
published widths.  The cost model takes the peak figures of the device
JAX finds (``hardware_for_device``), so this runs on a chip whose peaks
are recorded; any other device, the CPU included, is an error.
"""
from __future__ import annotations

import argparse
import logging

import jax
import numpy as np

from repro.configs import get_config
from repro.core import (TheoreticalCostModel, hardware_for_device,
                        make_scheduler)
from repro.data import azureconv_like, fixed_grid, hetero_mix, longform_like
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M
from repro.serving import Engine, EngineConfig

log = logging.getLogger("repro.serve")

WORKLOADS = {
    "fixed": lambda vocab: fixed_grid(12, 24, 8, vocab=vocab),
    "hetero": lambda vocab: hetero_mix(["SISO", "SILO"], 12, vocab=vocab),
    "azureconv": lambda vocab: azureconv_like(12, vocab=vocab),
    "longform": lambda vocab: longform_like(12, vocab=vocab),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--scheduler", default="vllm",
                    choices=["vllm", "vllm_hy", "sarathi", "sarathi_cs",
                             "orca", "vllm_pf", "sarathi_pf"])
    ap.add_argument("--replacement", default="srf",
                    choices=["nrf", "srf", "lrf", "pf"])
    ap.add_argument("--histogram", action="store_true",
                    help="SRF+Hist admission gating")
    ap.add_argument("--workload", default="fixed", choices=sorted(WORKLOADS))
    ap.add_argument("--M", type=int, default=128,
                    help="KV cache size in tokens")
    ap.add_argument("--nslots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    setup_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = WORKLOADS[args.workload](cfg.vocab_size)
    # crop requests to the engine's context budget
    for r in reqs:
        r.input_len = min(r.input_len, args.cache_len // 2)
        r.output_len = min(r.output_len, args.cache_len // 2)
        r.prompt = r.prompt[:r.input_len]

    sched = make_scheduler(args.scheduler, args.M, S=args.cache_len,
                           replacement=args.replacement,
                           use_histogram=args.histogram)
    cm = TheoreticalCostModel(
        cfg, hardware_for_device(jax.devices()[0].device_kind))
    eng = Engine(cfg, params, sched,
                 EngineConfig(nslots=args.nslots, cache_len=args.cache_len,
                              chunk=args.chunk), cost_model=cm)
    res = eng.run(reqs)
    s = res.metrics.summary()
    log.info("scheduler=%s replacement=%s workload=%s",
             args.scheduler, args.replacement, args.workload)
    for k, v in s.items():
        log.info("  %-16s %.6g", k, v)
    log.info("wall time %.2fs; sample output rid=0: %s",
             res.wall_time, res.outputs.get(0, [])[:16])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
