"""fig_prefix_sharing — shared-prefix page reuse on the paged plane
(PR 4; Optimizing LLM Queries in Relational Workloads, arXiv 2403.05821).

Relational LLM workloads fan one system prompt / table schema out over
many rows: most of every prompt is the same tokens.  With pooled KV
pages and the refcounted prefix registry, requests whose prompts share
leading FULL pages map the SAME physical pages and skip their prefill
compute entirely.

This benchmark sweeps the duplicate-prefix fraction of a
``data.workloads.shared_prefix`` workload (8 requests per point, the
group's template request staggered one batch ahead so its pages are in
the registry — prefix reuse is cross-batch) and runs each point through
the paged engine with sharing ON and OFF.  Reported per point:

  * peak resident pages (block-table-referenced physical pages — shared
    pages count ONCE; the dedup signal),
  * wall tok/s (sharing skips the shared tokens' prefill FLOPs; both
    engines run ``share_jits=True`` + ``warmup()`` so compiles stay out
    of the timed window — PR 8),
  * the engine's step-program counters (``EngineResult.phase_stats``):
    the real prefill tokens against the tokens of the padded grids, and
    the active decode rows per decode call,
  * prefix hits / shared tokens from the allocator stats.

Asserted: outputs are token-identical with sharing on and off at every
point (reuse is a memory/compute optimization, never a semantic one),
and at a 75% duplicate fraction sharing holds measurably fewer resident
pages than unshared paging, computes fewer prefill tokens, AND is
strictly faster wall-clock (the attach path replaces the shared tokens'
prefill work with a registry pointer bump).
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks.common import print_table, save_json


def _run(cfg, params, cm, reqs, *, sharing):
    from repro.core import make_scheduler
    from repro.serving import Engine, EngineConfig

    sched = make_scheduler("vllm", 400, S=512, replacement="srf")
    eng = Engine(cfg, params, sched,
                 EngineConfig(nslots=8, cache_len=64, chunk=16,
                              plane="paged", page_size=8,
                              prefix_sharing=sharing, share_jits=True),
                 cost_model=cm)
    eng.warmup()                   # compiles land OUTSIDE the timed window
    t0 = time.perf_counter()
    res = eng.run(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(v) for v in res.outputs.values())
    return dict(outputs=res.outputs, wall_s=wall, tokens=toks,
                tps=toks / wall,
                peak_pages=max(b.pages_used for b in res.metrics.batches),
                prefix_hits=eng.allocator.stats["prefix_hits"],
                shared_tokens=eng.allocator.stats["prefix_shared_tokens"],
                preemptions=res.metrics.num_preemptions,
                **res.phase_stats)


def run(smoke: bool = False) -> dict:
    import jax

    from repro.configs import get_config
    from repro.core import TheoreticalCostModel, get_hardware
    from repro.data.workloads import shared_prefix
    from repro.models import model as M

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cm = TheoreticalCostModel(cfg, get_hardware("tpu_v5e"))

    fracs = [0.0, 0.75] if smoke else [0.0, 0.25, 0.5, 0.75]
    n = 8
    rows, payload = [], {}
    for frac in fracs:
        # 48-token prompts: prefill (3 chunk rounds) carries enough of
        # the wall that attach savings clear run-to-run noise
        wl_kw = dict(n=n, input_len=48, prefix_frac=frac, output_len=6,
                     vocab=cfg.vocab_size, stagger=1e-6, seed=3)
        point = {}
        for sharing in (False, True):
            point[sharing] = _run(cfg, params, cm, shared_prefix(**wl_kw),
                                  sharing=sharing)
        off, on = point[False], point[True]
        assert on["outputs"] == off["outputs"], \
            f"prefix sharing changed tokens at frac={frac}"
        rows.append([f"{frac:.2f}",
                     off["peak_pages"], on["peak_pages"],
                     f"{off['tps']:.1f}", f"{on['tps']:.1f}",
                     off["prefill_real_tokens"], on["prefill_real_tokens"],
                     on["prefill_grid_tokens"],
                     f"{on['decode_rows'] / max(1, on['decode_calls']):.2f}",
                     on["prefix_hits"], on["shared_tokens"]])
        payload[f"frac_{frac}"] = {
            "unshared": {k: v for k, v in off.items() if k != "outputs"},
            "shared": {k: v for k, v in on.items() if k != "outputs"},
        }
    print_table(
        f"fig_prefix_sharing — resident pages & tok/s vs duplicate-prefix "
        f"fraction (paged plane, {n} requests, page_size=8)",
        ["dup frac", "pages (off)", "pages (on)", "tok/s (off)",
         "tok/s (on)", "prefill toks (off)", "prefill toks (on)",
         "grid toks (on)", "rows/decode (on)", "hits", "shared toks"],
        rows)

    # the point of the exercise: ≥8 requests sharing a 75% prefix hold
    # measurably fewer resident pages than unshared paging — and with
    # compiles out of the timed window (PR 8), sharing is also strictly
    # faster: attached pages skip their prefill rounds outright
    hi = payload[f"frac_{fracs[-1]}"]
    assert hi["shared"]["peak_pages"] < hi["unshared"]["peak_pages"], hi
    assert hi["shared"]["prefix_hits"] >= n - 1, hi
    assert hi["shared"]["prefill_real_tokens"] \
        < hi["unshared"]["prefill_real_tokens"], hi
    assert hi["shared"]["wall_s"] < hi["unshared"]["wall_s"], hi
    # no duplicate prefix -> no CROSS-request sharing.  The radix trie
    # (PR 9) can still legitimately re-attach a recompute-preempted
    # request's own surviving cached run — a partial hit the old
    # exact-match registry missed — so hits at frac 0 are bounded by
    # preemption churn, not zero.
    lo = payload["frac_0.0"]
    assert lo["shared"]["prefix_hits"] <= lo["shared"]["preemptions"]
    print("tokens identical with sharing on/off: True")
    payload["shared_vs_unshared_tps_ratio"] = (hi["shared"]["tps"] /
                                               hi["unshared"]["tps"])
    save_json("fig_prefix_sharing", payload)
    return payload


if __name__ == "__main__":
    run()
