"""``python -m benchmarks.run`` — every paper table/figure, in order.

Each module prints its table, asserts the paper's qualitative claims,
and persists JSON under experiments/bench/.

``--smoke`` runs the same modules with tiny workload sizes (small W/n)
so one offline command catches schedule/benchmark regressions in
minutes; the qualitative assertions still run.  Positional arguments
filter modules by substring (e.g. ``python -m benchmarks.run fig08``).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

sys.path.insert(0, "src")

from benchmarks import (appa_low_contention, appb_engine_validation,  # noqa: E402
                        appc_ranking, fig04_cost_linearity, fig06_roofline,
                        fig07_slo_pareto, fig08_recompute_vs_swap,
                        fig09_schedulers, fig11_preemption_free,
                        fig12_vary_m, fig13_csp, fig14_srf,
                        fig_cache_replacement, fig_engine_wall,
                        fig_fault_recovery, fig_prefix_sharing,
                        fig_radix_trie, five_minute_rule, roofline_table)
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

# (name, module, smoke-mode kwargs).  Modules without a size knob are
# already tiny/analytical and run unchanged in smoke mode.
MODULES = [
    ("Fig 4  cost-model linearity", fig04_cost_linearity, {}),
    ("Fig 5/6 roofline placement", fig06_roofline, {}),
    ("Fig 7  SLO pareto", fig07_slo_pareto, {}),
    ("Fig 8  recompute vs swap", fig08_recompute_vs_swap, {"smoke": True}),
    ("Fig 9  scheduler comparison (W=1024)", fig09_schedulers, {"W": 128}),
    ("App A  low contention (W=32)", appa_low_contention, {}),
    ("Fig 11 preemption-free", fig11_preemption_free, {"W": 256}),
    ("Fig 12 varying M", fig12_vary_m, {"W": 256}),
    ("Fig 13 CSP optimal scheduling", fig13_csp, {}),
    ("Fig 14 SRF vs NRF", fig14_srf, {"n": 128}),
    ("App B  engine-vs-sim validation", appb_engine_validation, {}),
    ("$Perf  engine wall-time planes", fig_engine_wall, {"smoke": True}),
    ("$Perf  shared-prefix page reuse", fig_prefix_sharing, {"smoke": True}),
    ("$Trie  radix vs exact prefix lookup", fig_radix_trie,
     {"smoke": True}),
    ("$6/§8  cache replacement + demotion", fig_cache_replacement,
     {"smoke": True}),
    ("App C  heterogeneous ranking", appc_ranking, {"W": 96}),
    ("$6     five-minute rule", five_minute_rule, {}),
    ("$Chaos fault injection & recovery ladder", fig_fault_recovery,
     {"smoke": True}),
    ("$Roofline table (dry-run artifacts)", roofline_table, {}),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload sizes (fast offline regression "
                         "check)")
    ap.add_argument("filters", nargs="*",
                    help="only run modules whose name contains a filter")
    args = ap.parse_args(argv)
    setup_compile_cache()

    t0 = time.time()
    failures = []
    records = []
    payloads = {}
    ran = 0
    for name, mod, smoke_kw in MODULES:
        if args.filters and not any(f.lower() in name.lower()
                                    or f.lower() in mod.__name__.lower()
                                    for f in args.filters):
            continue
        ran += 1
        print(f"\n{'='*72}\n>> {name}\n{'='*72}")
        t = time.time()
        try:
            payloads[mod.__name__] = mod.run(
                **(smoke_kw if args.smoke else {}))
            status = "ok"
            print(f"[ok] {name} ({time.time()-t:.1f}s)")
        except Exception:
            status = "fail"
            failures.append(name)
            traceback.print_exc()
            print(f"[FAIL] {name}")
        records.append({"name": name, "module": mod.__name__,
                        "status": status,
                        "duration_s": round(time.time() - t, 2)})
    print(f"\n{'='*72}")
    mode = "smoke" if args.smoke else "full"
    print(f"benchmarks ({mode}): {ran-len(failures)}/{ran} passed "
          f"in {time.time()-t0:.0f}s")
    if args.smoke and not args.filters:
        # one consolidated artifact for the smoke gate: CI/check.sh can
        # diff module-level status and spot pathological slowdowns
        # without parsing per-figure JSONs
        from benchmarks.common import save_json
        save_json("BENCH_smoke", {
            "mode": mode,
            "passed": ran - len(failures),
            "ran": ran,
            "failed": failures,
            "total_s": round(time.time() - t0, 2),
            "modules": records,
        })
    wall = payloads.get("benchmarks.fig_engine_wall")
    share = payloads.get("benchmarks.fig_prefix_sharing")
    if args.smoke and wall and share:
        # repo-root perf headline (PR 8): the two ratios the paged
        # plane is accountable for — check.sh gates on the first
        import json
        bench8 = {
            "paged_vs_batched_tps_ratio":
                round(wall["paged_vs_batched_tps_ratio"], 4),
            "shared_vs_unshared_tps_ratio":
                round(share["shared_vs_unshared_tps_ratio"], 4),
            "paged_tps": round(wall["paged"]["tps"], 2),
            "batched_tps": round(wall["batched"]["tps"], 2),
        }
        with open("BENCH_8.json", "w") as f:
            json.dump(bench8, f, indent=1)
        print("BENCH_8.json:", bench8)
    trie = payloads.get("benchmarks.fig_radix_trie")
    if args.smoke and trie:
        # repo-root trie headline (PR 9): what partial-prefix matching
        # buys over exact-match lookup on branching conversations —
        # check.sh gates on the shared-tokens ratio
        import json
        bench9 = {
            "trie_vs_exact_shared_tokens_ratio":
                round(trie["trie_vs_exact_shared_tokens_ratio"], 4),
            "trie_vs_exact_tps_ratio":
                round(trie["trie_vs_exact_tps_ratio"], 4),
            "conversation_tree_partial_hit_tokens":
                trie["conversation_tree"]["trie"]["partial_hit_tokens"],
        }
        with open("BENCH_9.json", "w") as f:
            json.dump(bench9, f, indent=1)
        print("BENCH_9.json:", bench9)
    if failures:
        print("failed:", ", ".join(failures))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
