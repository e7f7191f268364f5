"""The engine's step-program counters (``Engine.phase_stats``), the
requests' host-clock stamps, and the ``repro.engine.*`` host spans.

The counters are checked against two independent records: the step
programs' own inputs, recorded by wrapping the jitted calls, and the
committed ``BatchLog``s (a rolled-back attempt leaves none), so a count
an aborted attempt doubled shows as a mismatch."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (Request, TheoreticalCostModel, get_hardware,
                        make_scheduler)
from repro.core import stat_keys as SK
from repro.data.workloads import zipf_shared_prefix
from repro.models import model as M
from repro.serving import Engine, EngineConfig
from repro.serving.faults import FaultSpec

COUNTERS = (SK.PREFILL_ROUNDS, SK.PREFILL_GRID_TOKENS,
            SK.PREFILL_REAL_TOKENS, SK.DECODE_CALLS, SK.DECODE_ROWS,
            SK.DECODE_LIVE_PAGES)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def build(model, *, plane="paged", faults=None, M_kv=256,
          preempt_mode="recompute"):
    cfg, params = model
    sched = make_scheduler("vllm", M_kv, S=512, replacement="srf",
                           preempt_mode=preempt_mode)
    return Engine(cfg, params, sched,
                  EngineConfig(nslots=4, cache_len=64, chunk=16,
                               plane=plane, page_size=8,
                               cache_policy="break_even",
                               cache_demotion=plane == "paged",
                               faults=faults),
                  cost_model=TheoreticalCostModel(
                      cfg, get_hardware("tpu_v5e")))


def workload(cfg):
    return zipf_shared_prefix(n=16, num_groups=4, page_size=8, seed=1,
                              vocab=cfg.vocab_size)


def churn(vocab, n=8, seed=4):
    """Unshared prompts that outgrow a 60-token pool: preemptions and
    swaps."""
    rs = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        size, out = int(rs.randint(8, 25)), int(rs.randint(3, 9))
        reqs.append(Request(rid=i, input_len=size, output_len=out,
                            arrival=0.0,
                            prompt=rs.randint(0, vocab, size=size).tolist()))
    return reqs


class CallLog:
    """Wraps the pooled plane's two programs and records what each call
    was given: the grid's shape and real lengths, the active rows and
    the pages they attend over (a row at ``ctx`` reads ``ctx + 1``
    positions)."""

    def __init__(self, eng):
        self.prefill, self.decode, self.live_pages = [], [], []
        pf, df = eng._paged_prefill, eng._paged_decode
        pg = eng.ecfg.page_size

        def prefill(params, kp, vp, grid, bt):
            g = np.asarray(grid)
            self.prefill.append((g.shape[0] * (g.shape[1] - 2),
                                 int(g[:, -2].sum())))
            return pf(params, kp, vp, grid, bt)

        def decode(params, kp, vp, toks, ctx, bt, active):
            a = np.asarray(active)
            self.decode.append(int(a.sum()))
            self.live_pages.append(int((-(-(np.asarray(ctx)[a] + 1)
                                          // pg)).sum()))
            return df(params, kp, vp, toks, ctx, bt, active)

        eng._paged_prefill, eng._paged_decode = prefill, decode


def check_counts(eng, res, calls=None):
    ps = res.phase_stats
    assert set(ps) == set(COUNTERS)
    batches = res.metrics.batches
    decode_tokens = sum(b.num_decode for b in batches)
    prefill_tokens = sum(b.tokens - b.num_decode for b in batches)
    shared = eng.allocator.stats[SK.PREFIX_SHARED_TOKENS]
    # every decode row emits one token, and only committed batches emit
    assert ps[SK.DECODE_ROWS] == decode_tokens > 0
    assert ps[SK.DECODE_CALLS] == sum(1 for b in batches if b.num_decode)
    # a decode row attends over at least one page, at most a full table
    max_pages = -(-eng.ecfg.cache_len // eng.ecfg.page_size)
    assert (ps[SK.DECODE_ROWS] <= ps[SK.DECODE_LIVE_PAGES]
            <= ps[SK.DECODE_ROWS] * max_pages)
    # the prompt tokens computed: those granted, less the prefix-shared
    # ones that a page mapping satisfied
    assert ps[SK.PREFILL_REAL_TOKENS] == prefill_tokens - shared
    assert ps[SK.PREFILL_GRID_TOKENS] >= ps[SK.PREFILL_REAL_TOKENS]
    assert ps[SK.PREFILL_GRID_TOKENS] % eng.ecfg.nslots == 0
    if calls is not None:
        assert ps[SK.PREFILL_ROUNDS] == len(calls.prefill)
        assert ps[SK.PREFILL_GRID_TOKENS] == sum(g for g, _ in calls.prefill)
        assert ps[SK.PREFILL_REAL_TOKENS] == sum(n for _, n in calls.prefill)
        assert ps[SK.DECODE_CALLS] == len(calls.decode)
        assert ps[SK.DECODE_ROWS] == sum(calls.decode)
        assert ps[SK.DECODE_LIVE_PAGES] == sum(calls.live_pages)


def check_stamps(reqs):
    for r in reqs:
        assert r.wall_submitted is not None and r.wall_admitted is not None
        assert r.wall_submitted <= r.wall_admitted


def test_counters_match_the_programs_inputs_and_the_batch_logs(model):
    eng = build(model)
    calls = CallLog(eng)
    reqs = workload(model[0])
    res = eng.run(reqs)
    assert eng.allocator.stats[SK.PREFIX_SHARED_TOKENS] > 0
    check_counts(eng, res, calls)
    check_stamps(reqs)
    # a result carries a copy, not the live dict
    assert res.phase_stats is not eng.phase_stats


def test_decode_live_pages_sum_the_pages_each_decode_row_reads(model):
    """A decode at context m reads m + 1 positions, ceil((m + 1) / page)
    pages; a request of I prompt tokens and O outputs decodes at m = I
    to I + O - 2 (its prefill emits the first output)."""
    eng = build(model)
    calls = CallLog(eng)
    pg = eng.ecfg.page_size
    shapes = ((5, 6), (7, 4), (14, 12))     # (I, O): crossing pages of 8
    reqs = [Request(rid=i, input_len=n, output_len=o, arrival=0.0,
                    prompt=[(31 * i + t) % 97 + 1 for t in range(n)])
            for i, (n, o) in enumerate(shapes)]
    eng.run(reqs)
    ps = eng.phase_stats
    assert ps[SK.DECODE_ROWS] == sum(o - 1 for _, o in shapes)
    assert ps[SK.DECODE_LIVE_PAGES] == sum(
        -(-(m + 1) // pg) for n, o in shapes for m in range(n, n + o - 1))
    assert ps[SK.DECODE_LIVE_PAGES] == sum(calls.live_pages)


def test_counters_on_the_slot_plane(model):
    eng = build(model, plane="batched")
    reqs = workload(model[0])
    res = eng.run(reqs)
    check_counts(eng, res)
    assert eng.allocator.stats[SK.PREFIX_SHARED_TOKENS] == 0
    check_stamps(reqs)


@pytest.mark.parametrize("spec", [
    FaultSpec(seed=5, p_alloc=0.3),
    FaultSpec(seed=3, p_alloc=0.2, p_corrupt=0.5, p_store_permanent=0.2),
], ids=["alloc", "alloc+corrupt"])
def test_no_count_is_doubled_by_rollbacks(model, spec):
    eng = build(model, faults=spec, M_kv=60, preempt_mode="swap")
    calls = CallLog(eng)
    reqs = churn(model[0].vocab_size)
    res = eng.run(reqs)
    assert eng.recovery_stats[SK.ROLLBACKS] > 0
    check_counts(eng, res, calls)
    check_stamps(reqs)


def test_a_rolled_back_admission_is_stamped_again(model):
    """The admission stamp is request state of the step transaction: a
    rollback clears it, and the retry stamps it anew."""
    from repro.serving.txn import snapshot_requests
    r = Request(rid=0, input_len=4, output_len=2, prompt=[1, 2, 3, 4])
    r.wall_submitted = 1.0
    restore = snapshot_requests([r])
    r.wall_admitted = 2.0
    restore()
    assert r.wall_admitted is None and r.wall_submitted == 1.0


STEP_PARTS = {"txn", "schedule", "preempt", "swap_in", "admit", "upload",
              "prefill_call", "decode_call", "fetch", "register", "drain"}


def test_spans_land_in_a_profiler_trace_inside_the_steps(model, tmp_path):
    eng = build(model, M_kv=60, preempt_mode="swap")
    reqs = churn(model[0].vocab_size)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [(ev.name[len("repro.engine."):], ev.start_ns, ev.end_ns)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.engine.")]
    names = {n for n, _, _ in spans}
    steps = [(a, b) for n, a, b in spans if n == "step"]
    assert len(steps) >= len(eng.batch_logs) > 0
    # every part but the run's closing drain lies inside a step; the
    # swap-preempting pool makes the preempt and swap-in paths run
    assert STEP_PARTS - {"preempt", "swap_in"} <= names
    assert eng.sched.num_preemptions > 0 and eng.sched.num_swaps > 0
    assert {"preempt", "swap_in"} <= names
    for n, a, b in spans:
        if n in ("step", "drain"):
            continue
        assert any(s <= a and b <= e for s, e in steps), n
    assert names <= STEP_PARTS | {"step"}
