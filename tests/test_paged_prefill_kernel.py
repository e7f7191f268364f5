"""Fused paged-prefill Pallas kernel vs the jnp oracle (PR 8).

Interpret-mode parity for the gather-write-attend kernel — masked
padded rows, shared (CoW-attached) pages, attach-then-diverge, and the
bucket-ladder edge sizes — plus the engine contracts the kernel path
rides on: async pooled suspend snapshots are output- and
stats-identical to the sync path, and ``Engine.warmup`` really does
pre-compile every signature the run loop can hit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_prefill_attention
from repro.kernels.paged_attention.ref import paged_prefill_reference

from tests.test_paged_plane import (assert_reference_parity, build,
                                    requests_for)

K = jax.random.PRNGKey
TOL = dict(rtol=2e-5, atol=2e-5)


def _mk(B, c, H, Hkv, D, page, maxp, spare=3, seed=0):
    """Random chunk + pools + disjoint per-row block tables."""
    P = B * maxp + spare
    q = jax.random.normal(K(seed), (B, c, H, D))
    k = jax.random.normal(K(seed + 1), (B, c, Hkv, D))
    v = jax.random.normal(K(seed + 2), (B, c, Hkv, D))
    kp = jax.random.normal(K(seed + 3), (P, Hkv, page, D))
    vp = jax.random.normal(K(seed + 4), (P, Hkv, page, D))
    bt = jax.random.permutation(K(seed + 5), P)[:B * maxp] \
        .reshape(B, maxp).astype(jnp.int32)
    return q, k, v, kp, vp, bt


def _both(q, k, v, kp, vp, bt, starts, lengths):
    starts = jnp.asarray(starts, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    out, nk, nv = paged_prefill_attention(q, k, v, kp[None], vp[None], bt,
                                          starts, lengths, interpret=True)
    got = out, nk[0], nv[0]
    want = paged_prefill_reference(q, k, v, kp, vp, bt, starts, lengths)
    return got, want


def _assert_triple(got, want, lengths, c):
    out_g, kp_g, vp_g = map(np.asarray, got)
    out_w, kp_w, vp_w = map(np.asarray, want)
    # attention outputs only matter on real rows — padded rows are
    # masked inert by contract, not required to be numerically equal
    valid = np.arange(c)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(out_g[valid], out_w[valid], **TOL)
    # the pools must match EVERYWHERE: same writes, zero scribbles
    np.testing.assert_allclose(kp_g, kp_w, **TOL)
    np.testing.assert_allclose(vp_g, vp_w, **TOL)


# --------------------------------------------------------------------- #
# interpret-mode kernel parity
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("B,c,H,Hkv,D,page,maxp", [
    (2, 8, 4, 2, 64, 8, 3),       # GQA, mid-chunk
    (1, 16, 2, 2, 64, 4, 5),      # MHA, small pages
    (2, 8, 4, 1, 128, 16, 2),     # MQA, wide head
])
def test_prefill_kernel_parity_sweep(B, c, H, Hkv, D, page, maxp):
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp)
    starts = np.array([page, 0][:B] + [0] * max(0, B - 2))[:B]
    lengths = np.full((B,), c)
    got, want = _both(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple(got, want, lengths, c)


def test_prefill_kernel_masked_padded_rows():
    """Rows padded below the bucket — including fully inert length-0
    rows — write nothing: their pool pages are bit-untouched."""
    B, c, H, Hkv, D, page, maxp = 3, 8, 4, 2, 64, 8, 3
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp, seed=7)
    starts = np.array([0, 4, 0])
    lengths = np.array([c, 3, 0])          # full / partial / inert
    got, want = _both(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple(got, want, lengths, c)
    # the inert row's pages are bit-identical to the input pool
    own = np.asarray(bt[2])
    np.testing.assert_array_equal(np.asarray(got[1])[own],
                                  np.asarray(kp)[own])
    # the partial row beyond its length wrote nothing either: positions
    # [4+3, 8) of its first page keep the original pool bytes
    p0 = int(np.asarray(bt[1])[0])
    np.testing.assert_array_equal(np.asarray(got[1])[p0, :, 7:],
                                  np.asarray(kp)[p0, :, 7:])


@pytest.mark.parametrize("c", [8, 16, 64])
@pytest.mark.parametrize("ln_kind", ["one", "edge", "full"])
def test_prefill_kernel_bucket_ladder_edges(c, ln_kind):
    """Every ladder bucket at its edge lengths (1, bucket-1, bucket)."""
    B, H, Hkv, D, page = 2, 4, 2, 64, 16
    maxp = max(2, (c + page - 1) // page + 1)
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp, seed=c)
    ln = {"one": 1, "edge": c - 1, "full": c}[ln_kind]
    starts = np.array([0, page])
    lengths = np.array([ln, max(1, ln - 1)])
    got, want = _both(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple(got, want, lengths, c)


def test_prefill_kernel_attach_then_diverge():
    """Two rows share a physical prefix page (a zero-copy registry
    attach); each prefills only its private continuation.  The shared
    page must be read by both and written by neither."""
    B, c, H, Hkv, D, page, maxp = 2, 8, 4, 2, 64, 8, 3
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp, seed=11)
    bt = np.array(bt)
    bt[1, 0] = bt[0, 0]              # attach: same physical first page
    bt = jnp.asarray(bt)
    starts = np.array([page, page])  # both start past the shared page
    lengths = np.array([c, c])
    got, want = _both(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple(got, want, lengths, c)
    shared = int(np.asarray(bt)[0, 0])
    np.testing.assert_array_equal(np.asarray(got[1])[shared],
                                  np.asarray(kp)[shared])
    np.testing.assert_array_equal(np.asarray(got[2])[shared],
                                  np.asarray(vp)[shared])
    # rows carry different chunks past the shared page: they diverge
    assert not np.allclose(np.asarray(got[0])[0], np.asarray(got[0])[1])


def test_prefill_kernel_cow_boundary_page():
    """A row resuming mid-page (the CoW-guarded in-page append case)
    writes only positions >= start of that page."""
    B, c, H, Hkv, D, page, maxp = 1, 8, 4, 2, 64, 8, 2
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp, seed=13)
    starts = np.array([5])           # mid-page resume
    lengths = np.array([3])          # stays inside the boundary page
    got, want = _both(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple(got, want, lengths, c)
    p0 = int(np.asarray(bt)[0, 0])
    np.testing.assert_array_equal(np.asarray(got[1])[p0, :, :5],
                                  np.asarray(kp)[p0, :, :5])


def test_prefill_kernel_reads_and_writes_one_layer_of_a_stack():
    """Layer 1 of a 3-layer stack, with a full, a partial and an inert
    row: the chunk's real rows land in layer 1's owned pages, and
    nothing else moves.  The other layers hold NaNs, so a read of them
    would show in the output."""
    B, c, H, Hkv, D, page, maxp, L, layer = 3, 8, 4, 2, 64, 8, 3, 3, 1
    q, k, v, kp, vp, bt = _mk(B, c, H, Hkv, D, page, maxp, seed=17)
    starts = jnp.asarray([0, 4, 0], jnp.int32)
    lengths = jnp.asarray([c, 3, 0], jnp.int32)
    k_stack = jnp.full((L,) + kp.shape, jnp.nan).at[layer].set(kp)
    v_stack = jnp.full((L,) + vp.shape, jnp.nan).at[layer].set(vp)
    out, nk, nv = paged_prefill_attention(q, k, v, k_stack, v_stack, bt,
                                          starts, lengths, layer,
                                          interpret=True)
    want = paged_prefill_reference(q, k, v, kp, vp, bt, starts, lengths)
    _assert_triple((out, nk[layer], nv[layer]), want, lengths, c)
    for new, old in ((nk, k_stack), (nv, v_stack)):
        new, old = np.asarray(new), np.asarray(old)
        others = np.arange(L) != layer
        np.testing.assert_array_equal(new[others], old[others])
        unowned = np.setdiff1d(np.arange(kp.shape[0]), np.asarray(bt[:2]))
        np.testing.assert_array_equal(new[layer, unowned], old[layer, unowned])
    # the rows written are exactly the real ones: row 0's c from 0, row
    # 1's 3 from position 4; every other position of layer 1 keeps its
    # bytes
    written = np.zeros(np.asarray(kp).shape[:1] + (page,), bool)
    for b, (st, ln) in enumerate(((0, c), (4, 3))):
        for p in range(st, st + ln):
            written[int(np.asarray(bt)[b, p // page]), p % page] = True
    moved = np.any(np.asarray(nk[layer]) != np.asarray(kp), axis=(1, 3))
    np.testing.assert_array_equal(moved, written)


# --------------------------------------------------------------------- #
# engine contracts: async pooled suspends, warmup
# --------------------------------------------------------------------- #

_COUNTERS = ("swap_outs", "swap_ins", "kv_out", "kv_in", "swap_fallbacks",
             "promotions", "demotions", "kv_promoted", "kv_demoted")


@pytest.mark.slow
@pytest.mark.parametrize("partial", [False, True])
def test_async_pooled_suspend_parity_vs_sync(partial):
    """Async page-run snapshots (device-side gathers drained at step
    boundaries) are token- and counter-identical to the sync path —
    only wall attribution may differ."""
    results = {}
    for async_swap in (False, True):
        cfg, params, eng = build(M_kv=40, page_size=8, plane="paged",
                                 preempt_mode="swap",
                                 partial_preempt=partial,
                                 async_swap=async_swap)
        reqs = requests_for(cfg, n=6, seed=3)
        res = eng.run(reqs)
        assert res.metrics.num_swaps > 0, "churn was not real"
        assert not eng._pending_runs
        assert len(eng.swap_store) == 0
        results[async_swap] = (res.outputs,
                               {k: eng.swap_stats[k] for k in _COUNTERS})
    assert results[True][0] == results[False][0]
    assert results[True][1] == results[False][1]


@pytest.mark.slow
@pytest.mark.parametrize("plane", ["paged", "batched"])
def test_warmup_precompiles_every_signature(plane):
    """After ``warmup()`` a preemption-free workload hits only warmed
    signatures: ``num_compiles`` does not move during ``run``."""
    cfg, params, eng = build(M_kv=200, nslots=4, plane=plane,
                             page_size=8 if plane == "paged" else 1)
    eng.warmup()
    n0 = eng.num_compiles
    assert n0 > 0
    reqs = requests_for(cfg, n=4, seed=1)
    res = eng.run(reqs)
    assert eng.num_compiles == n0, (eng.num_compiles, n0)
    assert_reference_parity(cfg, params, reqs, res.outputs)
