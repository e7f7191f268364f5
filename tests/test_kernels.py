"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Assignment §c: 'For each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracle.'
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.paged_attention.ops import (decode_attention_dense,
                                               paged_decode_attention,
                                               write_rows)
from repro.kernels.paged_attention.paged_attention import pages_per_block
from repro.kernels.paged_attention.ref import paged_decode_reference
from repro.kernels.rwkv_scan.ops import wkv6
from repro.kernels.rwkv_scan.ref import wkv6_reference

K = jax.random.PRNGKey


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 8, 2, 64),       # GQA 4:1
    (1, 128, 8, 1, 128),      # MQA
    (2, 100, 4, 2, 64),       # ragged S (padding path)
    (1, 512, 2, 2, 128),      # long
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Hkv, D, dtype):
    q = jax.random.normal(K(0), (B, S, H, D), dtype)
    k = jax.random.normal(K(1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(K(2), (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v)
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    B, S, H, Hkv, D = 1, 256, 4, 2, 64
    q = jax.random.normal(K(0), (B, S, H, D))
    k = jax.random.normal(K(1), (B, S, Hkv, D))
    v = jax.random.normal(K(2), (B, S, Hkv, D))
    out = flash_attention(q, k, v, window=window)
    ref = attention_reference(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_prefix_lm():
    """PaliGemma-style bidirectional prefix."""
    B, S, H, Hkv, D = 1, 128, 4, 4, 64
    q = jax.random.normal(K(0), (B, S, H, D))
    k = jax.random.normal(K(1), (B, S, Hkv, D))
    v = jax.random.normal(K(2), (B, S, Hkv, D))
    out = flash_attention(q, k, v, prefix_len=32)
    ref = attention_reference(q, k, v, prefix_len=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (128, 128)])
def test_flash_attention_block_shapes(bq, bk):
    B, S, H, Hkv, D = 1, 256, 2, 2, 64
    q = jax.random.normal(K(3), (B, S, H, D))
    k = jax.random.normal(K(4), (B, S, Hkv, D))
    v = jax.random.normal(K(5), (B, S, Hkv, D))
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# paged decode attention
# --------------------------------------------------------------------- #

def edge_contexts(page, npages):
    """Contexts at each edge of a page and of a grid block, from 0 to
    the full table."""
    block = pages_per_block(page, npages) * page
    full = page * npages
    return sorted({c for c in (0, 1, page - 1, page, block, block + 1, full)
                   if c <= full})


@pytest.mark.parametrize("B,H,Hkv,D,page,npages", [
    (2, 8, 2, 64, 16, 4),
    (4, 4, 4, 64, 32, 2),
    (1, 8, 1, 128, 64, 3),
    (2, 32, 8, 128, 16, 20),     # Qwen3-4B widths; 2 blocks, the last short
    (2, 15, 5, 64, 16, 20),      # SmolLM-360M widths
    (2, 8, 2, 64, 64, 6),        # page 64: 4 pages a block, 2 blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_sweep(B, H, Hkv, D, page, npages, dtype):
    P = B * npages + 3                      # spare pages in the pool
    q = jax.random.normal(K(0), (B, H, D), dtype)
    kp = jax.random.normal(K(1), (P, Hkv, page, D), dtype)
    vp = jax.random.normal(K(2), (P, Hkv, page, D), dtype)
    bt = jax.random.permutation(K(3), P)[:B * npages].reshape(B, npages)
    bt = bt.astype(jnp.int32)
    ctx = jax.random.randint(K(4), (B,), 1, page * npages + 1)
    out = paged_decode_attention(q, kp[None], vp[None], bt, ctx)
    ref = paged_decode_reference(q.astype(jnp.float32),
                                 kp.astype(jnp.float32),
                                 vp.astype(jnp.float32), bt, ctx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))

    # one row at each edge context; every table entry past a row's
    # context points at another row's live page or at a page of NaNs,
    # so an entry the kernel read would show
    ctx = jnp.asarray(edge_contexts(page, npages), jnp.int32)
    R = ctx.shape[0]
    P = R * npages + 1
    q = jax.random.normal(K(5), (R, H, D), dtype)
    kp = jax.random.normal(K(6), (P, Hkv, page, D), dtype).at[P - 1].set(
        jnp.nan)
    vp = jax.random.normal(K(7), (P, Hkv, page, D), dtype).at[P - 1].set(
        jnp.nan)
    own = jax.random.permutation(K(8), P - 1).reshape(R, npages)
    own = own.astype(jnp.int32)
    entry = jnp.arange(npages)[None, :]
    past = entry * page >= ctx[:, None]
    tail = jnp.where(entry % 2 == 0, jnp.roll(own, 1, axis=0), P - 1)
    out = paged_decode_attention(q, kp[None], vp[None],
                                 jnp.where(past, tail, own), ctx)
    ref = paged_decode_reference(q.astype(jnp.float32),
                                 kp.astype(jnp.float32),
                                 vp.astype(jnp.float32), own, ctx)
    out = np.asarray(out, np.float32)
    live = np.asarray(ctx) > 0
    np.testing.assert_array_equal(out[~live], 0.0)    # context 0: zeros
    np.testing.assert_allclose(out[live], np.asarray(ref, np.float32)[live],
                               **tol(dtype))


@pytest.mark.parametrize("D", [128, 64])        # own copies; pipelined pages
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_writes_then_reads_one_layer_of_a_stack(D, dtype):
    """Layer 1 of a 3-layer stack, as ``ops.paged_decode`` runs it: each
    live row's new K/V row lands at (1, page, :, slot, :) in place, then
    the row attends over it; an idle row writes nothing.  The other layers hold NaNs, so a read of them
    would show, and they, like every page of layer 1 no row writes,
    keep their bytes."""
    B, H, Hkv, page, npages, L, layer = 5, 8, 2, 16, 20, 3, 1
    P = B * npages + 3
    kp = jnp.full((L, P, Hkv, page, D), jnp.nan, dtype)
    vp = jnp.full((L, P, Hkv, page, D), jnp.nan, dtype)
    kp = kp.at[layer].set(jax.random.normal(K(1), (P, Hkv, page, D), dtype))
    vp = vp.at[layer].set(jax.random.normal(K(2), (P, Hkv, page, D), dtype))
    q = jax.random.normal(K(0), (B, H, D), dtype)
    k = jax.random.normal(K(3), (B, Hkv, D), dtype)
    v = jax.random.normal(K(4), (B, Hkv, D), dtype)
    bt = jax.random.permutation(K(5), P)[:B * npages].reshape(B, npages)
    bt = bt.astype(jnp.int32)
    # idle; the first position; a page's last; the next page's first;
    # the table's last, past a block of 16 pages
    ctx = jnp.asarray([0, 1, page, page + 1, npages * page], jnp.int32)

    nk, nv = (write_rows(pools, new, bt, ctx, layer)
              for pools, new in ((kp, k), (vp, v)))
    out = paged_decode_attention(q, nk, nv, bt, ctx, layer)

    pos = np.asarray(ctx) - 1
    live = pos >= 0
    # float32 holds every bfloat16 exactly, NaNs included
    want_k, want_v = np.array(kp, np.float32), np.array(vp, np.float32)
    for b in np.flatnonzero(live):
        at = int(np.asarray(bt)[b, pos[b] // page]), pos[b] % page
        want_k[layer, at[0], :, at[1]] = np.asarray(k, np.float32)[b]
        want_v[layer, at[0], :, at[1]] = np.asarray(v, np.float32)[b]
    np.testing.assert_array_equal(np.asarray(nk, np.float32), want_k)
    np.testing.assert_array_equal(np.asarray(nv, np.float32), want_v)
    ref = paged_decode_reference(q.astype(jnp.float32),
                                 jnp.asarray(want_k[layer]),
                                 jnp.asarray(want_v[layer]), bt, ctx)
    out = np.asarray(out, np.float32)
    np.testing.assert_array_equal(out[~live], 0.0)
    np.testing.assert_allclose(out[live], np.asarray(ref, np.float32)[live],
                               **tol(dtype))


def test_paged_decode_short_context_skips_pages():
    """ctx=1: only the first page contributes (no later page is read)."""
    B, H, Hkv, D, page, npages = 1, 4, 2, 64, 16, 4
    P = B * npages
    q = jax.random.normal(K(0), (B, H, D))
    kp = jax.random.normal(K(1), (P, Hkv, page, D))
    vp = jax.random.normal(K(2), (P, Hkv, page, D))
    bt = jnp.arange(P).reshape(B, npages).astype(jnp.int32)
    ctx = jnp.array([1], jnp.int32)
    out = paged_decode_attention(q, kp[None], vp[None], bt, ctx)
    ref = paged_decode_reference(q, kp, vp, bt, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_dense_wrapper():
    B, S, H, Hkv, D = 3, 128, 8, 4, 64
    q = jax.random.normal(K(0), (B, H, D))
    k = jax.random.normal(K(1), (B, S, Hkv, D))
    v = jax.random.normal(K(2), (B, S, Hkv, D))
    ctx = jnp.array([5, 64, 128], jnp.int32)
    out = decode_attention_dense(q, k, v, ctx, page_size=32)
    from repro.distributed.collectives import decode_attn_reference
    ref = decode_attn_reference(q, k, v, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# wkv6 chunked scan
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("B,S,H,D,chunk", [
    (1, 64, 2, 32, 16),
    (2, 128, 4, 32, 64),
    (1, 96, 2, 64, 32),
    (2, 64, 2, 32, 64),                    # single chunk
])
def test_wkv6_sweep(B, S, H, D, chunk):
    r = jax.random.normal(K(0), (B, S, H, D)) * 0.3
    k = jax.random.normal(K(1), (B, S, H, D)) * 0.3
    v = jax.random.normal(K(2), (B, S, H, D)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(K(3), (B, S, H, D))) * 0.5 + 0.45
    u = jax.random.normal(K(4), (H, D)) * 0.1
    y, s = wkv6(r, k, v, w, u, chunk=chunk)
    yr, sr = wkv6_reference(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=3e-5, atol=3e-5)


def test_wkv6_decay_extremes():
    """w near 0 (full reset) and near 1 (full memory) stay stable."""
    B, S, H, D = 1, 64, 2, 32
    r = jax.random.normal(K(0), (B, S, H, D)) * 0.3
    k = jax.random.normal(K(1), (B, S, H, D)) * 0.3
    v = jax.random.normal(K(2), (B, S, H, D)) * 0.3
    u = jnp.zeros((H, D))
    for wval in (0.01, 0.999):
        w = jnp.full((B, S, H, D), wval)
        y, s = wkv6(r, k, v, w, u, chunk=16)
        yr, sr = wkv6_reference(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
