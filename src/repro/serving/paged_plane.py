"""Pooled-KV model steps for the engine's ``plane="paged"`` (PR 4).

Attention KV lives in shared per-layer head-major page pools
``(num_pages, Hkv, page_size, D)`` instead of per-slot contiguous
buffers; a request's
pages are named by the ``PagedAllocator`` block table, threaded in as a
``(B, max_pages)`` int32 array.  Both steps are shape-stable (fixed pool
/ table / grid shapes; prefill tokens padded to the engine's bucket
ladder with a per-row ``lengths`` mask), so the paged plane keeps the
batched plane's constant-compile-count property.

* ``prefill``: the chunk's K/V are projected, then ONE fused op —
  ``kernels.paged_attention.ops.paged_prefill`` — writes the chunk's
  rows through the block table into the pools (padded rows route out of
  bounds and drop — pool bytes of other requests are untouchable by
  construction) and attends causally over [own pages ++ the chunk].
  On TPU that is the Pallas gather-write-attend kernel streaming owned
  pages through a flash reduction; on CPU a jnp gather oracle with the
  dense plane's exact reduction order (bit parity preserved).
* ``decode``: the new token's K/V row is scattered into its page, then
  attention runs via ``kernels.paged_attention.ops.paged_decode`` — the
  Pallas flash-decoding kernel over scalar-prefetched block tables on
  TPU, a jnp block-table gather on CPU.

Only unbounded dense-attention families are pooled: sliding-window and
SSM/RWKV state is O(1) per request, so the engine keeps it slot-resident
(paging a bounded ring buys nothing and recurrent state cannot be
partially evicted anyway — there is no "tail" to shed).

The pools ARE the persistent memory layout — which is what makes
page-level partial preemption, refcounted shared-prefix pages, and the
prefix cache's host demotion tier possible upstream: a demoted registry
page is snapshotted straight out of these pools before eviction and
scattered back into a freshly promoted page on the next registry hit
(the engine's ``_snapshot_pages`` / ``_restore_pages`` on pool slices).
Both paths read/write pages in place on TPU (the Pallas kernels DMA
exactly the owned pages; prefill updates pools via
``input_output_aliases``), so device residency is ``num_pages`` for the
pools' persistent bytes plus chunk-sized activations — the old
per-bucket ``(B, max_pages*page, Hkv, D)`` gather transient is gone
from the kernel path (the CPU oracle still materializes it; parity
matters more than speed off-accelerator).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.ref import scatter_rows as _scatter_rows
from repro.models import attention as attn
from repro.models import model as M
from repro.models.common import rms_norm


def paged_supported(cfg: ModelConfig) -> bool:
    """True iff the family's attention KV is unbounded dense (the only
    state worth paging)."""
    return (cfg.num_heads > 0 and not cfg.window
            and cfg.family not in ("ssm", "hybrid"))


def _attn_paged_chunk(lp: Any, cfg: ModelConfig, h: jnp.ndarray,
                      k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                      starts: jnp.ndarray, lengths: jnp.ndarray,
                      block_tables: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked prefill attention against pooled KV.  h (B, c, d); pools
    (P, Hkv, page, D); starts/lengths (B,); block_tables (B, maxp).
    Returns (attn out (B, c, q_dim-projected), new pools)."""
    B, c, _ = h.shape
    positions = starts[:, None] + jnp.arange(c)[None, :]        # (B, c)
    q, k, v = attn._project_qkv(lp, cfg, h, positions)
    out, new_k, new_v = pa_ops.paged_prefill(
        q, k, v, k_pool, v_pool, block_tables, starts, lengths)
    out = out.reshape(B, c, cfg.q_dim) @ lp["wo"]
    return out, new_k, new_v


def _attn_paged_decode(lp: Any, cfg: ModelConfig, h: jnp.ndarray,
                       k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                       ctx: jnp.ndarray, block_tables: jnp.ndarray,
                       active: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode against pooled KV.  h (B, 1, d); ctx (B,) valid
    context (also the new token's position); active (B,) row mask."""
    B = h.shape[0]
    P, pg = k_pool.shape[0], k_pool.shape[2]
    maxp = block_tables.shape[1]
    positions = ctx[:, None]
    q, k, v = attn._project_qkv(lp, cfg, h, positions)
    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(positions // pg, 0, maxp - 1), axis=1)[:, 0]
    dest = jnp.where(active, page_idx * pg + ctx % pg, P * pg)
    new_k = _scatter_rows(k_pool, dest, k[:, 0])
    new_v = _scatter_rows(v_pool, dest, v[:, 0])
    # write-then-attend: context_lens = ctx + 1 includes the new token;
    # an idle slot attends over nothing (its output is never read)
    out = pa_ops.paged_decode(q[:, 0], new_k, new_v, block_tables,
                              jnp.where(active, ctx + 1, 0))
    out = out.reshape(B, cfg.q_dim) @ lp["wo"]
    return out[:, None, :], new_k, new_v


def build_paged_fns(cfg: ModelConfig, *, moe_impl: str = "dense"
                    ) -> Tuple[Callable, Callable]:
    """Returns jit-ready ``(prefill_fn, paged_decode_step)`` over pooled
    KV.

    prefill_fn(params, k_pools, v_pools, tokens (B, bucket),
               starts (B,), lengths (B,), block_tables (B, maxp))
        -> (greedy ids (B,), new_k_pools, new_v_pools)
    paged_decode_step(params, k_pools, v_pools, tokens (B,), ctx (B,),
                      block_tables (B, maxp), active (B,))
        -> (greedy ids (B,), new_k_pools, new_v_pools)

    Pools are stacked over layers: (L, P, Hkv, page, D).  Sampling is
    fused (argmax over the real vocabulary on device).  The attention
    backend of both steps is picked by the JAX backend alone
    (``ops.paged_prefill`` / ``ops.paged_decode``): the Pallas kernels
    on an accelerator, the jnp reference on the CPU.
    """
    if not paged_supported(cfg):
        raise ValueError("paged pools need unbounded dense attention, "
                         f"got {cfg.family!r}")
    vocab = cfg.vocab_size

    def _block(lp, x, attn_out):
        x = x + attn_out
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + M._mlp_or_moe(cfg, lp, h2, moe_impl)

    def prefill_fn(params, k_pools, v_pools, tokens, starts, lengths,
                   block_tables):
        B, c = tokens.shape
        positions = starts[:, None] + jnp.arange(c)[None, :]
        x, _ = M._embed(cfg, params, tokens, positions, None)

        def body(xc, per_layer):
            lp, (kp, vp) = per_layer
            h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
            a, kp, vp = _attn_paged_chunk(lp["attn"], cfg, h, kp, vp,
                                          starts, lengths, block_tables)
            return _block(lp, xc, a), (kp, vp)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], (k_pools, v_pools)))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        last = jnp.maximum(lengths - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = M._logits(cfg, params, x_last)
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, new_k, new_v

    def paged_decode_step(params, k_pools, v_pools, tokens, ctx,
                          block_tables, active):
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        x, _ = M._embed(cfg, params, tokens, ctx[:, None], None)

        def body(xc, per_layer):
            lp, (kp, vp) = per_layer
            h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
            a, kp, vp = _attn_paged_decode(lp["attn"], cfg, h, kp, vp,
                                           ctx, block_tables, active)
            return _block(lp, xc, a), (kp, vp)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], (k_pools, v_pools)))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = M._logits(cfg, params, x[:, 0])
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, new_k, new_v

    return prefill_fn, paged_decode_step
