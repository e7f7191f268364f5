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
from repro.models import attention as attn
from repro.models import model as M
from repro.models.common import rms_norm


def paged_supported(cfg: ModelConfig) -> bool:
    """True iff the family's attention KV is unbounded dense (the only
    state worth paging)."""
    return (cfg.num_heads > 0 and not cfg.window
            and cfg.family not in ("ssm", "hybrid"))


def _attn_paged_chunk(lp: Any, cfg: ModelConfig, h: jnp.ndarray,
                      k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                      layer: jnp.ndarray, starts: jnp.ndarray,
                      lengths: jnp.ndarray, block_tables: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked prefill attention against pooled KV.  h (B, c, d); pools
    stacked over layers (L, P, Hkv, page, D), of which ``layer`` is this
    one; starts/lengths (B,); block_tables (B, maxp).  Returns (attn out
    (B, c, q_dim-projected), new pools)."""
    B, c, _ = h.shape
    positions = starts[:, None] + jnp.arange(c)[None, :]        # (B, c)
    q, k, v = attn._project_qkv(lp, cfg, h, positions)
    out, k_pools, v_pools = pa_ops.paged_prefill(
        q, k, v, k_pools, v_pools, block_tables, starts, lengths, layer)
    out = out.reshape(B, c, cfg.q_dim) @ lp["wo"]
    return out, k_pools, v_pools


def _attn_paged_decode(lp: Any, cfg: ModelConfig, h: jnp.ndarray,
                       k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                       layer: jnp.ndarray, ctx: jnp.ndarray,
                       block_tables: jnp.ndarray, active: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode against pooled KV.  h (B, 1, d); ctx (B,) valid
    context (also the new token's position); active (B,) row mask."""
    B = h.shape[0]
    q, k, v = attn._project_qkv(lp, cfg, h, ctx[:, None])
    # write-then-attend: the new row lands at position ctx and
    # context_lens = ctx + 1 includes it; an idle row writes and attends
    # over nothing (its output is never read)
    out, k_pools, v_pools = pa_ops.paged_decode(
        q[:, 0], k[:, 0], v[:, 0], k_pools, v_pools, block_tables,
        jnp.where(active, ctx + 1, 0), layer)
    out = out.reshape(B, cfg.q_dim) @ lp["wo"]
    return out[:, None, :], k_pools, v_pools


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

    Pools are stacked over layers: (L, P, Hkv, page, D).  The layer loop
    carries the whole stack and hands it to the attention ops with the
    layer's index, so no step slices one layer's pool out or stacks it
    back.  Sampling is fused (argmax over the real vocabulary on device).
    The attention backend of both steps is picked by the JAX backend
    alone (``ops.paged_prefill`` / ``ops.paged_decode``): the Pallas
    kernels on an accelerator, the jnp reference on the CPU.
    """
    if not paged_supported(cfg):
        raise ValueError("paged pools need unbounded dense attention, "
                         f"got {cfg.family!r}")
    vocab = cfg.vocab_size

    def _layers(params, x, k_pools, v_pools, attend):
        """x through every layer; ``attend(lp, h, k_pools, v_pools,
        layer)`` -> (attn out, new pools)."""
        def body(carry, per_layer):
            xc, kp, vp = carry
            lp, layer = per_layer
            h = rms_norm(xc, lp["ln1"], cfg.norm_eps)
            a, kp, vp = attend(lp["attn"], h, kp, vp, layer)
            xc = xc + a
            h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
            return (xc + M._mlp_or_moe(cfg, lp, h2, moe_impl), kp, vp), None

        layer_ids = jnp.arange(k_pools.shape[0], dtype=jnp.int32)
        (x, k_pools, v_pools), _ = jax.lax.scan(
            body, (x, k_pools, v_pools), (params["layers"], layer_ids))
        return rms_norm(x, params["ln_f"], cfg.norm_eps), k_pools, v_pools

    def prefill_fn(params, k_pools, v_pools, tokens, starts, lengths,
                   block_tables):
        B, c = tokens.shape
        positions = starts[:, None] + jnp.arange(c)[None, :]
        x, _ = M._embed(cfg, params, tokens, positions, None)
        x, k_pools, v_pools = _layers(
            params, x, k_pools, v_pools,
            lambda lp, h, kp, vp, layer: _attn_paged_chunk(
                lp, cfg, h, kp, vp, layer, starts, lengths, block_tables))
        last = jnp.maximum(lengths - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = M._logits(cfg, params, x_last)
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, k_pools, v_pools

    def paged_decode_step(params, k_pools, v_pools, tokens, ctx,
                          block_tables, active):
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        x, _ = M._embed(cfg, params, tokens, ctx[:, None], None)
        x, k_pools, v_pools = _layers(
            params, x, k_pools, v_pools,
            lambda lp, h, kp, vp, layer: _attn_paged_decode(
                lp, cfg, h, kp, vp, layer, ctx, block_tables, active))
        logits = M._logits(cfg, params, x[:, 0])
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, k_pools, v_pools

    return prefill_fn, paged_decode_step
