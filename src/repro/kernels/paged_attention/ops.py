"""Jitted wrappers for paged attention (decode + fused prefill).

``paged_decode`` / ``paged_prefill`` are the engine's entry points
(``plane="paged"``): on TPU they run the Pallas kernels
(scalar-prefetched block tables, page-granular DMA, prefill writing the
chunk's K/V straight into the pools); on CPU they lower to jit-friendly
jnp block-table gathers (``ref``) instead of interpret-mode Pallas —
the interpreter re-traces per grid instance and would dominate the
offline suite's wall time.  Both backends read the SAME head-major
pooled layout, stacked over layers ``(L, num_pages, Hkv, page_size, D)``,
through the same tables, and take the layer as an index: the Pallas
kernels address that layer's pages in place, and the CPU reference works
on the layer's slice."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (paged_decode_bhd,
                                                           paged_prefill_bhd)
from repro.kernels.paged_attention.ref import (paged_decode_reference,
                                               paged_prefill_reference)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _layer(layer) -> jnp.ndarray:
    """A layer index as the kernels' (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def write_rows(pools: jnp.ndarray, rows: jnp.ndarray,
               block_tables: jnp.ndarray, context_lens: jnp.ndarray,
               layer) -> jnp.ndarray:
    """Row b's ``(Hkv, D)`` into layer ``layer`` of the stacked pools
    (L, P, Hkv, page, D) at position ``context_lens[b] - 1``, one
    in-place dynamic update a row; a row of context 0, or past its
    table, writes nothing.  rows (B, Hkv, D)."""
    _, _, Hkv, page, D = pools.shape
    npages = block_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32)
    for b in range(rows.shape[0]):
        pos = context_lens[b] - 1
        e = jnp.clip(pos // page, 0, npages - 1)
        at = (layer, block_tables[b, e], 0, jnp.maximum(pos, 0) % page, 0)
        old = jax.lax.dynamic_slice(pools, at, (1, 1, Hkv, 1, D))
        new = rows[b].reshape(old.shape).astype(pools.dtype)
        keep = (pos < 0) | (pos >= npages * page)
        pools = jax.lax.dynamic_update_slice(pools, jnp.where(keep, old, new),
                                             at)
    return pools


def paged_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                 block_tables: jnp.ndarray, context_lens: jnp.ndarray,
                 layer: jnp.ndarray):
    """Backend-dispatched paged decode, write-then-attend: q (B,H,D); k/v
    (B,Hkv,D), the new token's rows; pools stacked over layers
    (L, P, Hkv, page, D); block_tables (B, npages) int32; context_lens
    (B,), the new token included; layer, the pools' layer.  Row b's k/v
    go to position context_lens[b] - 1 in place (a row of context 0
    writes nothing), then the row attends over its context -> (out
    (B,H,D), new_k_pools, new_v_pools).  Safe to call inside an enclosing
    jit (the backend check is trace-time static)."""
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    k_pools, v_pools = (write_rows(pools, new, block_tables, context_lens,
                                   layer)
                        for pools, new in ((k_pools, k), (v_pools, v)))
    if _on_cpu():
        # the reference on the layer's slice: on the CPU speed does not
        # count
        out = paged_decode_reference(q, k_pools[layer], v_pools[layer],
                                     block_tables, context_lens)
    else:
        out = paged_decode_attention(q, k_pools, v_pools, block_tables,
                                     context_lens, layer, interpret=False)
    return out, k_pools, v_pools


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, k_pools: jnp.ndarray,
                           v_pools: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray, layer=0, *,
                           interpret: bool | None = None) -> jnp.ndarray:
    """q (B,H,D); pools (L, P, Hkv, page, D); block_tables (B, npages)
    int32; context_lens (B,) int32; layer, the pools' layer -> (B,H,D)."""
    if interpret is None:
        interpret = _on_cpu()
    B, H, D = q.shape
    Hkv = k_pools.shape[2]
    G = H // Hkv
    out = paged_decode_bhd(q.reshape(B, Hkv, G, D), k_pools, v_pools,
                           block_tables.astype(jnp.int32),
                           context_lens.astype(jnp.int32), _layer(layer),
                           interpret=interpret)
    return out.reshape(B, H, D)


def paged_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                  block_tables: jnp.ndarray, starts: jnp.ndarray,
                  lengths: jnp.ndarray, layer: jnp.ndarray):
    """Backend-dispatched fused paged prefill: q (B,c,H,D); k/v
    (B,c,Hkv,D); pools stacked over layers (L, P, Hkv, page, D);
    block_tables (B, maxp) int32; starts/lengths (B,); layer, the pools'
    layer -> (out (B,c,H,D), new_k_pools, new_v_pools).  Writes the
    chunk's rows into that layer (padded rows drop) and attends causally
    over [own pages ++ chunk].  Safe inside an enclosing jit (the backend
    check is trace-time static)."""
    if not _on_cpu():
        return paged_prefill_attention(q, k, v, k_pools, v_pools,
                                       block_tables, starts, lengths, layer,
                                       interpret=False)
    # the reference on the layer's slice: on the CPU speed does not count
    out, k_pool, v_pool = paged_prefill_reference(
        q, k, v, k_pools[layer], v_pools[layer],
        block_tables.astype(jnp.int32), starts.astype(jnp.int32),
        lengths.astype(jnp.int32))
    return (out, jax.lax.dynamic_update_index_in_dim(k_pools, k_pool, layer, 0),
            jax.lax.dynamic_update_index_in_dim(v_pools, v_pool, layer, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                            block_tables: jnp.ndarray, starts: jnp.ndarray,
                            lengths: jnp.ndarray, layer=0, *,
                            interpret: bool | None = None):
    """Pallas-kernel entry: q (B,c,H,D); k/v (B,c,Hkv,D); pools
    (L, P, Hkv, page, D); layer, the pools' layer -> (out (B,c,H,D),
    new_k_pools, new_v_pools)."""
    if interpret is None:
        interpret = _on_cpu()
    B, c, H, D = q.shape
    Hkv = k_pools.shape[2]
    G = H // Hkv
    qg = (q.reshape(B, c, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, c * G, D))
    out, new_k, new_v = paged_prefill_bhd(
        qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        k_pools, v_pools, block_tables.astype(jnp.int32),
        starts.astype(jnp.int32), lengths.astype(jnp.int32), _layer(layer),
        interpret=interpret)
    out = (out.reshape(B, Hkv, c, G, D).transpose(0, 2, 1, 3, 4)
           .reshape(B, c, H, D))
    return out, new_k, new_v


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def decode_attention_dense(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           context_lens: jnp.ndarray, *, page_size: int = 64,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Dense-cache decode through the paged kernel: the contiguous cache
    (B, S, Hkv, D) is re-laid out as a head-major page pool of one layer
    with identity block tables.  q (B,H,D) -> (B,H,D)."""
    B, S, Hkv, D = k.shape
    assert S % page_size == 0, (S, page_size)
    npages = S // page_size

    def as_pool(x):
        return (x.reshape(1, B, npages, page_size, Hkv, D)
                .transpose(0, 1, 2, 4, 3, 5)
                .reshape(1, B * npages, Hkv, page_size, D))
    k_pool, v_pool = as_pool(k), as_pool(v)
    block_tables = (jnp.arange(B)[:, None] * npages +
                    jnp.arange(npages)[None, :]).astype(jnp.int32)
    return paged_decode_attention(q, k_pool, v_pool, block_tables,
                                  context_lens, interpret=interpret)
