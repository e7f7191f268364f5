"""Jitted wrappers for paged attention (decode + fused prefill).

``paged_decode`` / ``paged_prefill`` are the engine's entry points
(``plane="paged"``): on TPU they run the Pallas kernels
(scalar-prefetched block tables, page-granular DMA, prefill writing the
chunk's K/V straight into the pools); on CPU they lower to jit-friendly
jnp block-table gathers (``ref``) instead of interpret-mode Pallas —
the interpreter re-traces per grid instance and would dominate the
offline suite's wall time.  Both backends read the SAME head-major
pooled layout ``(num_pages, Hkv, page_size, D)`` through the same
tables."""
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


def paged_decode(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                 block_tables: jnp.ndarray,
                 context_lens: jnp.ndarray) -> jnp.ndarray:
    """Backend-dispatched paged decode: q (B,H,D); pools
    (P, Hkv, page, D); block_tables (B, npages) int32; context_lens (B,)
    -> (B,H,D).  Safe to call inside an enclosing jit (the backend check
    is trace-time static)."""
    if _on_cpu():
        return paged_decode_reference(q, k_pool, v_pool,
                                      block_tables.astype(jnp.int32),
                                      context_lens.astype(jnp.int32))
    return paged_decode_attention(q, k_pool, v_pool, block_tables,
                                  context_lens, interpret=False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray, *,
                           interpret: bool | None = None) -> jnp.ndarray:
    """q (B,H,D); pools (P, Hkv, page, D); block_tables (B, npages) int32;
    context_lens (B,) int32 -> (B,H,D)."""
    if interpret is None:
        interpret = _on_cpu()
    B, H, D = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    out = paged_decode_bhd(qg, k_pool, v_pool,
                           block_tables.astype(jnp.int32),
                           context_lens.astype(jnp.int32),
                           interpret=interpret)
    return out.reshape(B, H, D)


def paged_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                  block_tables: jnp.ndarray, starts: jnp.ndarray,
                  lengths: jnp.ndarray):
    """Backend-dispatched fused paged prefill: q (B,c,H,D); k/v
    (B,c,Hkv,D); pools (P, Hkv, page, D); block_tables (B, maxp) int32;
    starts/lengths (B,) -> (out (B,c,H,D), new_k_pool, new_v_pool).
    Writes the chunk's rows into the pools (padded rows drop) and
    attends causally over [own pages ++ chunk].  Safe inside an
    enclosing jit (the backend check is trace-time static)."""
    if _on_cpu():
        return paged_prefill_reference(q, k, v, k_pool, v_pool,
                                       block_tables.astype(jnp.int32),
                                       starts.astype(jnp.int32),
                                       lengths.astype(jnp.int32))
    return paged_prefill_attention(q, k, v, k_pool, v_pool, block_tables,
                                   starts, lengths, interpret=False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                            block_tables: jnp.ndarray, starts: jnp.ndarray,
                            lengths: jnp.ndarray, *,
                            interpret: bool | None = None):
    """Pallas-kernel entry: q (B,c,H,D); k/v (B,c,Hkv,D); pools
    (P, Hkv, page, D) -> (out (B,c,H,D), new_k_pool, new_v_pool)."""
    if interpret is None:
        interpret = _on_cpu()
    B, c, H, D = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    qg = (q.reshape(B, c, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, c * G, D))
    out, new_k, new_v = paged_prefill_bhd(
        qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        k_pool, v_pool, block_tables.astype(jnp.int32),
        starts.astype(jnp.int32), lengths.astype(jnp.int32),
        interpret=interpret)
    out = (out.reshape(B, Hkv, c, G, D).transpose(0, 2, 1, 3, 4)
           .reshape(B, c, H, D))
    return out, new_k, new_v


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def decode_attention_dense(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           context_lens: jnp.ndarray, *, page_size: int = 64,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Dense-cache decode through the paged kernel: the contiguous cache
    (B, S, Hkv, D) is re-laid out as a head-major page pool with
    identity block tables.  q (B,H,D) -> (B,H,D)."""
    B, S, Hkv, D = k.shape
    assert S % page_size == 0, (S, page_size)
    npages = S // page_size

    def as_pool(x):
        return (x.reshape(B, npages, page_size, Hkv, D)
                .transpose(0, 1, 3, 2, 4)
                .reshape(B * npages, Hkv, page_size, D))
    k_pool, v_pool = as_pool(k), as_pool(v)
    block_tables = (jnp.arange(B)[:, None] * npages +
                    jnp.arange(npages)[None, :]).astype(jnp.int32)
    return paged_decode_attention(q, k_pool, v_pool, block_tables,
                                  context_lens, interpret=interpret)
