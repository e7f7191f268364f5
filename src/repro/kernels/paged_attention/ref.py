"""Pure-jnp oracles for paged attention (decode + fused prefill).

``paged_prefill_reference`` is ALSO the engine's CPU lowering: it is the
gather-write-attend formulation the paged plane used inline before the
fused kernel existed (PR 8), kept bit-for-bit so token-identity
contracts against the batched plane hold on the CPU backend, and so the
Pallas kernel has an oracle to parity-test against in interpret mode.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def paged_decode_reference(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray) -> jnp.ndarray:
    """q (B,H,D); pools (P, Hkv, page, D); block_tables (B, npages);
    context_lens (B,) -> out (B,H,D)."""
    B, H, D = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    k = gather_pages(k_pool, block_tables)          # (B, S, Hkv, D)
    v = gather_pages(v_pool, block_tables)
    S = k.shape[1]
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    valid = jnp.arange(S)[None] < context_lens[:, None]  # (B, S)
    s = jnp.where(valid[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w.astype(v.dtype), v)
    return out.reshape(B, H, D)


def gather_pages(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Position-ordered view of each row's pages: a (P, Hkv, page, D)
    pool through (B, npages) tables -> (B, npages*page, Hkv, D)."""
    B, npages = block_tables.shape
    _, Hkv, pg, D = pool.shape
    return (pool[block_tables].transpose(0, 1, 3, 2, 4)
            .reshape(B, npages * pg, Hkv, D))


def scatter_rows(pool: jnp.ndarray, dest: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Write rows into a (P, Hkv, page, D) pool at flat token positions
    ``dest`` = page * page_size + slot (OOB = drop).  rows (..., Hkv, D);
    dest (...,) int32."""
    _, Hkv, pg, D = pool.shape
    dest = dest.reshape(-1)
    return pool.at[dest // pg, :, dest % pg].set(
        rows.reshape(-1, Hkv, D), mode="drop")


def paged_prefill_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                            block_tables: jnp.ndarray, starts: jnp.ndarray,
                            lengths: jnp.ndarray
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather-write-attend oracle for the fused prefill kernel.

    q (B, c, H, D); k/v (B, c, Hkv, D) — the chunk's projected rows;
    pools (P, Hkv, page, D); block_tables (B, maxp); starts/lengths (B,).
    Returns (attn out (B, c, H, D), new_k_pool, new_v_pool).

    Table slot j covers absolute positions [j*page, (j+1)*page), so the
    gathered per-row view IS position order — the chunk is written in
    place and attended causally, exactly the dense plane's
    write-then-attend (same buffer width and reduction order, so the
    math matches that plane bit-for-bit; stale rows beyond each query's
    position never enter the mask).  Padded rows (index >= length)
    route out of bounds and drop — pool bytes of other requests are
    untouchable by construction.
    """
    B, c, H, D = q.shape
    P, Hkv, pg, _ = k_pool.shape
    maxp = block_tables.shape[1]
    Smax = maxp * pg
    G = H // Hkv
    positions = starts[:, None] + jnp.arange(c)[None, :]        # (B, c)
    valid = jnp.arange(c)[None, :] < lengths[:, None]           # (B, c)

    kg = gather_pages(k_pool, block_tables)                     # (B, Smax, Hkv, D)
    vg = gather_pages(v_pool, block_tables)
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, c))
    loc = jnp.where(valid, positions, Smax)                     # OOB drop
    kg = kg.at[rows, loc].set(k, mode="drop")
    vg = vg.at[rows, loc].set(v, mode="drop")

    mask = jnp.arange(Smax)[None, None, :] <= positions[:, :, None]
    qg = q.reshape(B, c, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(vg.dtype), vg)
    out = out.reshape(B, c, H, D)

    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(positions // pg, 0, maxp - 1), axis=1)
    dest = jnp.where(valid, page_idx * pg + positions % pg, P * pg)
    return out, scatter_rows(k_pool, dest, k), scatter_rows(v_pool, dest, v)
