"""Paged attention kernels (Pallas, TPU target) — flash reduction over pages.

Two ops share the head-major pooled ``(num_pages, Hkv, page_size, D)``
layout and the scalar-prefetched block-table addressing:

* ``paged_decode_bhd`` — flash-decoding, one token per row.
* ``paged_prefill_bhd`` — the FUSED chunked-prefill step: per grid
  instance it (a) overlays the chunk's K/V rows onto the owned page it
  is visiting (one-hot MXU matmul, written back through
  ``input_output_aliases`` so the pools update in place), (b) streams
  that page's PRIOR rows (pos < chunk start) through the online-softmax
  reduction, and (c) folds the chunk's own rows in causally from the
  operands on the last page step.  Nothing like the old
  ``k_pool[block_tables]`` transient ``(B, max_pages*page, Hkv, D)``
  gather is ever materialized — HBM traffic is the owned pages once,
  plus one page-sized write per page the chunk lands on.

One grid instance per (batch, kv-head, page): the page index comes from the
*scalar-prefetched* block table (``PrefetchScalarGridSpec``), i.e. the
BlockSpec index_map dereferences ``block_tables[b, ip]`` — the TPU DMA
engine streams exactly the pages owned by the request, never the whole
pool.  The fp32 (acc, m, l) scratch persists across the page axis
(innermost, sequential on TPU); pages beyond ``context_len`` are skipped
with ``pl.when`` so short requests cost O(their length), which is exactly
the ``m``-linear decode cost the paper's cost model assumes.

This is the TPU-native adaptation of vLLM's CUDA PagedAttention: instead
of a warp-per-token gather, pages are DMA'd as (page_size, D) VMEM tiles
and the G=H/Hkv query heads of a kv head are batched into a single
(G, page_size) MXU matmul.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(block_tables_ref, context_lens_ref,  # scalar prefetch
               q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *,
               page_size: int, scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = context_lens_ref[b]
    in_range = ip * page_size < ctx

    @pl.when(in_range)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, page)
        pos = ip * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ip == npages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pp_kernel(block_tables_ref, starts_ref, lengths_ref,  # scalar prefetch
               q_ref, kc_ref, vc_ref, kp_ref, vp_ref,
               o_ref, nkp_ref, nvp_ref,
               acc_ref, m_ref, l_ref, *,
               page_size: int, chunk: int, groups: int, scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    maxp = pl.num_programs(2)
    pg = page_size
    start = starts_ref[b]
    ln = lengths_ref[b]
    # Pages the row genuinely owns after this chunk (>= 1 so the
    # redirected index below is always a live page of THIS row).  The
    # pool index_map re-aims every garbage tail entry (ip >= np_owned)
    # at the LAST owned page: consecutive grid steps then revisit the
    # same block index, which the pipeline treats as one resident block
    # (no refetch, one copy-out) — a tail step recomputes the identical
    # overlay instead of flushing stale bytes over a fresh write.
    np_owned = jnp.maximum((start + ln + pg - 1) // pg, 1)
    ipe = jnp.minimum(ip, np_owned - 1)
    base = ipe * pg

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ---- write: overlay the chunk rows that land in page `ipe` ----
    # chunk row r sits at absolute position start + r; it lands in this
    # page at slot j iff base + j == start + r (and r is a real row).
    # One-hot matmul keeps the page update branch-free on the MXU;
    # rows no chunk row maps to keep their prior content.
    kc = kc_ref[0, 0].astype(jnp.float32)               # (c, D)
    vc = vc_ref[0, 0].astype(jnp.float32)
    jidx = jax.lax.broadcasted_iota(jnp.int32, (pg, chunk), 0)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (pg, chunk), 1)
    hit = ((base + jidx == start + ridx)
           & (ridx < ln)).astype(jnp.float32)           # (pg, c)
    keep = 1.0 - jnp.sum(hit, axis=1, keepdims=True)    # (pg, 1)
    k_old = kp_ref[0, 0].astype(jnp.float32)            # (pg, D)
    v_old = vp_ref[0, 0].astype(jnp.float32)
    nkp_ref[0, 0] = (keep * k_old + hit @ kc).astype(nkp_ref.dtype)
    nvp_ref[0, 0] = (keep * v_old + hit @ vc).astype(nvp_ref.dtype)

    q2 = q_ref[0, 0].astype(jnp.float32) * scale        # (c*G, D)

    def _accum(s, vv):
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # ---- attend over prior pages: only rows written by PREVIOUS
    # chunks (pos < start) are live history; later slots of the last
    # page are stale until the overlay above lands, and the chunk's own
    # rows arrive from the kc/vc operands in the final step instead.
    @pl.when(ip * pg < start)
    def _pages():
        s = jax.lax.dot_general(q2, k_old, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos_k = ip * pg + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accum(jnp.where(pos_k < start, s, NEG_INF), v_old)

    @pl.when(ip == maxp - 1)
    def _chunk_and_finish():
        s = jax.lax.dot_general(q2, kc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (c*G, c)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
        rj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accum(jnp.where((rj <= qi) & (rj < ln), s, NEG_INF), vc)
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_bhd(q: jnp.ndarray, kc: jnp.ndarray, vc: jnp.ndarray,
                      k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                      block_tables: jnp.ndarray, starts: jnp.ndarray,
                      lengths: jnp.ndarray, *,
                      interpret: bool = False
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused paged-prefill: gather-write-attend in ONE pass.

    q (B, Hkv, c*G, D) — the chunk's queries, head-grouped; kc/vc
    (B, Hkv, c, D) — the chunk's new K/V rows; pools (P, Hkv, page, D).
    Returns (out (B, Hkv, c*G, D), new_k_pool, new_v_pool); the pools
    are updated IN PLACE (``input_output_aliases``) — only pages the
    block tables own are touched, every other page keeps its bytes.
    """
    B, Hkv, cG, D = q.shape
    c = kc.shape[2]
    G = cG // c
    P, _, page, _ = k_pool.shape
    maxp = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    def pool_idx(b, h, ip, bt, st, ln):
        np_owned = jnp.maximum((st[b] + ln[b] + page - 1) // page, 1)
        return (bt[b, jnp.minimum(ip, np_owned - 1)], h, 0, 0)

    kernel = functools.partial(_pp_kernel, page_size=page, chunk=c,
                               groups=G, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, cG, D), lambda b, h, ip, bt, st, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, c, D), lambda b, h, ip, bt, st, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, c, D), lambda b, h, ip, bt, st, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), pool_idx),
            pl.BlockSpec((1, 1, page, D), pool_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cG, D), lambda b, h, ip, bt, st, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), pool_idx),
            pl.BlockSpec((1, 1, page, D), pool_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((cG, D), jnp.float32),
            pltpu.VMEM((cG, 1), jnp.float32),
            pltpu.VMEM((cG, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, cG, D), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ),
        # flattened operand order: bt(0) st(1) ln(2) q(3) kc(4) vc(5)
        # k_pool(6) v_pool(7); pools alias outputs 1/2 (in-place update)
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )(block_tables, starts, lengths, q, kc, vc, k_pool, v_pool)


def paged_decode_bhd(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                     block_tables: jnp.ndarray, context_lens: jnp.ndarray, *,
                     interpret: bool = False) -> jnp.ndarray:
    """q (B, Hkv, G, D); pools (P, Hkv, page, D) -> out (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    P, _, page, _ = k_pool.shape
    npages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_pa_kernel, page_size=page, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, npages),
        in_specs=[
            # q: all G heads of this kv head
            pl.BlockSpec((1, 1, G, D), lambda b, h, ip, bt, cl: (b, h, 0, 0)),
            # k/v page selected through the block table
            pl.BlockSpec((1, 1, page, D), lambda b, h, ip, bt, cl: (bt[b, ip], h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), lambda b, h, ip, bt, cl: (bt[b, ip], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ip, bt, cl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, q, k_pool, v_pool)
