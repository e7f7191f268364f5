"""Paged attention kernels (Pallas, TPU target) — flash reduction over pages.

Two ops share the head-major pools, stacked over layers
``(L, num_pages, Hkv, page_size, D)``, and the scalar-prefetched
addressing: the layer index and the block table.  A kernel reads and
writes pages of its layer of the whole stack in place, so a layer loop
carries the stacks and never slices one layer's pool out.

* ``paged_decode_bhd`` — flash-decoding, one token per row, over pages
  that already hold the row's new token (``write_rows`` puts it there
  first, in place: write-then-attend).
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

Both find pages through the *scalar-prefetched* block table
(``PrefetchScalarGridSpec``): a BlockSpec index_map, or the kernel's
own copy, dereferences ``block_tables[b, ...]``, so the TPU DMA engine
streams the pages a row owns, never the whole pool.  The fp32 (acc, m,
l) scratch persists across the innermost grid axis, which runs in order
on the chip.

Decode runs one grid instance per (row, block of ``ppb`` table
entries), for all kv heads at once: ``ppb`` comes from the page size
(``pages_per_block``: about 256 positions, at most 16 pages).  An
instance attends q ``(Hkv, G, D)`` against the block's
``(Hkv, ppb * page, D)`` in one batched matmul over the kv heads.  Where
the pool's tiles allow it (D a multiple of 128, pages of a multiple of 8
rows) the pools stay in HBM and the kernel copies each live page's
contiguous ``(Hkv, page, D)`` slab itself, block j+1's while block j
computes; a page at or past ``context_len`` is never copied.  Elsewhere
each page of a block is a pipelined operand of its own, re-aimed past
the context at a page it already holds, so nothing is copied for it.
A block wholly past the context skips its compute but still costs a
grid step, which is why a block holds many pages.  Prefill still runs
one grid instance per (row, kv head, page).

This is the TPU-native adaptation of vLLM's CUDA PagedAttention: instead
of a warp-per-token gather, pages are DMA'd as VMEM tiles and the
G=H/Hkv query heads of a kv head are batched into one MXU matmul.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 256      # decode: context positions per grid instance,
MAX_BLOCK_PAGES = 16    # in at most this many pages


def pages_per_block(page_size: int, npages: int) -> int:
    """Block-table entries one decode grid instance covers: about
    ``BLOCK_TOKENS`` positions, at most ``MAX_BLOCK_PAGES``, at least one
    page, at most the table."""
    return max(1, min(npages, MAX_BLOCK_PAGES, BLOCK_TOKENS // page_size))


def _pd_kernel(block_tables_ref, context_lens_ref, layer_ref,  # prefetch
               q_ref, *refs, page_size: int, ppb: int, npages: int,
               scale: float, dma: bool):
    if dma:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = refs
    else:
        k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
        o_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nblk = pl.num_programs(1)
    block = ppb * page_size
    layer = layer_ref[0]
    ctx = jnp.minimum(context_lens_ref[b], npages * page_size)
    live_pages = (ctx + page_size - 1) // page_size

    def live_in(blk):
        """How many of block ``blk``'s pages hold context."""
        return jnp.clip(live_pages - blk * ppb, 0, ppb)

    def page_copies(blk, i):
        """K and V copies of block ``blk``'s i-th page, one contiguous
        (Hkv, page, D) slab each, into that block's buffer slot."""
        page = block_tables_ref[b, blk * ppb + i]
        slot = blk % 2
        rows = pl.ds(i * page_size, page_size)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[slot, :, rows],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, :, rows],
                                      sems.at[1, slot]))

    def fetch(blk):
        n = live_in(blk)
        for i in range(ppb):
            @pl.when(i < n)
            def _start():
                for c in page_copies(blk, i):
                    c.start()

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if dma:
            fetch(0)

    if dma:
        # double buffer: block j+1's pages stream in while block j computes
        @pl.when(j + 1 < nblk)
        def _prefetch():
            fetch(j + 1)

    def block_kv():
        """The block's K and V, (Hkv, block, D) in float32."""
        if not dma:
            return [jnp.concatenate([r[0].astype(jnp.float32) for r in rs],
                                    axis=1) for rs in (k_refs, v_refs)]
        slot = j % 2
        n = live_in(j)
        for i in range(ppb):
            @pl.when(i < n)
            def _wait():
                for c in page_copies(j, i):
                    c.wait()

            # a page past the context is never copied: its V rows get
            # weight 0, and must not hold stale NaNs
            @pl.when(i >= n)
            def _clear():
                rows = pl.ds(i * page_size, page_size)
                v_buf[slot, :, rows] = jnp.zeros_like(v_buf[slot, :, rows])
        return k_buf[slot].astype(jnp.float32), v_buf[slot].astype(jnp.float32)

    @pl.when(j * block < ctx)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale              # (Hkv, G, D)
        k, v = block_kv()                                      # (Hkv, block, D)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)  # (Hkv, G, block)
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < ctx, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pp_kernel(block_tables_ref, starts_ref, lengths_ref, layer_ref,  # prefetch
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
                      k_pools: jnp.ndarray, v_pools: jnp.ndarray,
                      block_tables: jnp.ndarray, starts: jnp.ndarray,
                      lengths: jnp.ndarray, layer: jnp.ndarray, *,
                      interpret: bool = False
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused paged-prefill: gather-write-attend in ONE pass.

    q (B, Hkv, c*G, D) — the chunk's queries, head-grouped; kc/vc
    (B, Hkv, c, D) — the chunk's new K/V rows; pools stacked over layers
    (L, P, Hkv, page, D), of which layer ``layer`` ((1,) int32) is read
    and written.  Returns (out (B, Hkv, c*G, D), new_k_pools,
    new_v_pools); the pools are updated IN PLACE
    (``input_output_aliases``) — only that layer's pages the block tables
    own are touched, every other byte keeps its value.
    """
    B, Hkv, cG, D = q.shape
    c = kc.shape[2]
    G = cG // c
    page = k_pools.shape[3]
    maxp = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    def pool_idx(b, h, ip, bt, st, ln, ly):
        np_owned = jnp.maximum((st[b] + ln[b] + page - 1) // page, 1)
        return (ly[0], bt[b, jnp.minimum(ip, np_owned - 1)], h, 0, 0)

    def row_idx(b, h, ip, bt, st, ln, ly):
        return (b, h, 0, 0)

    pool = pl.BlockSpec((None, 1, 1, page, D), pool_idx)
    kernel = functools.partial(_pp_kernel, page_size=page, chunk=c,
                               groups=G, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, cG, D), row_idx),
            pl.BlockSpec((1, 1, c, D), row_idx),
            pl.BlockSpec((1, 1, c, D), row_idx),
            pool,
            pool,
        ],
        out_specs=[pl.BlockSpec((1, 1, cG, D), row_idx), pool, pool],
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
            jax.ShapeDtypeStruct(k_pools.shape, k_pools.dtype),
            jax.ShapeDtypeStruct(v_pools.shape, v_pools.dtype),
        ),
        # flattened operand order: bt(0) st(1) ln(2) layer(3) q(4) kc(5)
        # vc(6) k_pools(7) v_pools(8); pools alias outputs 1/2 (in place)
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
    )(block_tables, starts, lengths, layer, q, kc, vc, k_pools, v_pools)


def paged_decode_bhd(q: jnp.ndarray, k_pools: jnp.ndarray,
                     v_pools: jnp.ndarray, block_tables: jnp.ndarray,
                     context_lens: jnp.ndarray, layer: jnp.ndarray, *,
                     interpret: bool = False) -> jnp.ndarray:
    """q (B, Hkv, G, D); pools stacked over layers (L, P, Hkv, page, D),
    of which layer ``layer`` ((1,) int32) is read -> out (B, Hkv, G, D).
    A row of context 0 gets zeros."""
    B, Hkv, G, D = q.shape
    page = k_pools.shape[3]
    npages = block_tables.shape[1]
    ppb = pages_per_block(page, npages)
    scale = 1.0 / math.sqrt(D)
    # Mosaic slices a ref only along whole tiles, so the kernel's own
    # copies need D a multiple of 128 lanes and pages of a multiple of 8
    # rows; other shapes pipeline each page as an operand of its own
    dma = D % 128 == 0 and page % 8 == 0

    def page_spec(i):
        """The i-th page of block j, all kv heads.  An entry at or past
        the context re-aims at the row's last live page of the same
        operand, so the pipeline, seeing its block index unchanged,
        copies nothing; a row of context 0 stays on its first entry."""
        def index(b, j, bt, cl, ly):
            live = jnp.maximum(
                (jnp.minimum(cl[b], npages * page) + page - 1) // page, 1)
            e = jnp.minimum(jnp.minimum(j, (live - 1) // ppb) * ppb + i,
                            live - 1)
            return (ly[0], bt[b, e], 0, 0, 0)
        return pl.BlockSpec((None, 1, Hkv, page, D), index)

    if dma:
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        pool_specs, pools = [hbm, hbm], (k_pools, v_pools)
        buf = pltpu.VMEM((2, Hkv, ppb * page, D), k_pools.dtype)
        copy_scratch = [buf, buf, pltpu.SemaphoreType.DMA((2, 2))]
    else:
        # each pool goes in once per page of a block, one pipelined
        # operand each; a custom call's operands are references, so no
        # pool is copied
        pages = [page_spec(i) for i in range(ppb)]
        pool_specs = pages + pages
        pools = (k_pools,) * ppb + (v_pools,) * ppb
        copy_scratch = []
    row = pl.BlockSpec((1, Hkv, G, D), lambda b, j, bt, cl, ly: (b, 0, 0, 0))
    kernel = functools.partial(_pd_kernel, page_size=page, ppb=ppb,
                               npages=npages, scale=scale, dma=dma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pl.cdiv(npages, ppb)),
        in_specs=[row, *pool_specs],
        out_specs=row,
        scratch_shapes=[
            *copy_scratch,
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, layer, q, *pools)
