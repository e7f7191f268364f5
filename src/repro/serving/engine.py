"""Continuous-batching serving engine — REAL JAX execution of the paper's
schedules (the "deployment" path of Fig. 1; the simulator is the blue
path).

The engine drives the unified ``Scheduler`` (Algorithm 1) against an
actual model.  Memory accounting (the scheduler's M) is backed by a
``PagedAllocator`` at page granularity: the scheduler charges
page-rounded occupancy against the allocator's page-rounded capacity
(``ceil(M/page_size)`` pages), so a schedule the control plane admits is
allocator-feasible by construction — ``OutOfPagesError`` is unreachable,
internal fragmentation is charged up front, never discovered mid-batch.

Execution plane — THREE data planes, selected by ``EngineConfig.plane``:

* ``"batched"`` (default) — per-request contiguous cache slots; all
  prefill work of a scheduler batch runs as rounds of ONE
  ``prefill_many`` call over the full (nslots, bucket) token grid.
  Chunks are padded to a fixed bucket ladder (powers of two up to
  ``chunk``) and an explicit per-row ``length`` mask is threaded through
  ``models.model.prefill_chunk`` down to the attention / SSM / RWKV
  internals, so one compiled XLA signature per bucket serves every
  chunk size, request count, and prompt length: the number of distinct
  compiles is a small constant (see ``Engine.num_compiles`` and the
  compile-count regression test).  Inactive rows carry length 0 and are
  provably inert.
* ``"paged"`` — the allocator's block tables become the PHYSICAL memory
  layout (PR 4): attention KV lives in shared per-layer head-major page
  pools ``(num_pages, Hkv, page_size, D)`` (``serving.paged_plane``), prefill
  writes K/V through the block table into owned pages, and decode runs
  the ``kernels.paged_attention`` flash-decoding Pallas kernel over
  scalar-prefetched block tables (jnp gather fallback on CPU).  Pooled
  pages unlock what contiguous slots cannot express:

  - *page-level partial preemption* — on memory pressure the scheduler
    sheds only a victim's TAIL pages (``SchedulerConfig.
    partial_preempt``; the §8 SRF idea at sub-request granularity),
    with the Fig. 8 crossover deciding swap-vs-recompute PER PAGE RUN;
    swapped runs live in the ``KVSwapStore`` as ``PageRunEntry``s and
    are restored before the victim's next compute step.
  - *shared-prefix reuse* — full prompt pages are published to a
    refcounted prefix registry keyed by chained content hashes; a new
    request whose prompt matches maps the SAME physical pages
    (copy-on-write guarded via ``PagedAllocator.ensure_private``) and
    skips their prefill compute.  When the pool runs short,
    registry-cached pages are reclaimed in the eviction order of a
    PLUGGABLE replacement policy (``SchedulerConfig.cache_policy`` /
    ``EngineConfig.cache_policy``: ``lru``, or ``break_even`` — the §6
    five-minute rule scored per entry), so they never shrink
    schedulable capacity; entries whose page a live table still maps
    are skipped (evicting them frees nothing).
  - *host demotion tier* (``cache_demotion``) — evicted prefix pages
    are demoted into the ``KVSwapStore`` as refcount-free
    ``PrefixPageEntry`` snapshots instead of discarded; a registry hit
    on a host-resident prefix PROMOTES the page back through the swap
    path, charged ``swap_time`` in virtual time (mirrored by the
    simulator's ``PrefixTierSim`` shadow) and measured on the wall —
    every KV access resolves along the Fig. 8 spectrum: GPU-resident <
    host swap-in < recompute.

  Sliding-window and SSM/RWKV state is O(1) per request and stays
  slot-resident: for those families ``plane="paged"`` keeps the batched
  data plane and retains the page-rounded control plane.
* ``"legacy"`` — the PR-1 per-request chunk loop with exact (unpadded)
  shapes: every distinct tail length triggers a fresh XLA compile.
  Kept as the honest baseline for ``benchmarks/fig_engine_wall.py``.

Sampling is FUSED into the jitted steps: greedy argmax over the real
vocabulary happens on device and only (nslots,) int32 token ids ever
cross to the host — the full (nslots, vocab) logits array is never
materialized off-device.  ``EngineConfig.decode_append="deferred"``
routes decode through ``model.decode_step_deferred`` (one cache scatter
per step instead of one per layer).

Preemption supports BOTH §5.4 restoration paths, selected by
``SchedulerConfig.preempt_mode``:

* ``recompute`` — the victim's slot is freed and its KVs discarded; on
  re-admission it pays a full refill prefill (the §3 refill).
* ``swap`` — the victim's slot slice (every cache leaf, including the
  position index and recurrent SSM state) is snapshotted to a host-side
  ``KVSwapStore``; on re-admission the snapshot is written back into a
  free slot and generation continues where it stopped —
  ``Request.remaining_prefill`` sees the restored KVs, so no refill runs.
  If the store's ``EngineConfig.swap_bytes`` capacity is exhausted the
  victim falls back to discard-and-recompute for that preemption.
* ``auto`` — per-victim Fig. 8 decision via the cost model
  (``swap_time`` vs ``kv_projection_time``/``recompute_time``).

Swap-out transfers are ASYNC by default (``EngineConfig.async_swap``):
the victim's snapshot is computed on device (a fresh buffer — later
cache/pool updates cannot alias it), ``copy_to_host_async`` starts the
D2H transfer off the critical path, and the snapshot is finalized
(double-buffered, at most two in flight) at the next step boundary or
on demand when the victim is re-admitted within the same drain window.
This covers ALL host-bound KV traffic: the slot planes' whole-slot
slices, the pooled plane's page-run suspend/shed gathers (whose fresh
buffers are what let the freed pages be reused in the very same step),
and the prefix tier's page demotions.  Store capacity is charged at
enqueue time from array metadata — a full store still falls back to
recompute synchronously — and virtual-time charges are identical to
the sync path.

Virtual time charges ``cost_model.swap_time`` for each swap-out and
swap-in, mirroring the simulator, so simulated and engine schedules
agree.  Measured wall times of the host transfers are tracked in
``Engine.swap_stats`` (the fig08 validation column); per-batch measured
wall time lands in ``BatchLog.wall_s``.

Correctness contract (tested): scheduling, chunking, batching, padding
and preemption — under recompute, swap, AND auto — NEVER change the
generated tokens, exactly the paper's "standard inference optimization
techniques that do not affect inference outputs".  At the models layer
the padded cache state is bit-identical to the unpadded call for the
pure-attention family; for the recurrent families (SSM/RWKV) padding
changes the inner scans' chunk factorization, so states agree to float
reduction-order noise (~1e-7 relative) — the same order as the
chunked-vs-full divergence the parity oracle already tolerates, below
anything that flips a greedy argmax in practice.

Failure model (DBMS-style step transactions) — every scheduler batch
runs as an atomic STEP TRANSACTION (``serving.txn``): allocator,
swap store, scheduler, request state machines, and the engine-local
slot/output maps are snapshotted at batch start and rolled back as one
unit on a mid-step failure.  Failures are injected deterministically by
a seeded ``serving.faults.FaultPlan`` (``EngineConfig.faults``, written
through to the SchedulerConfig so the simulator draws the identical
schedule) and handled along a three-rung degradation ladder:

1. **retry in place** — transient swap-store write failures are retried
   with bounded exponential backoff (``distributed.fault_tolerance.
   run_with_retries`` with an injectable virtual-sleep clock; the
   schedule lands in ``swap_stats["backoff_s"]``, never on the wall).
2. **rollback + retry the step** — a transient device fault at page
   allocation (``FaultError``) aborts the attempt; the step transaction
   restores batch-start state and the step re-runs (allocation faults
   are keyed by attempt, so the retry draws fresh).  A real
   ``OutOfPagesError`` rolls back too — invariants stay green — but
   re-raises: it signals an accounting bug, not a survivable fault.
3. **degrade to recompute** — host snapshots are CRC-sealed at drain
   time (``swap_store.seal_entry``) and verified at swap-in / promote;
   a corrupt entry (``IntegrityError``) triggers rollback, the entry is
   dropped, its request degrades to a §3-style recompute, and the step
   retries.  Wrong tokens are never served: chaos tests assert outputs
   under any fault schedule are byte-identical to the fault-free run.
   Permanent store failures (``PermanentStoreError``, a
   ``SwapStoreFullError`` subclass) ride the existing full-store
   fallback: drop the snapshot, recompute.

Abort history is recorded in ``Engine.recovery_stats`` (rollbacks,
alloc faults, integrity failures, degraded recomputes, straggler
requeues, aborted wall time) — deliberately OUTSIDE the transaction, so
rolling back never erases the record of the rollback itself.  In-step
fault counters (retries, backoff, permanent failures, prefix integrity)
live in ``swap_stats`` INSIDE the transaction, so an aborted attempt's
draws are not double-counted by its retry.  ``StragglerMonitor``
(``EngineConfig.straggler_factor``) optionally requeues all running
requests when a step's wall time blows past the cost-model prediction.

State-safety analysis — the three protocols above are AUDITED
STATICALLY by ``repro.analysis`` (``make analyze``, the check.sh static
stage): ``txn-coverage`` diffs every ``self.*`` attribute mutated on a
path reachable from ``step()`` against what ``_begin_txn`` snapshots
(plus the participant/``Request`` write-sets against ``serving.txn``'s
capture lists), so adding engine state without adding it to the
transaction is a blocking finding, not a latent rollback hole; the few
attributes that deliberately survive rollback (measured wall, recovery
accounting, attempt/step identity, straggler inputs) each carry an
inline ``allow-txn-coverage`` stating why.  ``stat-mirror`` diffs the
``swap_stats``/``recovery_stats``/``BatchLog`` key sets written here
against the simulator's ``PrefixTierSim``/``_FaultMirror`` shadows
(keys are ``core.stat_keys`` constants; sanctioned asymmetries live in
that module's allowlist sets), and ``async-drain`` enforces the swap
protocol: every ``copy_to_host_async`` registers in a ``_pending_*``
buffer, payload reads sit behind a ``_drain_*`` boundary,
``EngineResult`` is built on fully-drained state, and drains are never
jit-reachable.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cost_model import BatchSpec, CostModel
from repro.core.invariants import invariant
from repro.core.kvcache import (OutOfPagesError, PagedAllocator,
                                attach_prefix_run, chain_keys)
from repro.core.policies import make_replacement_policy
from repro.core.request import Request
from repro.core.scheduler import Scheduler
from repro.core.simulator import BatchLog, SimResult
from repro.core import stat_keys as SK
from repro.distributed.fault_tolerance import (StragglerMonitor,
                                               run_with_retries)
from repro.models import model as M
from repro.serving.faults import (FaultError, FaultPlan, IntegrityError,
                                  PermanentStoreError, TransientStoreError)
from repro.serving.paged_plane import build_paged_fns, paged_supported
from repro.serving.serve_step import build_prefill_chunk_fn
from repro.serving.swap_store import (KVSwapStore, SwapEntry,
                                      SwapStoreFullError, flip_bit,
                                      seal_entry, verify_entry)
from repro.serving.txn import StepTxn, begin_step_txn

# host spans of the serving step: ``repro.engine.<part>`` annotations on
# the JAX profiler's clock, the clock of the device ops in its trace.
# With no profiler session open an annotation costs under a
# microsecond, so they stay on; none sits inside jitted code.
_span = jax.profiler.TraceAnnotation


def _spanned(name: str) -> Callable:
    """Method decorator: the whole call is one ``name`` span."""
    return functools.partial(jax.profiler.annotate_function, name=name)

# hard ceiling on fault-recovery retries of one step: content-keyed
# draws are idempotent, so only attempt-keyed allocation faults can
# chain — at any sane p_alloc the chance of 50 in a row is nil, and a
# loop this long means the fault plan (or a repair) is broken
_MAX_STEP_ATTEMPTS = 50


@dataclass
class EngineConfig:
    nslots: int = 8
    cache_len: int = 256          # per-slot context capacity (tokens)
    chunk: int = 64               # chunked-prefill chunk size
    page_size: int = 1            # allocator granularity (1 = token-exact,
    #                               matching the scheduler's M accounting)
    impl: str = "reference"       # attention backend
    moe_impl: str = "dense"       # chunk-invariant dispatch for parity
    swap_bytes: Optional[int] = None   # host swap-store capacity (None =
    #                                    unbounded); a full store makes the
    #                                    victim fall back to recompute
    check_invariants: bool = True
    # --- execution plane (PR 2 / PR 4) --------------------------------- #
    plane: str = "batched"        # "batched" (shape-stable bucketed
    #                               prefill_many over contiguous slots)
    #                             | "paged" (pooled per-layer KV pages +
    #                               block tables; slot-resident fallback
    #                               for bounded-state families)
    #                             | "legacy" (PR-1 per-request
    #                               exact-shape chunk loop)
    prefix_sharing: bool = True   # paged plane: map identical full
    #                               prompt pages to the same physical
    #                               pages via the refcounted registry
    prefix_lookup: Optional[str] = None  # "trie" (radix-trie longest-
    #                               prefix match, partial hits) |
    #                               "exact" (all-or-nothing ablation:
    #                               attach only when EVERY queried page
    #                               resolves on device).  None keeps the
    #                               SchedulerConfig's choice; set, it is
    #                               written through (like page_size) so
    #                               the simulator shadow matches
    # --- page-pool cache replacement (§6 five-minute rule) ------------- #
    cache_policy: Optional[str] = None   # "lru" | "break_even" — None
    #                               keeps the SchedulerConfig's choice;
    #                               set, it is written through to the
    #                               scheduler (like page_size) so both
    #                               planes agree on one policy
    cache_demotion: Optional[bool] = None  # evicted prefix pages demote
    #                               to the host KVSwapStore instead of
    #                               being discarded; registry hits on
    #                               host-resident prefixes promote back
    #                               through the swap path (charged
    #                               swap_time).  None = scheduler's.
    decode_append: str = "inline"   # "inline" | "deferred" (one cache
    #                                 scatter per step, §Perf cell A)
    async_swap: bool = True       # double-buffered async swap-out D2H —
    #                               covers the slot planes' whole-slot
    #                               snapshots, the prefix tier's page
    #                               demotions, AND the pooled plane's
    #                               page-run suspend/shed snapshots
    share_jits: bool = False      # reuse process-global jitted plane
    #                               steps (keyed by model config) across
    #                               Engine instances, so a fresh engine
    #                               with a known config pays ZERO XLA
    #                               compiles.  Off by default: sharing
    #                               makes ``num_compiles`` a process-
    #                               cumulative count, which the per-
    #                               engine compile budgets / constancy
    #                               tests must not see.  Benchmarks turn
    #                               it on (with ``warmup()``) so timed
    #                               windows price compute, not
    #                               backend_compile
    min_bucket: int = 8           # smallest tail bucket of the ladder
    # --- failure model (step transactions + fault injection) ----------- #
    faults: Optional[Any] = None  # a serving.faults.FaultSpec; written
    #                               through to SchedulerConfig.faults
    #                               (like page_size) so engine and
    #                               simulator draw one fault schedule.
    #                               Typed Any: the core scheduler config
    #                               mirrors the field and must not
    #                               import the serving layer
    straggler_factor: Optional[float] = None  # arm StragglerMonitor: a
    #                               step whose measured wall time
    #                               exceeds factor x the cost model's
    #                               predicted dt requeues every running
    #                               request through the scheduler's
    #                               preemption path.  Wall-clock
    #                               dependent — leave None (off) in
    #                               parity/chaos tests


def _bucket_ladder(chunk: int, min_bucket: int) -> List[int]:
    """Fixed padding targets: powers of two in [min_bucket, chunk), plus
    ``chunk`` itself.  Every prefill sub-chunk is padded UP to the
    smallest bucket that holds it, so at most ``len(ladder)`` distinct
    prefill signatures ever compile."""
    b = 1
    while b < min(min_bucket, chunk):
        b *= 2
    ladder = []
    while b < chunk:
        ladder.append(b)
        b *= 2
    ladder.append(chunk)
    return ladder


def _slot_axis(leaf: jnp.ndarray) -> int:
    """Cache leaves are (L, B, ...) except index (B,)."""
    return 0 if leaf.ndim == 1 else 1


# --------------------------------------------------------------------- #
# plane step builders — module level so ``EngineConfig.share_jits`` can
# cache the JITTED closures per model config: every Engine with the same
# (cfg, impl, moe_impl) then shares one XLA compile cache, and a warmed
# signature is never paid for twice in a process (benchmarks construct
# several engines per figure; without sharing each re-compiles the same
# cells inside its first — often timed — steps)
# --------------------------------------------------------------------- #

def _mask_merge(active, new_cache, old_cache):
    def merge(new, old):
        ax = _slot_axis(new)
        m = active.reshape((1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
        return jnp.where(m, new, old)
    return jax.tree.map(merge, new_cache, old_cache)


def _slot_slice_fn(cache, slot):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1,
                                               _slot_axis(a)), cache)


def _slot_write_fn(cache, upd, slot):
    return jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_slice_in_dim(
            a, u, slot, _slot_axis(a)), cache, upd)


def _reset_slot_fn(cache, slot):
    zeroed = jax.tree.map(
        lambda a: jnp.zeros_like(
            jax.lax.dynamic_slice_in_dim(a, slot, 1, _slot_axis(a))),
        cache)
    return _slot_write_fn(cache, zeroed, slot)


def _make_slot_fns():
    """Fresh per-engine aliases of the slot helpers.  jax keys its
    compiled-executable cache on the wrapped callable, so jitting the
    module-level functions directly would leak compile counts (and
    ``num_compiles``) between engines even with ``share_jits=False``."""
    def slot_slice(cache, slot):
        return _slot_slice_fn(cache, slot)

    def slot_write(cache, upd, slot):
        return _slot_write_fn(cache, upd, slot)

    def reset_slot(cache, slot):
        return _reset_slot_fn(cache, slot)
    return slot_slice, slot_write, reset_slot


def _make_legacy_prefill(cfg: ModelConfig, impl: str, moe_impl: str):
    def prefill_one(params, cache, slot, tokens):
        sl = _slot_slice_fn(cache, slot)
        logits, new_sl = M.prefill_chunk(cfg, params, tokens, sl,
                                         impl=impl, moe_impl=moe_impl)
        return logits[0], _slot_write_fn(cache, new_sl, slot)
    return prefill_one


def _make_batched_prefill(cfg: ModelConfig, impl: str, moe_impl: str):
    chunk_fn = build_prefill_chunk_fn(cfg, impl=impl, moe_impl=moe_impl)
    vocab = cfg.vocab_size

    def prefill_many(params, cache, tokens, lengths):
        """One batched bucketed chunk round over ALL slots.
        tokens (nslots, bucket); lengths (nslots,), 0 = inert row.
        Returns (greedy token ids (nslots,), merged cache) — fused
        on-device sampling, full logits never leave the device."""
        logits, new_cache = chunk_fn(params, tokens, cache, lengths)
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, _mask_merge(lengths > 0, new_cache, cache)
    return prefill_many


def _make_decode(cfg: ModelConfig, impl: str, moe_impl: str,
                 decode_append: str):
    decode_step = (M.decode_step_deferred if decode_append == "deferred"
                   else M.decode_step)
    vocab = cfg.vocab_size

    def decode_many(params, cache, tokens, mask):
        logits, new_cache = decode_step(cfg, params, tokens, cache,
                                        impl=impl, moe_impl=moe_impl)
        toks = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return toks, _mask_merge(mask, new_cache, cache)
    return decode_many


def _make_paged_step_fns(cfg: ModelConfig, moe_impl: str):
    """The pooled plane's two step programs.  Their function names are
    their XLA module names (``jit_paged_prefill_step``,
    ``jit_paged_decode_step``), which name them in a profiler trace."""
    pf, paged_decode_step = build_paged_fns(cfg, moe_impl=moe_impl)

    def paged_prefill_step(params, k_pools, v_pools, grid, block_tables):
        # one coalesced host->device transfer per round: the tokens,
        # lengths and starts of every slot ride a single (nslots,
        # bucket+2) int32 grid — [toks | lens | starts] — unpacked here
        # (on-device slices are free next to three separate uploads)
        toks = grid[:, :-2]
        lens = grid[:, -2]
        starts = grid[:, -1]
        return pf(params, k_pools, v_pools, toks, starts, lens,
                  block_tables)
    return paged_prefill_step, paged_decode_step


@functools.lru_cache(maxsize=1)
def _shared_slot_jits():
    return (jax.jit(_slot_slice_fn), jax.jit(_slot_write_fn),
            jax.jit(_reset_slot_fn))


@functools.lru_cache(maxsize=64)
def _shared_legacy_jit(cfg: ModelConfig, impl: str, moe_impl: str):
    return jax.jit(_make_legacy_prefill(cfg, impl, moe_impl))


@functools.lru_cache(maxsize=64)
def _shared_batched_jit(cfg: ModelConfig, impl: str, moe_impl: str):
    return jax.jit(_make_batched_prefill(cfg, impl, moe_impl))


@functools.lru_cache(maxsize=64)
def _shared_decode_jit(cfg: ModelConfig, impl: str, moe_impl: str,
                       decode_append: str):
    return jax.jit(_make_decode(cfg, impl, moe_impl, decode_append))


@functools.lru_cache(maxsize=64)
def _shared_paged_jits(cfg: ModelConfig, moe_impl: str):
    pf, df = _make_paged_step_fns(cfg, moe_impl)
    return jax.jit(pf), jax.jit(df)


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, scheduler: Scheduler,
                 ecfg: Optional[EngineConfig] = None,
                 cost_model: Optional[CostModel] = None):
        # copy the config: a shared default (or caller-reused) instance
        # must not be mutated by the per-model chunk clamp below
        ecfg = replace(ecfg) if ecfg is not None else EngineConfig()
        if cfg.window:
            ecfg.chunk = min(ecfg.chunk, cfg.window)
        if ecfg.plane not in ("batched", "legacy", "paged"):
            raise ValueError(f"unknown plane {ecfg.plane!r}")
        if ecfg.decode_append not in ("inline", "deferred"):
            raise ValueError(f"unknown decode_append {ecfg.decode_append!r}")
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.sched = scheduler
        self.cost_model = cost_model
        if scheduler.cost_model is None:
            scheduler.cost_model = cost_model   # auto preempt-mode pricing
        scheduler.cfg.max_running = ecfg.nslots
        # page-rounded capacity: ceil, NOT floor — flooring silently lost
        # up to page_size-1 tokens of capacity while the scheduler kept
        # admitting by raw token count, making OutOfPagesError reachable
        # on schedules the control plane proved feasible.  The scheduler
        # is told the granularity so both sides round identically.
        scheduler.cfg.page_size = ecfg.page_size
        # cache-replacement knobs: an EngineConfig override is written
        # through to the SchedulerConfig (like page_size above) so the
        # control plane — including any simulator shadow built from the
        # same config — and this data plane agree on one policy and on
        # which tier every prefix lands in
        if ecfg.cache_policy is not None:
            scheduler.cfg.cache_policy = ecfg.cache_policy
        if ecfg.cache_demotion is not None:
            scheduler.cfg.cache_demotion = ecfg.cache_demotion
        if ecfg.prefix_lookup is not None:
            scheduler.cfg.prefix_lookup = ecfg.prefix_lookup
        if ecfg.faults is not None:
            scheduler.cfg.faults = ecfg.faults
        if scheduler.cfg.prefix_lookup not in ("trie", "exact"):
            raise ValueError(
                f"unknown prefix_lookup {scheduler.cfg.prefix_lookup!r}")
        # pooled paged data plane: only unbounded dense-attention
        # families are pooled; bounded-state families keep slots
        self._pooled = ecfg.plane == "paged" and paged_supported(cfg)
        if scheduler.cfg.partial_preempt and not self._pooled:
            raise ValueError(
                "partial_preempt needs the pooled paged data plane")
        self._demotion = bool(scheduler.cfg.cache_demotion) \
            and self._pooled and ecfg.prefix_sharing
        self.allocator = PagedAllocator(
            num_pages=max(1, -(-scheduler.cfg.M // ecfg.page_size)),
            page_size=ecfg.page_size,
            policy=make_replacement_policy(scheduler.cfg.cache_policy,
                                           cost_model=cost_model,
                                           M=scheduler.cfg.M),
            on_evict=self._demote_prefix if self._demotion else None)
        if self._pooled:
            pg = ecfg.page_size
            self.max_pages = -(-ecfg.cache_len // pg)
            pool_shape = (cfg.num_layers, self.allocator.num_pages,
                          cfg.num_kv_heads, pg, cfg.head_dim_)
            self.k_pools = jnp.zeros(pool_shape, jnp.dtype(cfg.dtype))
            self.v_pools = jnp.zeros_like(self.k_pools)
            self.cache = None
        else:
            # init_cache caps the per-slot KV length at cfg.window
            self.cache = M.init_cache(cfg, ecfg.nslots, ecfg.cache_len)
        # shared-prefix bookkeeping (pooled plane): chained page keys per
        # rid and the per-grant data-plane skip from a registry hit
        self._page_keys_of: Dict[int, List[int]] = {}
        self._page_tokens_of: Dict[int, List[Tuple[int, ...]]] = {}
        self._prefix_skip: Dict[int, int] = {}
        # (allocator version, device array) — see _block_tables_device
        self._bt_cache: Optional[Tuple[int, jnp.ndarray]] = None
        # persistent host mirror of the device block tables: refreshed
        # row-by-row from the allocator's dirty-rid delta (never rebuilt
        # whole), then uploaded in ONE host->device transfer
        self._bt_host: Optional[np.ndarray] = None
        # device-resident decode inputs keyed by cohort — steady-state
        # decode uploads NOTHING (see _run_decodes_paged)
        self._decode_state: Optional[Dict[str, Any]] = None
        self.free_slots: List[int] = list(range(ecfg.nslots - 1, -1, -1))
        self.slot_of: Dict[int, int] = {}
        self.token_ids: Dict[int, List[int]] = {}
        self.outputs: Dict[int, List[int]] = {}
        self.buckets = _bucket_ladder(ecfg.chunk, ecfg.min_bucket)
        self.swap_store = KVSwapStore(capacity_bytes=ecfg.swap_bytes)
        # --- failure model: fault plan + step-transaction machinery ----- #
        self.fault_plan: Optional[FaultPlan] = (
            FaultPlan(scheduler.cfg.faults)
            if scheduler.cfg.faults is not None else None)
        if self.fault_plan is not None:
            self.allocator.fault_hook = self._alloc_fault_hook
        self._attempt = 0           # retry index of the current step
        self._alloc_ordinal = 0     # allocation counter within an attempt
        self._last_dt = 0.0         # predicted dt of the last batch
        self._last_wall = 0.0       # measured wall of the last batch
        # abort-history counters — deliberately OUTSIDE the step txn:
        # they record aborted attempts, and rolling the step back must
        # not erase the record of the rollback itself
        self.recovery_stats: Dict[str, float] = {
            SK.ROLLBACKS: 0, SK.ALLOC_FAULTS: 0, SK.INTEGRITY_FAILURES: 0,
            SK.DEGRADED_RECOMPUTES: 0, SK.STRAGGLER_REQUEUES: 0,
            SK.WALL_ABORTED_S: 0.0}
        self._straggler: Optional[StragglerMonitor] = (
            StragglerMonitor(deadline_factor=ecfg.straggler_factor)
            if ecfg.straggler_factor else None)
        # the engine's counters of step-program work (prefill rounds and
        # the tokens of their padded grids against the real ones, decode
        # calls, their active rows and the pages those rows attend
        # over) — OUTSIDE the step txn like
        # ``wall``: a program an aborted attempt dispatched still ran.
        # Every abort point precedes the first dispatch of an attempt,
        # so a retried step counts its programs once.
        self.phase_stats: Dict[str, int] = {
            SK.PREFILL_ROUNDS: 0, SK.PREFILL_GRID_TOKENS: 0,
            SK.PREFILL_REAL_TOKENS: 0, SK.DECODE_CALLS: 0,
            SK.DECODE_ROWS: 0, SK.DECODE_LIVE_PAGES: 0}
        # in-flight async swap-out snapshots (rid -> (store entry whose
        # cache leaves are still device arrays mid-D2H, enqueue step)).
        # An entry enqueued during step N overlaps its D2H copy with
        # step N+1's compute and is finalized at the END of step N+1 —
        # or earlier, on same-window re-admission / double-buffer
        # pressure (more than two transfers in flight).
        self._pending_swaps: "OrderedDict[int, Tuple[SwapEntry, int]]" = \
            OrderedDict()
        # in-flight async prefix-page demotions (chain key -> enqueue
        # step): the PrefixPageEntry's kv leaves stay device arrays
        # mid-D2H; finalized alongside _pending_swaps at the same drain
        # boundaries.  A promotion that lands before the drain simply
        # pops the entry — the bytes never round-trip.
        self._pending_demotes: "OrderedDict[int, int]" = OrderedDict()
        # in-flight async pooled page-run snapshots ((rid, run start) ->
        # (PageRunEntry whose kv leaves are device-side page gathers
        # mid-D2H, enqueue step)) — keyed by run start because tail
        # sheds can stack several runs per rid.  Drained at the same
        # boundaries as _pending_swaps / _pending_demotes.
        self._pending_runs: \
            "OrderedDict[Tuple[int, int], Tuple[Any, int]]" = OrderedDict()
        self._step_no = 0
        # measured host-transfer wall times (fig08 validation column);
        # promotions/demotions are the prefix cache's host-tier traffic
        self.swap_stats: Dict[str, float] = {
            SK.SWAP_OUTS: 0, SK.SWAP_INS: 0, SK.KV_OUT: 0, SK.KV_IN: 0,
            SK.SWAP_FALLBACKS: 0, SK.DRAINS_ON_SWAPIN: 0,
            SK.WALL_OUT_S: 0.0, SK.WALL_IN_S: 0.0,
            SK.PROMOTIONS: 0, SK.DEMOTIONS: 0, SK.DEMOTE_DROPS: 0,
            SK.KV_PROMOTED: 0, SK.KV_DEMOTED: 0,
            SK.WALL_PROMOTE_S: 0.0, SK.WALL_DEMOTE_S: 0.0,
            # fault-injection counters: inside the step txn (this dict
            # is snapshotted), so an aborted attempt's draws roll back
            # and its retry does not double-count them
            SK.PERMANENT_STORE_FAILURES: 0, SK.TRANSIENT_RETRIES: 0,
            SK.BACKOFF_S: 0.0, SK.PREFIX_INTEGRITY: 0,
            # radix-trie attach outcomes (PR 9): attaches that reused
            # at least one page, and the tokens reused by attaches that
            # matched only PART of the queried chain — the reuse the
            # exact-match registry could never see
            SK.TRIE_HITS: 0, SK.PARTIAL_HIT_TOKENS: 0}
        # virtual-time owed by prefix-tier traffic (demotions fire inside
        # allocator reclaims; promotions inside the prefix attach) —
        # folded into the CURRENT batch's swap_s before its dt is priced
        self._tier_swap_s = 0.0
        # swap-out virtual-time charges from rounds that admitted no
        # items, owed to the next executed batch (mirrors the simulator)
        self._carry_swap_s = 0.0
        self._carry_out = 0
        self.now = 0.0
        self.wall = 0.0
        self.batch_logs: List[BatchLog] = []
        self._build_jits()

    # ------------------------------------------------------------------ #
    def _build_jits(self) -> None:
        cfg, ecfg = self.cfg, self.ecfg
        key = (cfg, ecfg.impl, ecfg.moe_impl)
        legacy = ecfg.plane == "legacy"
        if ecfg.share_jits:
            slot = _shared_slot_jits()
            prefill_jit = (_shared_legacy_jit(*key) if legacy
                           else _shared_batched_jit(*key))
            decode_jit = _shared_decode_jit(*key, ecfg.decode_append)
        else:
            slot = tuple(jax.jit(f) for f in _make_slot_fns())
            prefill_jit = jax.jit(_make_legacy_prefill(*key) if legacy
                                  else _make_batched_prefill(*key))
            decode_jit = jax.jit(_make_decode(*key, ecfg.decode_append))
        # swap data plane: slot snapshot (device->host) and slot restore
        self._slot_slice, self._slot_write, self._reset_slot = slot
        if legacy:
            self._prefill_one = prefill_jit
        else:
            self._prefill_many = prefill_jit
        self._decode_many = decode_jit
        # num_compiles counts only the fns THIS plane can reach, so a
        # shared cache (share_jits) never leaks another plane's
        # signatures into this engine's count
        self._jit_fns = [prefill_jit, decode_jit, *slot]
        if self._pooled:
            if ecfg.share_jits:
                ppf, pdf = _shared_paged_jits(cfg, ecfg.moe_impl)
            else:
                pf, df = _make_paged_step_fns(cfg, ecfg.moe_impl)
                ppf, pdf = jax.jit(pf), jax.jit(df)
            self._paged_prefill, self._paged_decode = ppf, pdf
            self._jit_fns = [ppf, pdf]   # the pooled plane uses nothing else

    def warmup(self) -> "Engine":
        """Pre-compile every signature the run loop can hit — one
        prefill per ladder bucket plus the fused decode — with inert
        inputs (zero lengths, all-false masks): outputs are discarded
        and pools/cache stay bit-identical.  Benchmarks call this before
        their timed window (ideally with ``share_jits``) so measured
        tok/s prices data movement and compute, not XLA's
        backend_compile.  The legacy plane cannot warm up by
        construction: its exact-shape signatures depend on request data
        — which is precisely the shape-instability the bucket ladder
        fixes."""
        ns = self.ecfg.nslots
        zi = jnp.zeros((ns,), jnp.int32)
        za = jnp.zeros((ns,), bool)
        if self._pooled:
            bt = jnp.zeros((ns, self.max_pages), jnp.int32)
            for b in self.buckets:
                self._paged_prefill(self.params, self.k_pools,
                                    self.v_pools,
                                    jnp.zeros((ns, b + 2), jnp.int32), bt)
            self._paged_decode(self.params, self.k_pools, self.v_pools,
                               zi, zi, bt, za)
            # the suspend/restore data plane too: page-run gathers and
            # swap-in scatters build one eager executable per run
            # length — a small discrete set bounded by the per-request
            # page budget — and the first preemption would otherwise
            # eat those compiles inside the timed window.  Scattering a
            # page's own bytes back over itself is the identity, so the
            # pools stay bit-identical.
            for npg in range(1, self.max_pages + 1):
                ids = jnp.zeros((npg,), jnp.int32)
                for pool in (self.k_pools, self.v_pools):
                    run = pool[:, ids]
                    jax.block_until_ready(pool.at[:, ids].set(run))  # repro: allow-host-sync(warmup runs BEFORE the timed window by contract - blocking here is the point: compiles must finish before serving starts)
        elif self.ecfg.plane != "legacy":
            for b in self.buckets:
                self._prefill_many(self.params, self.cache,
                                   jnp.zeros((ns, b), jnp.int32), zi)
            self._decode_many(self.params, self.cache, zi, za)
            self._reset_slot(self.cache, 0)
        return self

    @property
    def num_compiles(self) -> int:
        """Distinct XLA compiles across every entry point this plane
        can reach.  The batched plane keeps this a small constant —
        independent of request count, prompt lengths, and preemptions
        (tested).  Under ``share_jits`` the caches are process-global,
        so the count covers every engine sharing them."""
        return sum(f._cache_size() for f in self._jit_fns)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError(f"chunk step {n} exceeds ladder {self.buckets}")

    # ------------------------------------------------------------------ #
    def submit(self, r: Request) -> None:
        if r.prompt is None:
            raise ValueError("engine requests need real token ids")
        if len(r.prompt) != r.input_len:
            raise ValueError(
                f"request {r.rid}: prompt length {len(r.prompt)} != "
                f"input_len {r.input_len}")
        # window/ssm archs hold bounded state; dense caches must fit
        if not (self.cfg.window or self.cfg.family == "ssm"
                or r.peak_kv <= self.ecfg.cache_len):
            raise ValueError(
                f"request {r.rid} peak KV {r.peak_kv} > cache_len")
        self.token_ids[r.rid] = list(r.prompt)
        self.outputs[r.rid] = []
        r.wall_submitted = time.perf_counter()
        self.sched.add_request(r)

    # ------------------------------------------------------------------ #
    def _claim_slot(self, rid: int, reset: bool = True) -> int:
        slot = self.free_slots.pop()
        self.slot_of[rid] = slot
        if reset:
            self.cache = self._reset_slot(self.cache, slot)
        return slot

    def _release(self, rid: int) -> None:
        slot = self.slot_of.pop(rid, None)
        if slot is not None:
            self.free_slots.append(slot)
            if self._bt_host is not None:
                # the freed rid won't be in slot_of at the next delta
                # rebuild, so its row must be cleared here
                self._bt_host[slot, :] = 0
        self.allocator.free(rid)  # repro: allow-unpriced-mutation(releasing pages moves no bytes; the preemption decision that led here was already charged - swap_time or refill compute - by the scheduler)
        # refill restarts from scratch: drop generated tokens beyond prompt?
        # NO — generated tokens are kept and re-prefilled (paper §3 refill).

    # --- failure model: fault hooks, guarded puts, integrity ----------- #
    def _alloc_fault_hook(self, need: int) -> None:
        """``PagedAllocator.fault_hook``: a transient device fault on
        this (step, attempt, ordinal) aborts the attempt.  Keyed by
        attempt so the rolled-back retry draws fresh (no livelock), and
        trace-free by construction — an aborted attempt leaves no
        parity-visible state, so the simulator never mirrors these."""
        self._alloc_ordinal += 1
        if self.fault_plan.alloc_fault(self._step_no, self._attempt,
                                       self._alloc_ordinal):
            raise FaultError(
                f"injected allocation fault: step {self._step_no} "
                f"attempt {self._attempt} ordinal {self._alloc_ordinal}")

    def _retry_sleep(self, seconds: float) -> None:
        """Injectable backoff clock for ``run_with_retries``: records
        the schedule in virtual time instead of stalling the step."""
        self.swap_stats[SK.BACKOFF_S] += seconds

    _PERM_KIND = {"store_put": "perm_put", "store_run": "perm_run"}

    def _guarded_put(self, kind: str, key: Tuple, do_put):
        """Run a swap-store write under the fault plan.  A permanent
        draw raises ``PermanentStoreError`` — a ``SwapStoreFullError``
        subclass, so the caller's full-store fallback (drop + degrade
        to recompute) handles it unchanged.  A transient draw fails the
        write 1-3 times and then succeeds under ``run_with_retries``'s
        exponential backoff (rung 1 of the degradation ladder; the
        injected failures always fit the retry budget, so a transient
        fault alone never escalates)."""
        plan = self.fault_plan
        if plan is None:
            return do_put()
        if plan.decide(self._PERM_KIND[kind], *key):
            self.swap_stats[SK.PERMANENT_STORE_FAILURES] += 1
            raise PermanentStoreError(
                f"injected permanent store failure {kind}{key}")
        remaining = [plan.transient_failures(kind, *key)]

        def attempt():
            if remaining[0] > 0:
                remaining[0] -= 1
                self.swap_stats[SK.TRANSIENT_RETRIES] += 1
                raise TransientStoreError(
                    f"injected transient store failure {kind}{key}")
            return do_put()

        return run_with_retries(attempt, retries=3,
                                retry_on=(TransientStoreError,),
                                sleep=self._retry_sleep)

    def _corrupt_draw(self, kind: str, key: Tuple) -> bool:
        return (self.fault_plan is not None
                and self.fault_plan.decide(kind, *key))

    def _finalize_entry(self, entry) -> None:
        """Seal an entry's host bytes once; apply a pending corruption
        marker exactly once.  The seal-once guard doubles as the
        flip-once guard: after a step rollback the engine may re-drain
        an already-finalized entry (entry objects are shared by
        reference across snapshots — see ``txn.snapshot_store``), and
        re-sealing would bless the corruption while re-flipping would
        undo it."""
        if entry.crc is not None:
            return
        seal_entry(entry)
        if entry.corrupt and entry.crc is not None:
            flip_bit(entry.cache if isinstance(entry, SwapEntry)
                     else entry.kv)

    def _drop_snapshot_repair(self, r: Request):
        """Post-rollback repair for a corrupt full-slot snapshot: drop
        the entry and degrade ``r`` to recompute (the suspend never
        stuck, exactly like the store-full fallback)."""
        def repair() -> None:
            self.swap_store.discard(r.rid)
            self._pending_swaps.pop(r.rid, None)
            r.drop_suspended()
            self.sched.num_swaps -= 1
        return repair

    def _drop_runs_repair(self, r: Request, claim: bool):
        """Post-rollback repair for a corrupt page run: drop EVERY
        stored run of ``r`` (a tiling with a rotten stripe is
        unrestorable as a whole) and unwind the matching swap counters —
        the same arithmetic as the store-full fallbacks — degrading the
        request to recompute."""
        def repair() -> None:
            self._purge_pending_runs(r.rid)
            if claim:                      # fully suspended victim
                n = self.swap_store.discard_runs(r.rid)
                for _ in range(n - 1):     # tail runs beyond the base
                    r.swaps -= 1
                    self.sched.num_swaps -= 1
                r.drop_suspended()
                self.sched.num_swaps -= 1
            else:                          # partially shed victim
                for run in self.swap_store.pop_runs(r.rid):
                    r.drop_tail_run(run.num_tokens)
                    self.sched.num_swaps -= 1
        return repair

    # --- §5.4 swap data plane ------------------------------------------ #
    def _swap_out(self, victim: Request) -> bool:
        """Snapshot the victim's slot to the host store, then free it.
        Returns False when the store is full: the snapshot is dropped and
        the victim falls back to discard-and-recompute (finite host
        memory is the five-minute-rule's operating constraint).

        With ``async_swap`` the snapshot is a device-side slice whose
        host copy is started here and finalized later (``_drain_swaps``);
        capacity is charged immediately from array metadata so the
        full-store fallback stays synchronous and deterministic."""
        t0 = time.perf_counter()
        slot = self.slot_of[victim.rid]
        snap = self._slot_slice(self.cache, jnp.int32(slot))
        # content key: identical across engine/simulator and across
        # aborted-attempt retries, so fault draws are idempotent
        fkey = (victim.rid, victim.suspended_m, victim.swaps)
        try:
            if self.ecfg.async_swap:
                nbytes = sum(l.nbytes for l in jax.tree.leaves(snap))
                entry = self._guarded_put(
                    "store_put", fkey,
                    lambda: self.swap_store.put(
                        victim.rid, snap, self.token_ids[victim.rid],
                        victim.suspended_m, nbytes=nbytes))
                entry.corrupt = self._corrupt_draw("corrupt_put", fkey)
                for leaf in jax.tree.leaves(snap):
                    leaf.copy_to_host_async()
                self._pending_swaps[victim.rid] = (entry, self._step_no)
            else:
                snap = jax.device_get(snap)  # repro: allow-host-sync(the synchronous swap-out path async_swap=False selects; charged swap_time in virtual time and measured into wall_out_s)
                entry = self._guarded_put(
                    "store_put", fkey,
                    lambda: self.swap_store.put(
                        victim.rid, snap, self.token_ids[victim.rid],
                        victim.suspended_m))
                if self.ecfg.check_invariants:
                    # repro: allow-host-sync(invariant check reads the already-fetched host snapshot; no extra device traffic)
                    assert int(np.asarray(snap["index"])[0]) \
                        == victim.suspended_m, \
                        (victim.rid, snap["index"], victim.suspended_m)
                entry.corrupt = self._corrupt_draw("corrupt_put", fkey)
                self._finalize_entry(entry)
        except SwapStoreFullError:
            victim.drop_suspended()
            self.sched.num_swaps -= 1   # the suspend did not stick
            self.swap_stats[SK.SWAP_FALLBACKS] += 1
            self._release(victim.rid)
            return False
        self.swap_stats[SK.SWAP_OUTS] += 1
        self.swap_stats[SK.KV_OUT] += victim.suspended_m
        self.swap_stats[SK.WALL_OUT_S] += time.perf_counter() - t0
        self._release(victim.rid)
        # double buffering: finalize the oldest transfer(s) OUTSIDE the
        # timed enqueue window above (the drain bills its own wait into
        # wall_out_s — overlapping windows would double-count it)
        while len(self._pending_swaps) > 2:
            self._drain_swaps(rid=next(iter(self._pending_swaps)))
        return True

    @_spanned("repro.engine.drain")
    def _drain_swaps(self, rid: Optional[int] = None,
                     before_step: Optional[int] = None) -> None:
        """Finalize in-flight swap-out transfers: block on the async D2H
        copy and replace the store entry's device leaves with host
        arrays.  ``rid`` drains one entry (same-window re-admission,
        double-buffer pressure); ``before_step`` drains entries enqueued
        before that step (the end-of-step boundary); neither drains
        everything (end of run).  In-flight prefix demotions AND pooled
        page-run snapshots share the ``before_step`` / drain-all
        boundaries (``rid`` here is a slot-plane concept; demotes drain
        per chain key via ``_drain_demotes``, runs per rid via
        ``_drain_runs``)."""
        if rid is not None:
            rids = [rid] if rid in self._pending_swaps else []
        elif before_step is not None:
            rids = [r for r, (_, s) in self._pending_swaps.items()
                    if s < before_step]
        else:
            rids = list(self._pending_swaps)
        for r in rids:
            entry, _ = self._pending_swaps.pop(r)
            t0 = time.perf_counter()
            # the drain IS the double-buffer boundary: the one place the
            # slot plane may block on its own already-started D2H copy
            entry.cache = jax.device_get(entry.cache)  # repro: allow-host-sync(async swap-out drain boundary - blocks only on a D2H copy started a step earlier, overlapped with that step's compute)
            if self.ecfg.check_invariants:
                assert int(np.asarray(entry.cache["index"])[0]) \
                    == entry.num_kv, (r, entry.cache["index"], entry.num_kv)
            self._finalize_entry(entry)   # CRC seal (+ fault-plan flip)
            self.swap_stats[SK.WALL_OUT_S] += time.perf_counter() - t0
        if rid is None:
            if before_step is not None:
                keys = [k for k, s in self._pending_demotes.items()
                        if s < before_step]
            else:
                keys = list(self._pending_demotes)
            for k in keys:
                self._drain_demotes(key=k)
            self._drain_runs(before_step=before_step)

    def _drain_demotes(self, key: int) -> None:
        """Finalize one in-flight prefix-page demotion: block on the
        async D2H copy and replace the entry's device leaves with host
        arrays.  A key whose entry was promoted (or discarded) before
        the drain is simply forgotten — its bytes never round-tripped,
        and ``pop_prefix`` already settled the byte accounting."""
        self._pending_demotes.pop(key, None)
        entry = self.swap_store.peek_prefix(key)
        if entry is None:
            return
        t0 = time.perf_counter()
        entry.kv = jax.device_get(entry.kv)  # repro: allow-host-sync(async demotion drain boundary - blocks only on its own already-started D2H page copy)
        seal_entry(entry)   # prefix rot is modeled by flag, never flipped
        self.swap_stats[SK.WALL_DEMOTE_S] += time.perf_counter() - t0

    def _swap_in(self, r: Request) -> None:
        """Restore r's snapshot into a free slot; no refill is needed."""
        if r.rid in self._pending_swaps:
            # re-admitted within the drain window: finalize on demand
            self.swap_stats[SK.DRAINS_ON_SWAPIN] += 1
            self._drain_swaps(rid=r.rid)
        if not verify_entry(self.swap_store.peek(r.rid)):
            # rung 3: corrupt snapshot — abort the step; post-rollback
            # the repair drops the entry and degrades r to recompute
            raise IntegrityError(
                f"rid {r.rid}: corrupt swap snapshot",
                repairs=[self._drop_snapshot_repair(r)])
        t0 = time.perf_counter()
        entry = self.swap_store.pop(r.rid)
        slot = self._claim_slot(r.rid, reset=False)  # fully overwritten
        upd = jax.tree.map(jnp.asarray, entry.cache)
        self.cache = self._slot_write(self.cache, upd, jnp.int32(slot))
        jax.block_until_ready(self.cache["index"])  # repro: allow-host-sync(restore barrier - the slot must be fully written before this step's compute reads it; measured into wall_in_s)
        self.allocator.allocate(r.rid, entry.num_kv)
        restored = r.resume()
        if self.ecfg.check_invariants:
            assert restored == entry.num_kv, (r.rid, restored, entry.num_kv)
            assert self.token_ids[r.rid] == entry.tokens, r.rid
        self.swap_stats[SK.SWAP_INS] += 1
        self.swap_stats[SK.KV_IN] += entry.num_kv
        self.swap_stats[SK.WALL_IN_S] += time.perf_counter() - t0

    # --- pooled (paged) swap data plane -------------------------------- #
    def _check_run_capacity(self, npages: int) -> None:
        """Raise ``SwapStoreFullError`` from shape metadata BEFORE the
        D2H page gather — a doomed snapshot must not pay the transfer
        (mirrors the slot plane's charge-at-enqueue)."""
        cap = self.swap_store.capacity_bytes
        if cap is None:
            return
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        nbytes = 2 * self.cfg.num_layers * npages * self.ecfg.page_size \
            * self.cfg.num_kv_heads * self.cfg.head_dim_ * itemsize
        if self.swap_store.nbytes + nbytes > cap:
            raise SwapStoreFullError(
                f"page run of {npages} pages ({nbytes}B) over capacity "
                f"({self.swap_store.nbytes}/{cap}B held)")

    def _snapshot_pages(self, page_ids) -> Dict[str, np.ndarray]:
        ids = np.asarray(page_ids, np.int32)
        return {"k": np.asarray(self.k_pools[:, ids]),   # repro: allow-host-sync(the synchronous page gather async_swap=False selects; pooled suspends, tail sheds and prefix demotions all route around it under async_swap)
                "v": np.asarray(self.v_pools[:, ids])}   # repro: allow-host-sync(same sync gather as the k plane above)

    def _gather_pages_device(self, page_ids) -> Dict[str, jnp.ndarray]:
        """Async page snapshot: gather the pages into FRESH device
        buffers (immutable — later pool writes, and even freeing the
        source pages, cannot alias them) and start the D2H copy
        immediately; the host bytes land at a drain boundary
        (``_drain_runs``).  This is what lets ``_shed_tail`` free the
        gathered pages in the same step without waiting on the host
        link."""
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        kv = {"k": self.k_pools[:, ids], "v": self.v_pools[:, ids]}
        kv["k"].copy_to_host_async()
        kv["v"].copy_to_host_async()
        return kv

    def _drain_runs(self, rid: Optional[int] = None,
                    before_step: Optional[int] = None) -> None:
        """Finalize in-flight pooled page-run snapshots — the paged
        plane's analogue of ``_drain_swaps``: block on the
        already-started D2H copy, replace the entry's device leaves
        with host arrays, CRC-seal (+ apply any pending corruption
        marker)."""
        if rid is not None:
            keys = [k for k in self._pending_runs if k[0] == rid]
        elif before_step is not None:
            keys = [k for k, (_, s) in self._pending_runs.items()
                    if s < before_step]
        else:
            keys = list(self._pending_runs)
        for k in keys:
            entry, _ = self._pending_runs.pop(k)
            t0 = time.perf_counter()
            entry.kv = jax.device_get(entry.kv)  # repro: allow-host-sync(async page-run drain boundary - blocks only on a D2H copy started at suspend time and overlapped with later compute)
            self._finalize_entry(entry)
            self.swap_stats[SK.WALL_OUT_S] += time.perf_counter() - t0

    def _purge_pending_runs(self, rid: int) -> None:
        """Forget in-flight snapshots of runs the store no longer holds
        (full-store unwind, recompute discard, post-rollback repair):
        their entries were already popped, so draining them would
        finalize dangling objects and misattribute wall time."""
        for k in [k for k in self._pending_runs if k[0] == rid]:
            del self._pending_runs[k]

    def _restore_pages(self, page_ids, kv) -> None:
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        self.k_pools = self.k_pools.at[:, ids].set(jnp.asarray(kv["k"]))
        self.v_pools = self.v_pools.at[:, ids].set(jnp.asarray(kv["v"]))

    def _swap_out_paged(self, victim: Request) -> bool:
        """Full suspend in the pooled plane: one ``PageRunEntry`` run
        covering every device page (tail runs shed earlier are already
        in the store; together they tile [0, suspended_m)).  Returns
        False when the store is full — the victim (and any stored tail
        runs) falls back to discard-and-recompute.

        With ``async_swap`` the snapshot is a device-side page gather
        (fresh immutable buffers, so the freed pages can be reused this
        very step) whose host copy is started here and finalized at a
        drain boundary; capacity is charged from shape metadata before
        the gather, so the full-store fallback stays synchronous."""
        t0 = time.perf_counter()
        tbl = self.allocator.table(victim.rid)
        device_tokens = tbl.num_tokens
        # same key shape as the slot plane's full suspend, so the
        # simulator's fault mirror is plane-agnostic
        fkey = (victim.rid, victim.suspended_m, victim.swaps)
        try:
            self._check_run_capacity(len(tbl.pages))  # before the D2H copy
            if self.ecfg.async_swap:
                kv = self._gather_pages_device(tbl.pages)
                entry = self._guarded_put(
                    "store_put", fkey,
                    lambda: self.swap_store.put_run(
                        victim.rid, start=0, num_tokens=device_tokens,
                        kv=kv, nbytes=kv["k"].nbytes + kv["v"].nbytes))
                entry.corrupt = self._corrupt_draw("corrupt_put", fkey)
                self._pending_runs[(victim.rid, 0)] = (entry, self._step_no)
            else:
                entry = self._guarded_put(
                    "store_put", fkey,
                    lambda: self.swap_store.put_run(
                        victim.rid, start=0, num_tokens=device_tokens,
                        kv=self._snapshot_pages(tbl.pages)))
                entry.corrupt = self._corrupt_draw("corrupt_put", fkey)
                self._finalize_entry(entry)
        except SwapStoreFullError:
            # stored tail runs are unrestorable without the device
            # portion: unwind their swap counts along with this one
            if self.swap_store.has_runs(victim.rid):
                for _ in self.swap_store.pop_runs(victim.rid):
                    victim.swaps -= 1
                    self.sched.num_swaps -= 1
                    self.swap_stats[SK.SWAP_FALLBACKS] += 1
            self._purge_pending_runs(victim.rid)
            victim.drop_suspended()
            self.sched.num_swaps -= 1   # the suspend did not stick
            self.swap_stats[SK.SWAP_FALLBACKS] += 1
            self._release(victim.rid)
            return False
        self.swap_stats[SK.SWAP_OUTS] += 1
        self.swap_stats[SK.KV_OUT] += device_tokens
        self.swap_stats[SK.WALL_OUT_S] += time.perf_counter() - t0
        self._release(victim.rid)
        # double buffering, as in _swap_out: finalize the oldest
        # transfer(s) outside the timed enqueue window above
        while len(self._pending_runs) > 2:
            self._drain_runs(rid=next(iter(self._pending_runs))[0])
        return True

    def _shed_tail(self, r: Request, npages: int, n_tokens: int,
                   mode: str) -> bool:
        """Page-level partial preemption: snapshot (swap mode) and free
        only the victim's last ``npages`` pages.  Returns True iff the
        run was swapped (caller charges its host-link time); a full
        store falls back to recompute for this run."""
        tbl = self.allocator.table(r.rid)
        start = tbl.num_tokens - n_tokens
        swapped = False
        if mode == "swap":
            t0 = time.perf_counter()
            # r.m is already reduced to the run's start by the
            # scheduler's partial_preempt, so this key is stable across
            # attempt retries and reproducible by the simulator
            fkey = (r.rid, r.m, n_tokens, r.partial_preemptions)
            try:
                self._check_run_capacity(npages)   # before the D2H copy
                if self.ecfg.async_swap:
                    # the gather's fresh buffers are what make the
                    # free_tail below safe in the same step
                    kv = self._gather_pages_device(tbl.pages[-npages:])
                    entry = self._guarded_put(
                        "store_run", fkey,
                        lambda: self.swap_store.put_run(
                            r.rid, start=start, num_tokens=n_tokens,
                            kv=kv,
                            nbytes=kv["k"].nbytes + kv["v"].nbytes))
                    entry.corrupt = self._corrupt_draw("corrupt_run", fkey)
                    self._pending_runs[(r.rid, start)] = \
                        (entry, self._step_no)
                else:
                    entry = self._guarded_put(
                        "store_run", fkey,
                        lambda: self.swap_store.put_run(
                            r.rid, start=start, num_tokens=n_tokens,
                            kv=self._snapshot_pages(tbl.pages[-npages:])))
                    entry.corrupt = self._corrupt_draw("corrupt_run", fkey)
                    self._finalize_entry(entry)
                swapped = True
                self.swap_stats[SK.SWAP_OUTS] += 1
                self.swap_stats[SK.KV_OUT] += n_tokens
                self.swap_stats[SK.WALL_OUT_S] += time.perf_counter() - t0
            except SwapStoreFullError:
                r.drop_tail_run(n_tokens)
                self.sched.num_swaps -= 1
                self.swap_stats[SK.SWAP_FALLBACKS] += 1
                # the failed run sits BELOW every run already stored for
                # this rid (the tail is shed top-down), so the stored
                # tiling now has an unrestorable gap: fold those runs
                # back to recompute too
                if self.swap_store.has_runs(r.rid):
                    for run in self.swap_store.pop_runs(r.rid):
                        r.drop_tail_run(run.num_tokens)
                        self.sched.num_swaps -= 1
                        self.swap_stats[SK.SWAP_FALLBACKS] += 1
                self._purge_pending_runs(r.rid)
        removed = self.allocator.free_tail(r.rid, npages)
        if self.ecfg.check_invariants:
            assert removed == n_tokens, (r.rid, removed, n_tokens)
        if swapped:
            while len(self._pending_runs) > 2:
                self._drain_runs(rid=next(iter(self._pending_runs))[0])
        return swapped

    def _swap_in_paged(self, r: Request) -> None:
        """Restore a fully suspended pooled request: fresh pages are
        allocated and every stored run is scattered back in ascending
        start order (their page spans tile the table exactly)."""
        self._restore_runs(r, claim=True, resume=r.resume)

    def _swap_in_tail(self, r: Request) -> None:
        """Restore a partially shed request's tail runs before its next
        compute step (the kept prefix never left the device)."""
        self._restore_runs(r, claim=False, resume=r.resume_tail)

    def _restore_runs(self, r: Request, *, claim: bool, resume) -> None:
        if any(k[0] == r.rid for k in self._pending_runs):
            # re-admitted within the drain window: finalize on demand —
            # BEFORE the verify below, which is trivially true (crc
            # None) on an undrained entry
            self.swap_stats[SK.DRAINS_ON_SWAPIN] += 1
            self._drain_runs(rid=r.rid)
        if not all(verify_entry(run)
                   for run in self.swap_store.peek_runs(r.rid)):
            # rung 3: one rotten stripe poisons the whole tiling —
            # abort; the post-rollback repair drops every stored run
            # and degrades r to recompute
            raise IntegrityError(
                f"rid {r.rid}: corrupt page run",
                repairs=[self._drop_runs_repair(r, claim)])
        t0 = time.perf_counter()
        runs = self.swap_store.pop_runs(r.rid)
        total = sum(run.num_tokens for run in runs)
        if claim:
            self._claim_slot(r.rid, reset=False)
        self.allocator.allocate(r.rid, total)
        self._write_runs(r.rid, runs)
        restored = resume()
        if self.ecfg.check_invariants:
            assert restored == total, (r.rid, restored, total)
        self.swap_stats[SK.SWAP_INS] += len(runs)   # run-for-run with outs
        self.swap_stats[SK.KV_IN] += total
        self.swap_stats[SK.WALL_IN_S] += time.perf_counter() - t0

    def _write_runs(self, rid: int, runs) -> None:
        pg = self.ecfg.page_size
        tbl = self.allocator.table(rid)
        for run in runs:
            invariant(run.start % pg == 0, (rid, run.start))
            p0 = run.start // pg
            npg = -(-run.num_tokens // pg)
            self._restore_pages(tbl.pages[p0:p0 + npg], run.kv)

    # --- shared-prefix reuse (pooled plane) ----------------------------- #
    def _page_keys(self, r: Request) -> List[int]:
        keys = self._page_keys_of.get(r.rid)
        if keys is None:
            keys = chain_keys(r.prompt, self.ecfg.page_size)
            self._page_keys_of[r.rid] = keys
        return keys

    def _page_tokens(self, r: Request, n: int) -> List[Tuple[int, ...]]:
        """Token ids of the first n full prompt pages (the registry's
        collision-verification payload), memoized per rid like
        ``_page_keys`` — the attach and every later registration
        re-derive the same leading pages."""
        toks = self._page_tokens_of.get(r.rid)
        if toks is None or len(toks) < n:
            pg = self.ecfg.page_size
            toks = [tuple(r.prompt[i * pg:(i + 1) * pg]) for i in range(n)]
            self._page_tokens_of[r.rid] = toks
        return toks[:n]

    def _demote_prefix(self, key: int, page: int, tokens, n_kvs: int
                       ) -> None:
        """Allocator eviction hook: snapshot the evicted registry page
        to the host demotion tier (refcount-free ``PrefixPageEntry``)
        instead of discarding its KV.  A full store drops the demotion —
        the page falls back to recompute-on-next-miss, the pre-demotion
        behaviour.  Charged ``swap_time(page_size)`` in virtual time
        (folded into the current batch) and measured on the wall.

        With ``async_swap`` the snapshot is a device-side page gather
        (a fresh immutable buffer, so the pool slot can be reused
        immediately) whose host copy is started here and finalized at
        the next drain boundary; capacity is charged from array
        metadata so the full-store drop stays synchronous.  Without it,
        the gather is a blocking ``device_get`` on the eviction path —
        the stall ROADMAP item 1 measured eating the prefix-sharing
        win."""
        if self.swap_store.has_prefix(key):
            return          # an identical snapshot is already host-resident
        if self.fault_plan is not None \
                and self.fault_plan.decide("demote_fail", key):
            # the async D2H copy "never lands": drop the demotion — the
            # page recomputes on its next miss, the pre-demotion
            # behaviour — with no charge.  PrefixTierSim mirrors the
            # same draw, so demote_drops stays parity-comparable.
            self.swap_stats[SK.DEMOTE_DROPS] += 1
            return
        t0 = time.perf_counter()
        try:
            self._check_run_capacity(1)     # metadata check BEFORE the D2H
            if self.ecfg.async_swap:
                ids = jnp.asarray([page], jnp.int32)
                kv = {"k": self.k_pools[:, ids], "v": self.v_pools[:, ids]}
                self.swap_store.put_prefix(
                    key, tokens, n_kvs, kv,
                    nbytes=kv["k"].nbytes + kv["v"].nbytes)
                kv["k"].copy_to_host_async()
                kv["v"].copy_to_host_async()
                self._pending_demotes[key] = self._step_no
            else:
                seal_entry(self.swap_store.put_prefix(
                    key, tokens, n_kvs, self._snapshot_pages([page])))
        except SwapStoreFullError:
            self.swap_stats[SK.DEMOTE_DROPS] += 1
            return
        pg = self.ecfg.page_size
        self._tier_swap_s += self._swap_time(pg)
        self.swap_stats[SK.DEMOTIONS] += 1
        self.swap_stats[SK.KV_DEMOTED] += pg
        self.swap_stats[SK.WALL_DEMOTE_S] += time.perf_counter() - t0
        # double buffering, as in _swap_out: finalize the oldest
        # transfer(s) outside the timed enqueue window above
        while len(self._pending_demotes) > 2:
            self._drain_demotes(key=next(iter(self._pending_demotes)))

    def _verify_prefix(self, entry) -> bool:
        """Promotion gate of ``attach_prefix_run``: CRC-check the
        host-resident page and consult the fault plan —
        ``corrupt_prefix`` models rot the CRC would catch on a drained
        entry (flagged, never byte-flipped: async drain timing must not
        diverge engine from simulator), ``promote_fail`` a failed host
        read.  A bad entry is dropped by the attach (the page
        recomputes); counted in ``swap_stats`` (step-txn scoped) so the
        simulator mirror stays parity-comparable."""
        plan = self.fault_plan
        ok = verify_entry(entry) and not (
            plan is not None
            and (plan.decide("corrupt_prefix", entry.key)
                 or plan.decide("promote_fail", entry.key)))
        if not ok:
            self.swap_stats[SK.PREFIX_INTEGRITY] += 1
        return ok

    def _promote_restore(self, page: int, kv) -> None:
        t0 = time.perf_counter()
        self._restore_pages([page], kv)
        self.swap_stats[SK.WALL_PROMOTE_S] += time.perf_counter() - t0

    def _attach_prefix(self, r: Request, c: int) -> int:
        """At a fresh claim, map the LONGEST cached run matching the
        prompt's leading full pages into r's block table (radix-trie
        walk — partial hits included) and return the number of tokens
        whose prefill compute is SKIPPED.  The trie resolves the run on
        the device first, then (with demotion enabled) extends it
        against the host tier — a host hit promotes the page back
        through the swap path, charged ``swap_time`` into this batch's
        virtual time exactly like a §5.4 swap-in.  Under
        ``prefix_lookup="exact"`` the attach is all-or-nothing (the
        pre-trie ablation).  Control-plane accounting is untouched
        (each sharer is charged its full page-rounded occupancy —
        sharing only ever reduces physical use), so admitted schedules
        stay allocator-feasible.  At least one granted token is always
        computed (the emitting batch needs real logits), and only pages
        wholly inside this grant qualify."""
        pg = self.ecfg.page_size
        cap = min(r.input_len - 1, c - 1) // pg
        if pg <= 1 or cap <= 0:
            return 0
        attached, promoted = attach_prefix_run(
            self.allocator, r.rid, self._page_keys(r)[:cap],
            self._page_tokens(r, cap),
            host_tier=self.swap_store if self._demotion else None,
            restore=self._promote_restore,
            verify=self._verify_prefix if self._demotion else None,
            exact=self.sched.cfg.prefix_lookup == "exact")
        if promoted:
            self._tier_swap_s += self._swap_time(promoted)
            self.swap_stats[SK.PROMOTIONS] += promoted // pg
            self.swap_stats[SK.KV_PROMOTED] += promoted
        if attached:
            self.swap_stats[SK.TRIE_HITS] += 1
            if attached < cap * pg:
                self.swap_stats[SK.PARTIAL_HIT_TOKENS] += attached
        return attached

    @_spanned("repro.engine.register")
    def _register_prefix(self, r: Request, m_new: int) -> None:
        """Publish the now-complete full PROMPT pages to the registry
        (generated-token pages are never shared)."""
        n = min(m_new, r.input_len) // self.ecfg.page_size
        if n > 0 and self.allocator.has(r.rid):
            # repro: allow-unpriced-mutation(registration moves no bytes - the pages already live on device, owned by rid; charges accrue at eviction/demotion/promotion)
            self.allocator.register_prefix(r.rid, self._page_keys(r)[:n],
                                           self._page_tokens(r, n))

    def _cow_guard(self, rid: int, pos: int) -> None:
        """Copy-on-write: an in-page append at token position ``pos``
        writes into an existing page — remap + copy it first if shared
        or registry-pinned (full-page-only sharing makes this rare, but
        the guard is what makes the sharing SAFE)."""
        pg = self.ecfg.page_size
        if pos % pg == 0:
            return                      # boundary: a fresh private page
        moved = self.allocator.ensure_private(rid, pos // pg)  # repro: allow-unpriced-mutation(CoW remap is a device-side page copy with no host traffic; its cost rides the decode batch_time)
        if moved is not None:
            old, new = moved
            self.k_pools = self.k_pools.at[:, new].set(self.k_pools[:, old])
            self.v_pools = self.v_pools.at[:, new].set(self.v_pools[:, old])

    @_spanned("repro.engine.upload")
    def _block_tables_device(self) -> jnp.ndarray:
        """Device-side (nslots, max_pages) block tables, cached against
        the allocator's mutation version — decode steps that allocated
        nothing new (in-page appends) skip the refresh entirely.  On a
        version bump only the rows of rids whose page list actually
        changed (``consume_dirty``) are rewritten in the persistent
        host mirror, then the whole mirror ships in ONE upload: a
        thousand-slot step that grew one table touches one row."""
        v = self.allocator.version
        if self._bt_cache is not None and self._bt_cache[0] == v:
            return self._bt_cache[1]
        if self._bt_host is None:
            self._bt_host = np.zeros((self.ecfg.nslots, self.max_pages),
                                     np.int32)
            self.allocator.consume_dirty()
            dirty = set(self.slot_of)          # first build: all rows
        else:
            dirty = self.allocator.consume_dirty()
        for rid in dirty:
            slot = self.slot_of.get(rid)
            if slot is None:
                continue     # freed rid: _release already zeroed its row
            row = self._bt_host[slot]
            row[:] = 0
            if self.allocator.has(rid):
                pages = self.allocator.table(rid).pages
                row[:len(pages)] = pages
        # the np.array COPY is load-bearing: on CPU jnp.asarray may
        # alias the numpy buffer zero-copy, and later in-place edits of
        # the mirror would corrupt device tables still referenced by
        # step-txn snapshots
        self._bt_cache = (v, jnp.asarray(np.array(self._bt_host)))
        return self._bt_cache[1]

    def _swap_time(self, n_kvs: int) -> float:
        return self.cost_model.swap_time(n_kvs) if self.cost_model else 0.0

    def _sample(self, logits: jnp.ndarray) -> int:
        """Greedy over the REAL vocabulary (padding logits excluded)."""
        return int(jnp.argmax(logits[..., :self.cfg.vocab_size], axis=-1))

    # ------------------------------------------------------------------ #
    def _run_prefills_legacy(self, prefill_items) -> Dict[int, int]:
        """PR-1 plane: per-request chunk loop with exact (unpadded)
        shapes — every distinct tail length compiles a new signature."""
        final_tok: Dict[int, int] = {}
        for r, c in prefill_items:
            slot = self.slot_of[r.rid]
            ids = self.token_ids[r.rid]
            start, remaining = r.m, c
            logits = None
            while remaining > 0:
                step_c = min(self.ecfg.chunk, remaining)
                with _span("repro.engine.upload"):
                    toks = jnp.asarray([ids[start:start + step_c]], jnp.int32)  # repro: allow-dynamic-shape(legacy plane pre-dates bucketing; distinct lengths are bounded by the chunk ladder and pinned by the compile-count test)
                with _span("repro.engine.prefill_call"):
                    logits, self.cache = self._prefill_one(
                        self.params, self.cache, jnp.int32(slot), toks)
                self._count_prefill(step_c, step_c)
                start += step_c
                remaining -= step_c
            if r.m + c == r.target_context:   # this batch emits a token
                with _span("repro.engine.fetch"):
                    final_tok[r.rid] = self._sample(logits)
        return final_tok

    def _count_prefill(self, grid_tokens: int, real_tokens: int) -> None:
        """Count one prefill program call: the tokens of its grid, padding
        included, and the real ones among them."""
        # repro: allow-txn-coverage(phase_stats counts programs dispatched - an aborted attempt's programs still ran, and every abort point precedes an attempt's first dispatch)
        self.phase_stats[SK.PREFILL_ROUNDS] += 1
        self.phase_stats[SK.PREFILL_GRID_TOKENS] += grid_tokens
        self.phase_stats[SK.PREFILL_REAL_TOKENS] += real_tokens

    def _run_prefill_rounds(self, plans, emits, step_fn) -> Dict[int, int]:
        """Shared bucketed round loop of the batched AND paged planes:
        one ``step_fn(toks, lens, starts)`` call per round over the full
        slot grid, sub-chunks padded to the bucket ladder.  Only
        (nslots,) sampled token ids are fetched, and only on rounds
        where some emitting request finishes its batch allotment.
        ``plans`` rows are [request, slot, next-token cursor, remaining]."""
        nslots = self.ecfg.nslots
        final_tok: Dict[int, int] = {}
        while True:
            steps = {p[1]: min(self.ecfg.chunk, p[3])
                     for p in plans if p[3] > 0}
            if not steps:
                break
            bucket = self._bucket_for(max(steps.values()))
            finishing: List[Tuple[Request, int]] = []
            with _span("repro.engine.upload"):
                toks = np.zeros((nslots, bucket), np.int32)
                lens = np.zeros((nslots,), np.int32)
                starts = np.zeros((nslots,), np.int32)
                for p in plans:
                    r, slot, cursor, rem = p
                    if rem <= 0:
                        continue
                    sc = steps[slot]
                    toks[slot, :sc] = \
                        self.token_ids[r.rid][cursor:cursor + sc]
                    lens[slot] = sc
                    starts[slot] = cursor
                    p[2] += sc
                    p[3] -= sc
                    if p[3] == 0:
                        finishing.append((r, slot))
            tok_ids = step_fn(toks, lens, starts)
            self._count_prefill(nslots * bucket, int(lens.sum()))
            if any(emits[r.rid] for r, _ in finishing):
                with _span("repro.engine.fetch"):
                    host = np.asarray(tok_ids)  # repro: allow-host-sync(per-step sampled-token fetch - ids must reach the host to extend prompts and detect EOS; (nslots,) int32 only)
                for r, slot in finishing:
                    if emits[r.rid]:
                        final_tok[r.rid] = int(host[slot])
        return final_tok

    def _run_prefills_batched(self, prefill_items) -> Dict[int, int]:
        """Shape-stable slot plane: the shared round loop over
        ``prefill_many`` (starts are implicit in the cache index)."""
        plans = [[r, self.slot_of[r.rid], r.m, c] for r, c in prefill_items]
        emits = {r.rid: r.m + c == r.target_context for r, c in prefill_items}

        def step(toks, lens, starts):
            with _span("repro.engine.upload"):
                toks_dev, lens_dev = jnp.asarray(toks), jnp.asarray(lens)
            with _span("repro.engine.prefill_call"):
                tok_ids, self.cache = self._prefill_many(
                    self.params, self.cache, toks_dev, lens_dev)
            return tok_ids

        return self._run_prefill_rounds(plans, emits, step)

    def _run_prefills_paged(self, prefill_items) -> Dict[int, int]:
        """Pooled plane: the shared round loop over ``paged_prefill`` —
        K/V rows are written through the block table into pooled pages.
        A grant's leading registry-shared tokens (``_prefix_skip``) are
        satisfied by page mapping and never computed (the cursor starts
        past them)."""
        plans = []
        for r, c in prefill_items:
            skip = self._prefix_skip.pop(r.rid, 0)
            plans.append([r, self.slot_of[r.rid], r.m + skip, c - skip])
        emits = {r.rid: r.m + c == r.target_context for r, c in prefill_items}
        block_tables = self._block_tables_device()

        def step(toks, lens, starts):
            # one coalesced upload per round — [toks | lens | starts]
            # ride a single (nslots, bucket+2) grid, unpacked on device
            # inside the jitted step (see _make_paged_step_fns)
            with _span("repro.engine.upload"):
                grid = jnp.asarray(np.concatenate(
                    [toks, lens[:, None], starts[:, None]], axis=1))
            with _span("repro.engine.prefill_call"):
                tok_ids, self.k_pools, self.v_pools = self._paged_prefill(
                    self.params, self.k_pools, self.v_pools, grid,
                    block_tables)
            return tok_ids

        return self._run_prefill_rounds(plans, emits, step)

    def _run_decodes_paged(self, decode_items) -> np.ndarray:
        """One fused decode step over all slots against the pooled KV:
        scatter the new token's K/V through the block table, then
        flash-decode over scalar-prefetched pages.

        Steady-state decode uploads NOTHING: the inputs of step N+1 are
        step N's own device outputs — last step's argmax ids ARE this
        step's tokens, and ctx advances by the (cached) active mask —
        so a stable cohort runs entirely device-resident.  The cohort
        key is (rid, slot, m, len(token_ids)) per row: any admission,
        finish, preemption, swap-in, or recompute-refill (the ntoks
        term — a refill re-emits and appends, so (rid, slot, m) alone
        could match a stale token buffer) perturbs it and forces one
        packed re-upload.  Non-cohort rows carry garbage on a hit,
        harmlessly: inactive scatters route out of bounds and their
        outputs are never read."""
        nslots = self.ecfg.nslots
        key = tuple(sorted(
            (r.rid, self.slot_of[r.rid], r.m, len(self.token_ids[r.rid]))
            for r, _ in decode_items))
        st = self._decode_state
        if st is not None and st["key"] == key:
            toks_dev, ctx_dev = st["toks"], st["ctx"]
            active_dev, ones = st["active"], st["ones"]
        else:
            with _span("repro.engine.upload"):
                toks = np.zeros((nslots,), np.int32)
                ctx = np.zeros((nslots,), np.int32)
                active = np.zeros((nslots,), bool)
                for r, _ in decode_items:
                    slot = self.slot_of[r.rid]
                    toks[slot] = self.token_ids[r.rid][-1]
                    ctx[slot] = r.m
                    active[slot] = True
                packed = jnp.asarray(np.stack([toks, ctx]))  # ONE upload
                toks_dev, ctx_dev = packed[0], packed[1]
                active_dev = jnp.asarray(active)
                ones = active_dev.astype(jnp.int32)
        block_tables = self._block_tables_device()
        with _span("repro.engine.decode_call"):
            tok_ids, self.k_pools, self.v_pools = self._paged_decode(
                self.params, self.k_pools, self.v_pools, toks_dev,
                ctx_dev, block_tables, active_dev)
        self._count_decode(decode_items)
        nxt = tuple(sorted(
            (r.rid, self.slot_of[r.rid], r.m + 1,
             len(self.token_ids[r.rid]) + 1) for r, _ in decode_items))
        self._decode_state = {"key": nxt, "toks": tok_ids,
                              "ctx": ctx_dev + ones,
                              "active": active_dev, "ones": ones}
        with _span("repro.engine.fetch"):
            return np.asarray(tok_ids)  # repro: allow-host-sync(per-step sampled-token fetch - ids must reach the host to extend prompts and detect EOS; (nslots,) int32 only)

    def _count_decode(self, decode_items) -> None:
        """Count one decode program call, its active rows, and the pages
        they attend over: a row at ``m`` reads ``m + 1`` positions."""
        pg = self.ecfg.page_size
        # repro: allow-txn-coverage(phase_stats counts programs dispatched - an aborted attempt's programs still ran, and every abort point precedes an attempt's first dispatch)
        self.phase_stats[SK.DECODE_CALLS] += 1
        self.phase_stats[SK.DECODE_ROWS] += len(decode_items)
        self.phase_stats[SK.DECODE_LIVE_PAGES] += sum(
            -(-(r.m + 1) // pg) for r, _ in decode_items)

    # ------------------------------------------------------------------ #
    def step(self) -> int:
        """Run one scheduler batch ATOMICALLY.  Returns the number of
        items executed.

        The whole batch — preemptions, swap-outs/ins, slot claims,
        prefix attach/CoW, allocation, pricing, compute — runs inside a
        step transaction (``serving.txn``).  An injected transient
        device fault (``FaultError``) or a corrupt host snapshot
        (``IntegrityError``) aborts the attempt: every control-plane
        participant rolls back to batch start, the error's repairs drop
        any poisoned entries (degrading their requests to recompute),
        and the step retries — so generated tokens are identical to the
        fault-free run by construction.  A real ``OutOfPagesError``
        also rolls back (the caller observes invariant-clean state) but
        re-raises: it signals an accounting bug, not a survivable
        fault."""
        if not self.sched.has_work():
            return 0
        with _span("repro.engine.step"):
            return self._step_retrying()

    def _step_retrying(self) -> int:
        """``step``'s attempts: roll back and retry on a survivable
        fault, re-raise on an accounting bug."""
        self._step_no += 1  # repro: allow-txn-coverage(step identity deliberately survives rollback - a retried attempt is the SAME step, and drain/fault keying depends on that)
        for attempt in range(_MAX_STEP_ATTEMPTS):
            # repro: allow-txn-coverage(attempt bookkeeping is reset at every attempt start and keys the per-attempt fault draws - restoring it would replay attempt 0's faults forever)
            self._attempt, self._alloc_ordinal = attempt, 0
            txn = self._begin_txn()
            t0 = time.perf_counter()
            try:
                executed = self._step_attempt()
            except (FaultError, IntegrityError) as e:
                txn.rollback()
                aborted_s = time.perf_counter() - t0
                self.wall += aborted_s  # repro: allow-txn-coverage(measured wall of the aborted attempt is real elapsed time - rolling it back would hide the Fig. 9 recovery cost)
                # repro: allow-txn-coverage(recovery accounting counts rollbacks so it must survive them - written only AFTER txn.rollback, never inside a txn)
                self.recovery_stats[SK.ROLLBACKS] += 1
                self.recovery_stats[SK.WALL_ABORTED_S] += aborted_s
                if isinstance(e, IntegrityError):
                    self.recovery_stats[SK.INTEGRITY_FAILURES] += 1
                    self.recovery_stats[SK.DEGRADED_RECOMPUTES] += 1
                    for repair in e.repairs:   # on rolled-back state
                        repair()
                else:
                    self.recovery_stats[SK.ALLOC_FAULTS] += 1
                continue
            except OutOfPagesError:
                txn.rollback()
                self.recovery_stats[SK.ROLLBACKS] += 1
                raise
            if (self._straggler is not None and executed
                    and self._straggler.observe(predicted_s=self._last_dt,
                                                actual_s=self._last_wall)):
                self._requeue_stragglers()
            return executed
        raise RuntimeError(
            f"step {self._step_no}: {_MAX_STEP_ATTEMPTS} fault-recovery "
            f"attempts exhausted")

    @_spanned("repro.engine.txn")
    def _begin_txn(self) -> StepTxn:
        """Open the step transaction: common participants via
        ``begin_step_txn`` plus the engine-local view.  Device KV needs
        only reference saves — JAX arrays are immutable, so restoring
        ``cache``/``k_pools``/``v_pools`` rolls back every in-step
        scatter for free."""
        txn = begin_step_txn(
            scheduler=self.sched, allocator=self.allocator,
            store=self.swap_store,
            requests=self.sched.waiting + self.sched.running)
        cache = self.cache
        pools = (self.k_pools, self.v_pools) if self._pooled else None
        slot_of, free_slots = dict(self.slot_of), list(self.free_slots)
        token_ids = {k: list(v) for k, v in self.token_ids.items()}
        outputs = {k: list(v) for k, v in self.outputs.items()}
        page_keys = dict(self._page_keys_of)
        page_tokens = dict(self._page_tokens_of)
        skip = dict(self._prefix_skip)
        bt_cache = self._bt_cache
        # deep copy: the delta rebuild mutates the mirror in place
        bt_host = np.array(self._bt_host) \
            if self._bt_host is not None else None
        decode_state = self._decode_state   # replaced wholesale per step
        pending = OrderedDict(self._pending_swaps)
        demotes = OrderedDict(self._pending_demotes)
        runs = OrderedDict(self._pending_runs)
        scalars = (self._tier_swap_s, self._carry_swap_s,
                   self._carry_out, self.now)
        stats = dict(self.swap_stats)
        nlogs = len(self.batch_logs)

        def restore() -> None:
            self.cache = cache
            if pools is not None:
                self.k_pools, self.v_pools = pools
            self.slot_of, self.free_slots = dict(slot_of), list(free_slots)
            self.token_ids = {k: list(v) for k, v in token_ids.items()}
            self.outputs = {k: list(v) for k, v in outputs.items()}
            self._page_keys_of = dict(page_keys)
            self._page_tokens_of = dict(page_tokens)
            self._prefix_skip = dict(skip)
            self._bt_cache = bt_cache
            self._bt_host = bt_host
            self._decode_state = decode_state
            self._pending_swaps = OrderedDict(pending)
            self._pending_demotes = OrderedDict(demotes)
            self._pending_runs = OrderedDict(runs)
            (self._tier_swap_s, self._carry_swap_s,
             self._carry_out, self.now) = scalars
            self.swap_stats = dict(stats)
            del self.batch_logs[nlogs:]

        txn.add(restore)
        return txn

    def _requeue_stragglers(self) -> None:
        """``StragglerMonitor`` flagged the step (measured wall far past
        the cost-model prediction): requeue every running request
        through the scheduler's preemption path so the next batch
        re-plans from a clean slate.  Swap charges are owed to the next
        executed batch, exactly like an empty-admission round."""
        self.recovery_stats[SK.STRAGGLER_REQUEUES] += 1
        for victim in list(self.sched.running):
            self.sched._preempt(victim)
            s, o = self._handle_preempted(victim)
            self._carry_swap_s += s
            self._carry_out += o

    def _handle_preempted(self, victim: Request) -> Tuple[float, int]:
        """Free (or swap out) one full-preemption victim; returns the
        (virtual swap time, swap-out count) owed to the draining
        batch."""
        if victim.suspended:
            m = victim.swap_out_m   # device-resident portion only
            swapper = (self._swap_out_paged if self._pooled
                       else self._swap_out)
            if swapper(victim):      # False: store full, fell back
                return self._swap_time(m), 1
        else:
            if self._pooled:
                self.swap_store.discard_runs(victim.rid)
                self._purge_pending_runs(victim.rid)
            self._release(victim.rid)
        return 0.0, 0

    def _step_attempt(self) -> int:
        """One attempt at the current step (see ``step``)."""
        t0 = time.perf_counter()
        self.allocator.now = self.now   # replacement-policy clock
        with _span("repro.engine.schedule"):
            batch = self.sched.get_next_batch()
        swap_s = 0.0
        num_swap_out = num_swap_in = 0
        with _span("repro.engine.preempt"):
            # page-level partial preemptions first: chronologically
            # they precede any later FULL preemption of the same victim,
            # and the tail pages must be snapshotted before the
            # remainder is
            for r, npages, n_tokens, mode in batch.partial_preempted:
                if not r.running:
                    # the victim was ALSO fully preempted later this
                    # round.  A swap-mode shed folds into the full
                    # suspend: the full-preempt path below snapshots the
                    # WHOLE table (tail included) as one run, so skip the
                    # data movement but keep the per-run virtual-time
                    # charge — the simulator charges it at shed time
                    # too.  A recompute-mode shed must still come OFF the
                    # table so the full snapshot (or release) matches the
                    # request's reduced bookkeeping (suspended_m
                    # excludes it).
                    if mode == "swap":
                        swap_s += self._swap_time(n_tokens)
                        num_swap_out += 1
                    else:
                        removed = self.allocator.free_tail(r.rid, npages)
                        if self.ecfg.check_invariants:
                            assert removed == n_tokens, (r.rid, removed,
                                                         n_tokens)
                    continue
                if self._shed_tail(r, npages, n_tokens, mode):
                    swap_s += self._swap_time(n_tokens)
                    num_swap_out += 1
            for victim in batch.preempted:
                s, o = self._handle_preempted(victim)
                swap_s += s
                num_swap_out += o
        if not batch.items:
            # swap-outs still happened: owe their virtual-time charge to
            # the next executed batch (mirrors the simulator's carry)
            self._carry_swap_s += swap_s
            self._carry_out += num_swap_out
            self._drain_swaps(before_step=self._step_no)
            self.wall += time.perf_counter() - t0
            return 0
        swap_s += self._carry_swap_s
        num_swap_out += self._carry_out
        self._carry_swap_s, self._carry_out = 0.0, 0

        # swap-ins: restore suspended re-admissions before classification
        # so they re-enter as decodes/short prefills, not full refills;
        # partially shed requests restore their tail runs the same way
        with _span("repro.engine.swap_in"):
            for r, _ in batch.items:
                if r.suspended:
                    swap_s += self._swap_time(r.suspended_m)
                    num_swap_in += 1
                    (self._swap_in_paged if self._pooled
                     else self._swap_in)(r)
                elif r.tail_suspended_m > 0:
                    swap_s += self._swap_time(r.tail_suspended_m)
                    num_swap_in += 1
                    self._swap_in_tail(r)

        with _span("repro.engine.admit"):
            # classify + virtual-time the batch up front
            spec = BatchSpec()
            prefill_items: List[Tuple[Request, int]] = []
            decode_items: List[Tuple[Request, int]] = []
            admitted_at = time.perf_counter()
            for r, c in batch.items:
                if r.wall_admitted is None:      # first time in a batch
                    r.wall_admitted = admitted_at
                if r.generated > 0 and c == 1 and r.remaining_prefill == 1:
                    decode_items.append((r, c))
                    spec.decodes.append((c, r.m))
                else:
                    prefill_items.append((r, c))
                    spec.prefills.append((c, r.m))

            # claim slots + control-plane allocation BEFORE pricing the
            # batch: the prefix attach may PROMOTE host-demoted pages,
            # and any allocation (or CoW remap) may reclaim-and-DEMOTE
            # registry entries — those host-link swap_time charges
            # belong to THIS batch's virtual time, mirroring the
            # simulator shadow
            for r, c in prefill_items:
                if r.rid not in self.slot_of:
                    self._claim_slot(r.rid, reset=not self._pooled)
                skip = 0
                if (self._pooled and self.ecfg.prefix_sharing
                        and r.m == 0 and not self.allocator.has(r.rid)):
                    skip = self._attach_prefix(r, c)
                if self._pooled:
                    self._prefix_skip[r.rid] = skip
                self.allocator.allocate(r.rid, c - skip)
                if self._pooled:
                    self._cow_guard(r.rid, r.m + skip)
            for r, _ in decode_items:
                self.allocator.allocate(r.rid, 1)
                if self._pooled:
                    self._cow_guard(r.rid, r.m)
        swap_s += self._tier_swap_s
        self._tier_swap_s = 0.0

        dt = (self.cost_model.batch_time(spec) if self.cost_model else 0.0) \
            + swap_s
        self.now += dt

        # ---- prefills (one batched bucketed call per round) ------------- #
        if prefill_items:
            runner = {"batched": self._run_prefills_batched,
                      "legacy": self._run_prefills_legacy,
                      "paged": (self._run_prefills_paged if self._pooled
                                else self._run_prefills_batched)}[
                                    self.ecfg.plane]
            final_tok = runner(prefill_items)
            for r, c in prefill_items:
                m_new = r.m + c
                generated = r.advance(c, self.now)
                if self._pooled and self.ecfg.prefix_sharing:
                    self._register_prefix(r, m_new)
                if generated:
                    tok = final_tok[r.rid]
                    self.outputs[r.rid].append(tok)
                    if r.finished:
                        self.sched.complete(r)
                        self._release(r.rid)
                    else:
                        self.token_ids[r.rid].append(tok)

        # ---- decodes (one batched fused step over all slots) ------------ #
        if decode_items:
            nslots = self.ecfg.nslots
            if self._pooled:
                host = self._run_decodes_paged(decode_items)
            else:
                with _span("repro.engine.upload"):
                    toks = np.zeros((nslots,), np.int32)
                    mask = np.zeros((nslots,), bool)
                    for r, _ in decode_items:
                        slot = self.slot_of[r.rid]
                        toks[slot] = self.token_ids[r.rid][-1]
                        mask[slot] = True
                    toks_dev, mask_dev = jnp.asarray(toks), jnp.asarray(mask)
                with _span("repro.engine.decode_call"):
                    tok_ids, self.cache = self._decode_many(
                        self.params, self.cache, toks_dev, mask_dev)
                self._count_decode(decode_items)
                with _span("repro.engine.fetch"):
                    host = np.asarray(tok_ids)  # repro: allow-host-sync(per-step sampled-token fetch - ids must reach the host to extend prompts and detect EOS; (nslots,) int32 only)
            for r, c in decode_items:
                slot = self.slot_of[r.rid]
                r.advance(c, self.now)
                tok = int(host[slot])
                self.outputs[r.rid].append(tok)
                if r.finished:
                    self.sched.complete(r)
                    self._release(r.rid)
                else:
                    self.token_ids[r.rid].append(tok)

        # end-of-step boundary: snapshots enqueued in EARLIER steps have
        # had a full step of compute to overlap their D2H copy; finalize
        # them now (this step's own snapshots stay in flight)
        self._drain_swaps(before_step=self._step_no)
        wall_s = time.perf_counter() - t0
        self.wall += wall_s
        self._last_dt, self._last_wall = dt, wall_s   # straggler inputs  # repro: allow-txn-coverage(straggler-monitor inputs describe the attempt that COMMITTED - an aborted attempt never reaches this line)
        if self.ecfg.check_invariants:
            self.allocator.check_invariants()
            self.swap_store.check_invariants()
            self._check_index_sync(batch)
        kv_used = sum(r.m for r in self.sched.running)
        self.batch_logs.append(BatchLog(
            t_start=self.now - dt, t_end=self.now,
            num_prefill=len(spec.prefills), num_decode=len(spec.decodes),
            tokens=spec.total_tokens, kv_used=kv_used,
            preempted=len(batch.preempted) + len(batch.partial_preempted),
            swapped_out=num_swap_out, swapped_in=num_swap_in,
            swap_s=swap_s, wall_s=wall_s,
            pages_used=self.allocator.table_pages))
        return len(batch.items)

    def _check_index_sync(self, batch) -> None:
        if self._pooled:
            # no device index in the pooled plane: the allocator's token
            # count is the position book — it must track r.m exactly
            for r, _ in batch.items:
                if r.finished or r.rid not in self.slot_of:
                    continue
                nt = (self.allocator.table(r.rid).num_tokens
                      if self.allocator.has(r.rid) else 0)
                invariant(nt == r.m, (r.rid, nt, r.m))
            return
        idx = np.asarray(self.cache["index"])  # repro: allow-host-sync(check_invariants-gated debug validation; off in benchmark configurations)
        for r, _ in batch.items:
            if r.finished or r.rid not in self.slot_of:
                continue
            slot = self.slot_of[r.rid]
            invariant(idx[slot] == r.m, (r.rid, idx[slot], r.m))

    # ------------------------------------------------------------------ #
    def run(self, requests: Sequence[Request],
            max_batches: int = 100_000) -> "EngineResult":
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        i = 0
        for _ in range(max_batches):
            while i < len(pending) and pending[i].arrival <= self.now + 1e-12:
                self.submit(pending[i])
                i += 1
            if not self.sched.has_work():
                if i >= len(pending):
                    break
                self.now = pending[i].arrival
                continue
            executed = self.step()
            if executed == 0:
                if i < len(pending):     # blocked until the next arrival
                    self.now = max(self.now, pending[i].arrival)
                    continue
                raise RuntimeError(
                    "engine deadlock: work remains but nothing schedulable")
        else:
            raise RuntimeError("engine did not converge")
        self._drain_swaps()
        if self.ecfg.check_invariants:
            assert not self._pending_swaps
            assert not self._pending_demotes
            assert not self._pending_runs
            assert len(self.swap_store) == 0, \
                f"swap store leaked rids {self.swap_store.suspended_rids}"
        sim = SimResult(requests=list(requests), batches=self.batch_logs,
                        num_preemptions=self.sched.num_preemptions,
                        num_partial_preempts=self.sched.num_partial_preempts,
                        num_swaps=self.sched.num_swaps)
        return EngineResult(outputs=dict(self.outputs), metrics=sim,
                            wall_time=self.wall,
                            swap_stats=dict(self.swap_stats),
                            num_compiles=self.num_compiles,
                            recovery_stats=dict(self.recovery_stats),
                            phase_stats=dict(self.phase_stats))


@dataclass
class EngineResult:
    outputs: Dict[int, List[int]]
    metrics: SimResult
    wall_time: float
    swap_stats: Dict[str, float] = field(default_factory=dict)
    num_compiles: int = 0
    recovery_stats: Dict[str, float] = field(default_factory=dict)
    # the engine's step-program counters (prefill rounds, grid and real
    # prefill tokens, decode calls and rows) — fig_prefix_sharing's
    # columns
    phase_stats: Dict[str, int] = field(default_factory=dict)


# --------------------------------------------------------------------- #
# reference generation (no scheduler) — the parity oracle
# --------------------------------------------------------------------- #


@functools.lru_cache(maxsize=64)
def _reference_decode_fn(cfg: ModelConfig, impl: str, moe_impl: str):
    """Jitted (params, cur (1,), cache) -> (next token (1,), cache) with
    fused greedy sampling; cached per (cfg, impl, moe_impl) so repeated
    parity-oracle calls stop paying an uncompiled decode per token."""

    def step(params, cur, cache):
        logits, cache = M.decode_step(cfg, params, cur, cache,
                                      impl=impl, moe_impl=moe_impl)
        nxt = jnp.argmax(logits[:, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
        return nxt, cache

    return jax.jit(step)


def generate_reference(cfg: ModelConfig, params: Any, prompt: Sequence[int],
                       num_tokens: int, *, cache_len: int,
                       impl: str = "reference",
                       moe_impl: str = "dense") -> List[int]:
    """Greedy generation of one request, full prefill + sequential decode.
    The decode loop is jitted (one compile per (cfg, cache shape), reused
    across calls) and samples on device — only token ids reach the host."""
    toks = jnp.asarray([list(prompt)], jnp.int32)
    logits, cache = M.prefill(cfg, params, {"tokens": toks},
                              cache_len=cache_len, impl=impl,
                              moe_impl=moe_impl)
    out: List[int] = []
    cur = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    out.append(int(cur[0]))
    decode = _reference_decode_fn(cfg, impl, moe_impl)
    for _ in range(num_tokens - 1):
        cur, cache = decode(params, cur, cache)
        out.append(int(cur[0]))
    return out
