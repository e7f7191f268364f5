"""Chip smoke run: serve Qwen3-4B at published widths on one TPU.

    python chip_smoke.py [--seed N]

One process drives the engine's main serving path on the first TPU that
JAX finds: ``Engine`` with ``plane="paged"``, the scheduler, the paged KV
manager, preemption and the prefix cache.  Weights are random, drawn from
``--seed``; nothing is downloaded.  Phases, each printing its own lines:

  device   the TPU JAX reports, and the cost model's peaks for its kind.
  kernels  both paged Pallas kernels against the jnp reference at
           Qwen3-4B widths, with the serving page size and chunk.
  serve    16 requests through the full-width engine, with a page pool
           tight enough to preempt and a shared 512-token prefix.
  tokens   a 2-layer float32 cut of the same widths, greedy tokens of the
           paged engine against ``generate_reference``.

A phase that fails raises, and the script exits non-zero without its last
line.  On success the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Every time printed here is a smoke figure from one run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (Request, TheoreticalCostModel,  # noqa: E402
                        hardware_for_device, make_scheduler)
from repro.core import stat_keys as SK  # noqa: E402
from repro.kernels.paged_attention.ops import (  # noqa: E402
    paged_decode, paged_prefill_attention)
from repro.kernels.paged_attention.ref import (  # noqa: E402
    paged_decode_reference, paged_prefill_reference, scatter_rows)
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import Engine, EngineConfig, generate_reference  # noqa: E402

ARCH = "qwen3-4b"
PAGE = 16
CHUNK = 256          # largest ladder bucket; the prefill kernel's VMEM
#                      holds chunk x G score rows, and 512 overflows it
MIN_BUCKET = 64      # bucket ladder 64, 128, 256: three prefill programs
NSLOTS = 8
CACHE_LEN = 2048
POOL_TOKENS = 4096   # tight enough that 8 running requests must preempt
N_REQUESTS = 16
PREFIX = 512         # tokens shared by every even-numbered request
TOKEN_CHECK = 16     # greedy tokens compared per request in the f32 check
NEAR_TIE = 1e-3      # reference top-2 logit gap below which a token may flip
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Seconds XLA spends compiling, and the persistent compilation
    cache's hits and misses, from JAX's own monitoring events (tracing
    and lowering are host time, left in the wall)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return (f"{self.seconds!r} s compiling so far, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def phase_device():
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev.platform!r} devices")
    log("device", f"platform={dev.platform} device_kind={dev.device_kind!r} "
                  f"count={len(devs)}")
    hw = hardware_for_device(dev.device_kind)
    log("device", f"cost-model peaks {hw.name}: {hw.flops:.4g} FLOP/s, "
                  f"{hw.hbm_bw:.4g} B/s HBM, {hw.hbm_cap:.4g} B")
    return dev, devs, hw


# --------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------- #

def _check_close(phase: str, name: str, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    log(phase, f"{name}: max |kernel - reference| = {err!r}")
    np.testing.assert_allclose(got, want, **BF16_TOL, err_msg=name)


def phase_kernels(cfg, seed: int) -> None:
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    B, maxp = NSLOTS, CACHE_LEN // PAGE
    P = B * maxp + 64                          # 64 pages no table maps
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    bf = jnp.bfloat16
    kp = jax.random.normal(ks[0], (P, Hkv, PAGE, D), bf)
    vp = jax.random.normal(ks[1], (P, Hkv, PAGE, D), bf)
    # the kernels index layer 1 of a stack of two; layer 0 holds NaNs,
    # which a read of the wrong layer would carry into the outputs
    layer = 1
    k_stack = jnp.stack([jnp.full_like(kp, jnp.nan), kp])
    v_stack = jnp.stack([jnp.full_like(vp, jnp.nan), vp])
    bt = jax.random.permutation(ks[2], P)[:B * maxp] \
        .reshape(B, maxp).astype(jnp.int32)
    log("kernels", f"Qwen3-4B widths H={H} Hkv={Hkv} D={D}, page {PAGE}, "
                   f"chunk {CHUNK}, {B} rows x {maxp} pages, pool {P} pages, "
                   f"layer {layer} of 2")

    # decode: one query token per row over up to CACHE_LEN positions,
    # its new K/V row written at position ctx - 1 first
    q = jax.random.normal(ks[3], (B, H, D), bf)
    kn = jax.random.normal(ks[7], (B, Hkv, D), bf)
    vn = jax.random.normal(ks[8], (B, Hkv, D), bf)
    ctx = jnp.asarray([1, 15, 16, 17, 700, 1024, 2047, 2048], jnp.int32)
    got, nk, nv = jax.jit(paged_decode)(q, kn, vn, k_stack, v_stack, bt, ctx,
                                        layer)
    pos = ctx - 1
    dest = jnp.take_along_axis(bt, (pos // PAGE)[:, None], 1)[:, 0] * PAGE \
        + pos % PAGE
    rk, rv = scatter_rows(kp, dest, kn), scatter_rows(vp, dest, vn)
    want = paged_decode_reference(q.astype(jnp.float32),
                                  rk.astype(jnp.float32),
                                  rv.astype(jnp.float32), bt, ctx)
    _check_close("kernels", "paged decode", got, want)
    for name, new, ref in (("K", nk[layer], rk), ("V", nv[layer], rv)):
        if not np.array_equal(np.asarray(new, np.float32),
                              np.asarray(ref, np.float32)):
            raise AssertionError(f"decode wrote its {name} rows wrong")
    _check_other_layer("kernels", "paged decode", nk, nv, layer)

    # prefill: full, partial, mid-page and inert rows
    qc = jax.random.normal(ks[4], (B, CHUNK, H, D), bf)
    kc = jax.random.normal(ks[5], (B, CHUNK, Hkv, D), bf)
    vc = jax.random.normal(ks[6], (B, CHUNK, Hkv, D), bf)
    starts = jnp.asarray([0, 0, 16, 5, 512, 1000, 1792, 0], jnp.int32)
    lengths = jnp.asarray([CHUNK, 1, CHUNK, 100, 37, CHUNK, CHUNK, 0],
                          jnp.int32)
    out, nk, nv = paged_prefill_attention(qc, kc, vc, k_stack, v_stack, bt,
                                          starts, lengths, layer)
    rout, rk, rv = paged_prefill_reference(qc, kc, vc, kp, vp, bt, starts,
                                           lengths)
    valid = np.arange(CHUNK)[None, :] < np.asarray(lengths)[:, None]
    _check_close("kernels", "paged prefill output (real rows)",
                 np.asarray(out, np.float32)[valid],
                 np.asarray(rout, np.float32)[valid])
    _check_close("kernels", "paged prefill K pool", nk[layer], rk)
    _check_close("kernels", "paged prefill V pool", nv[layer], rv)
    unowned = np.setdiff1d(np.arange(P), np.asarray(bt).ravel())
    for name, new, old in (("K", nk[layer], kp), ("V", nv[layer], vp)):
        if not np.array_equal(np.asarray(new)[unowned],
                              np.asarray(old)[unowned]):
            raise AssertionError(f"prefill changed unowned {name} pages")
    _check_other_layer("kernels", "paged prefill", nk, nv, layer)
    log("kernels", f"{len(unowned)} unowned pages of each pool are "
                   "bit-identical after prefill")


def _check_other_layer(phase: str, name: str, nk, nv, layer: int) -> None:
    """Every layer but ``layer`` of both stacks still holds only NaNs."""
    for new in (nk, nv):
        other = np.delete(np.asarray(new, np.float32), layer, axis=0)
        if not np.isnan(other).all():
            raise AssertionError(f"{name} wrote another layer")
    log(phase, f"{name}: the other layer of each stack is untouched")


def assert_kernels_compiled(eng: Engine) -> None:
    """The engine's compiled paged steps hold the Pallas kernels
    (``tpu_custom_call``), not the jnp reference."""
    ns = eng.ecfg.nslots
    bt = jnp.zeros((ns, eng.max_pages), jnp.int32)
    zi = jnp.zeros((ns,), jnp.int32)
    steps = {
        "prefill": eng._paged_prefill.lower(
            eng.params, eng.k_pools, eng.v_pools,
            jnp.zeros((ns, eng.buckets[-1] + 2), jnp.int32), bt),
        "decode": eng._paged_decode.lower(
            eng.params, eng.k_pools, eng.v_pools, zi, zi, bt,
            jnp.zeros((ns,), bool)),
    }
    for name, lowered in steps.items():
        n = lowered.compile().as_text().count("tpu_custom_call")
        log("kernels", f"engine paged {name} step: {n} tpu_custom_call "
                       "in compiled HLO")
        if n == 0:
            raise AssertionError(f"paged {name} step runs no Pallas kernel")


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #

def make_requests(vocab: int, seed: int):
    rs = np.random.RandomState(seed)
    prefix = rs.randint(0, vocab, size=PREFIX).tolist()
    reqs = []
    for rid in range(N_REQUESTS):
        shared = rid % 2 == 0
        n_in = int(rs.randint(PREFIX + PAGE if shared else 256, 1025))
        n_out = int(rs.randint(32, 129))
        head = prefix if shared else []
        prompt = head + rs.randint(0, vocab, size=n_in - len(head)).tolist()
        reqs.append(Request(rid=rid, input_len=n_in, output_len=n_out,
                            arrival=0.0, prompt=prompt))
    return reqs


def build_engine(cfg, params, hw, pool_tokens: int) -> Engine:
    sched = make_scheduler("vllm", pool_tokens, S=CACHE_LEN, page_size=PAGE)
    return Engine(cfg, params, sched,
                  EngineConfig(nslots=NSLOTS, cache_len=CACHE_LEN,
                               chunk=CHUNK, min_bucket=MIN_BUCKET,
                               page_size=PAGE, plane="paged"),
                  cost_model=TheoreticalCostModel(cfg, hw))


def check_outputs(reqs, outputs, vocab: int) -> int:
    for r in reqs:
        out = outputs.get(r.rid, [])
        if not r.finished or len(out) != r.output_len:
            raise AssertionError(f"request {r.rid}: finished={r.finished}, "
                                 f"{len(out)} of {r.output_len} tokens")
        if not all(0 <= t < vocab for t in out):
            raise AssertionError(f"request {r.rid}: id outside vocabulary")
    return sum(len(outputs[r.rid]) for r in reqs)


def phase_serve(cfg, dev, hw, seed: int, clock: CompileClock) -> None:
    t = time.perf_counter()
    c0 = clock.seconds
    params = jax.block_until_ready(M.init_params(cfg, jax.random.PRNGKey(seed)))
    pbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log("serve", f"{ARCH} {cfg.dtype}: {cfg.num_layers} layers, "
                 f"{pbytes} parameter bytes, init {time.perf_counter() - t!r} s "
                 f"({clock.seconds - c0!r} s compiling)")

    per_token = cfg.num_layers * 2 * cfg.kv_dim * jnp.dtype(cfg.dtype).itemsize
    stats = dev.memory_stats()
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    # two copies of the pools live at once: no step donates its inputs,
    # and the step transaction keeps the pre-step pools
    fit = (free - (1 << 30)) // (2 * per_token) // PAGE * PAGE
    pool_tokens = min(POOL_TOKENS, fit)
    if pool_tokens < CACHE_LEN:
        raise RuntimeError(f"{free} free bytes hold only {fit} pool tokens")
    log("serve", f"pool: {pool_tokens} tokens ({pool_tokens // PAGE} pages "
                 f"of {PAGE}), {pool_tokens * per_token} bytes per copy of "
                 f"K+V, {2 * pool_tokens * per_token} bytes for two copies; "
                 f"{free} bytes were free after the weights")

    eng = build_engine(cfg, params, hw, pool_tokens)
    assert_kernels_compiled(eng)
    log("serve", "warmup() skipped: its page-run loop compiles two programs "
                 f"for each run length 1..{eng.max_pages}")

    reqs = make_requests(cfg.vocab_size, seed)
    log("serve", f"{len(reqs)} requests, prompts "
                 f"{min(r.input_len for r in reqs)}-"
                 f"{max(r.input_len for r in reqs)} tokens, outputs "
                 f"{min(r.output_len for r in reqs)}-"
                 f"{max(r.output_len for r in reqs)}; even rids share a "
                 f"{PREFIX}-token prefix")
    c0 = clock.seconds
    t = time.perf_counter()
    res = eng.run(reqs)
    wall = time.perf_counter() - t
    compile_s = clock.seconds - c0
    generated = check_outputs(reqs, res.outputs, cfg.vocab_size)
    preempt = (res.metrics.num_preemptions
               + res.metrics.num_partial_preempts)
    hits = eng.swap_stats[SK.TRIE_HITS]
    log("serve", f"finished {sum(r.finished for r in reqs)}/{len(reqs)} "
                 f"requests, {generated} tokens generated")
    log("serve", f"compile_s={compile_s!r} run_wall_s={wall!r}")
    log("serve", f"preemptions={preempt} trie_hits={hits} "
                 f"batches={len(res.metrics.batches)} "
                 f"num_compiles={res.num_compiles}")
    log("serve", f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    log("serve", f"compile clock: {clock}")
    if preempt < 1:
        raise AssertionError("no preemption: the pool was not tight")
    if hits < 1:
        raise AssertionError("no prefix-cache hit")


# --------------------------------------------------------------------- #
# float32 token check
# --------------------------------------------------------------------- #

def _top2_gap(cfg, params, tokens) -> float:
    logits, _ = M.prefill(cfg, params, {"tokens": jnp.asarray([tokens])},
                          cache_len=CACHE_LEN)
    top = np.sort(np.asarray(logits[0, :cfg.vocab_size], np.float64))[-2:]
    return float(top[1] - top[0])


def phase_tokens(base, hw, seed: int) -> None:
    cfg = dataclasses.replace(base, num_layers=2, dtype="float32")
    with jax.default_matmul_precision("highest"):
        params = M.init_params(cfg, jax.random.PRNGKey(seed + 1))
        rs = np.random.RandomState(seed + 1)
        reqs = [Request(rid=i, input_len=n, output_len=TOKEN_CHECK,
                        arrival=0.0,
                        prompt=rs.randint(0, cfg.vocab_size, size=n).tolist())
                for i, n in enumerate(rs.randint(17, 600, size=4))]
        eng = build_engine(cfg, params, hw, POOL_TOKENS)
        res = eng.run(reqs)
        check_outputs(reqs, res.outputs, cfg.vocab_size)
        agree = total = 0
        for r in reqs:
            got = res.outputs[r.rid]
            want = generate_reference(cfg, params, r.prompt, TOKEN_CHECK,
                                      cache_len=CACHE_LEN)
            for j, (a, b) in enumerate(zip(got, want)):
                total += 1
                if a == b:
                    agree += 1
                    continue
                gap = _top2_gap(cfg, params, r.prompt + want[:j])
                log("tokens", f"rid {r.rid} position {j}: engine {a}, "
                              f"reference {b}, reference top-2 gap {gap!r}")
                if gap >= NEAR_TIE:
                    raise AssertionError(
                        f"rid {r.rid} diverges at token {j} with top-2 gap "
                        f"{gap!r} >= {NEAR_TIE}")
                break        # a near tie flipped: later tokens may differ
    log("tokens", f"{cfg.num_layers}-layer float32 cut, highest matmul "
                  f"precision: {agree}/{total} compared tokens agree over "
                  f"{len(reqs)} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log("setup", f"compile cache: {setup_compile_cache()}")
    clock = CompileClock()
    dev, devs, hw = phase_device()
    cfg = get_config(ARCH)
    phase_kernels(cfg, args.seed)
    phase_serve(cfg, dev, hw, args.seed, clock)
    phase_tokens(cfg, hw, args.seed)
    log("setup", f"compile clock: {clock}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
