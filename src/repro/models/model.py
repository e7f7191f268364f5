"""Decoder-LM assembly for all families, with lax.scan over stacked layers.

Families:
  dense / moe / vlm / audio : transformer blocks (attention + MLP/MoE)
  hybrid (hymba)            : parallel attention || SSM heads, then MLP
  ssm (rwkv6)               : time-mix + channel-mix

Public API (all functional):
  init_params(cfg, rng)                       -> params pytree
  train_loss(cfg, params, batch, impl=...)    -> scalar loss
  prefill(cfg, params, batch, cache_len, ...) -> (last_logits, cache)
  decode_step(cfg, params, tokens, cache, ...)-> (logits, cache)
  init_cache(cfg, batch, cache_len)           -> cache pytree

The cache pytree always carries "index" (B,) = number of tokens already in
context (== next absolute position).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (Params, embed_init, gated_mlp,
                                 gated_mlp_init, rms_norm,
                                 sinusoidal_pos_emb, softmax_cross_entropy)

# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #


def _init_layer(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), dtype),
                 "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.family == "ssm":
        p.update(rwkv_mod.init_rwkv_layer(ks[0], cfg))
        return p
    p["attn"] = attn.init_attention(ks[0], cfg)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
        p["ln_attn"] = jnp.zeros((cfg.d_model,), dtype)
        p["ln_ssm"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.num_experts:
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
    else:
        p["mlp"] = gated_mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, rng) -> Params:
    """Random parameters from ``rng``, generated on the device in one
    program.  Layers are drawn one at a time (``lax.map``) into their
    stacked slots, so no float32 draw of a whole stacked weight is ever
    live; every value equals the eager per-layer draw bit for bit."""
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_head, k_layers, k_patch = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    layers = jax.lax.map(lambda k: _init_layer(cfg, k), layer_keys)
    params: Params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(k_head, cfg.padded_vocab, cfg.d_model, dtype)
    if cfg.frontend == "patch":
        # stub projection applied to precomputed patch embeddings
        params["patch_proj"] = embed_init(k_patch, cfg.d_model, cfg.d_model, dtype)
    return params


# --------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------- #


def _mlp_or_moe(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                moe_impl: str) -> jnp.ndarray:
    if cfg.num_experts:
        fn = {"dense": moe_mod.apply_moe,
              "sparse": moe_mod.apply_moe_sparse,
              "ep": moe_mod.apply_moe_ep}[moe_impl]
        return fn(lp["moe"], cfg, x)
    act = "gelu" if cfg.family == "vlm" else "silu"
    return gated_mlp(lp["mlp"], x, act=act)


def _block_full(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                positions: jnp.ndarray, prefix_len: int, impl: str,
                moe_impl: str, cache_len: int) -> Tuple[jnp.ndarray, Any]:
    """Full-sequence transformer/hybrid/ssm block. Returns (x, cache)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    if cfg.family == "ssm":
        tm_state = rwkv_mod.init_rwkv_state(cfg, x.shape[0])
        y, st = rwkv_mod.rwkv_time_mix_full(lp, cfg, h, tm_state)
        x = x + y
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        cm, x_cm = rwkv_mod.rwkv_channel_mix(lp, cfg, h2, tm_state["x_cm"])
        x = x + cm
        st["x_cm"] = x_cm
        return x, st
    if cfg.family == "hybrid":
        a, kv = attn.attention_full(lp["attn"], cfg, h, positions,
                                    prefix_len=prefix_len, impl=impl,
                                    cache_len=min(cache_len, cfg.window) if cache_len else 0)
        s, ssm_state = ssm_mod.apply_ssm_full(lp["ssm"], cfg, h)
        y = 0.5 * (rms_norm(a, lp["ln_attn"], cfg.norm_eps)
                   + rms_norm(s, lp["ln_ssm"], cfg.norm_eps))
        x = x + y
        if cache_len:  # repro: allow-recompile-hazard(cache_len is a static Python int closed over per plane; one specialization per cache length by design)
            new_cache = {"k": kv["k"], "v": kv["v"],
                         "h": ssm_state["h"], "conv": ssm_state["conv"]}
    else:
        a, kv = attn.attention_full(lp["attn"], cfg, h, positions,
                                    prefix_len=prefix_len, impl=impl,
                                    cache_len=cache_len)
        x = x + a
        if cache_len:  # repro: allow-recompile-hazard(cache_len is a static Python int closed over per plane; one specialization per cache length by design)
            new_cache = {"k": kv["k"], "v": kv["v"]}
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + _mlp_or_moe(cfg, lp, h2, moe_impl)
    return x, new_cache


def _block_chunk(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                 layer_cache: Dict, start: jnp.ndarray, impl: str,
                 moe_impl: str,
                 length: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, Dict]:
    """Chunked-prefill block: continue from an existing per-layer cache.
    x: (B, c, d); start: (B,) absolute position of the chunk's first token.
    ``length`` (B,) marks the real (non-padding) prefix of each row —
    padded steps must leave the cache/recurrent state untouched.
    """
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        st = {"S": layer_cache["S"], "x_tm": layer_cache["x_tm"],
              "x_cm": layer_cache["x_cm"]}
        y, st = rwkv_mod.rwkv_time_mix_full(lp, cfg, h, st, length=length)
        x = x + y
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        cm, x_cm = rwkv_mod.rwkv_channel_mix(lp, cfg, h2, st["x_cm"],
                                             length=length)
        x = x + cm
        st["x_cm"] = x_cm
        return x, st
    if cfg.family == "hybrid":
        kv = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, kv = attn.attention_chunk(lp["attn"], cfg, h, kv, start, impl=impl,
                                     length=length)
        sst = {"h": layer_cache["h"], "conv": layer_cache["conv"]}
        s, sst = ssm_mod.apply_ssm_full(lp["ssm"], cfg, h, state=sst,
                                        length=length)
        y = 0.5 * (rms_norm(a, lp["ln_attn"], cfg.norm_eps)
                   + rms_norm(s, lp["ln_ssm"], cfg.norm_eps))
        x = x + y
        new_cache = {"k": kv["k"], "v": kv["v"], "h": sst["h"],
                     "conv": sst["conv"]}
    else:
        kv = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, kv = attn.attention_chunk(lp["attn"], cfg, h, kv, start, impl=impl,
                                     length=length)
        x = x + a
        new_cache = {"k": kv["k"], "v": kv["v"]}
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + _mlp_or_moe(cfg, lp, h2, moe_impl)
    return x, new_cache


def _block_decode(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                  layer_cache: Dict, cache_index: jnp.ndarray, impl: str,
                  moe_impl: str) -> Tuple[jnp.ndarray, Dict]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        st = {"S": layer_cache["S"], "x_tm": layer_cache["x_tm"],
              "x_cm": layer_cache["x_cm"]}
        y, st = rwkv_mod.rwkv_time_mix_decode(lp, cfg, h, st)
        x = x + y
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        cm, x_cm = rwkv_mod.rwkv_channel_mix(lp, cfg, h2, st["x_cm"])
        x = x + cm
        st["x_cm"] = x_cm
        return x, st
    if cfg.family == "hybrid":
        kv = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, kv = attn.attention_decode(lp["attn"], cfg, h, kv, cache_index,
                                      impl=impl)
        sst = {"h": layer_cache["h"], "conv": layer_cache["conv"]}
        s, sst = ssm_mod.apply_ssm_decode(lp["ssm"], cfg, h, sst)
        y = 0.5 * (rms_norm(a, lp["ln_attn"], cfg.norm_eps)
                   + rms_norm(s, lp["ln_ssm"], cfg.norm_eps))
        x = x + y
        new_cache = {"k": kv["k"], "v": kv["v"], "h": sst["h"],
                     "conv": sst["conv"]}
    else:
        kv = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, kv = attn.attention_decode(lp["attn"], cfg, h, kv, cache_index,
                                      impl=impl)
        x = x + a
        new_cache = {"k": kv["k"], "v": kv["v"]}
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + _mlp_or_moe(cfg, lp, h2, moe_impl)
    return x, new_cache


# --------------------------------------------------------------------- #
# embedding / head / frontends
# --------------------------------------------------------------------- #


def _embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
           positions: jnp.ndarray,
           patch_embeds: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, int]:
    """Returns (x (B,S,d), prefix_len)."""
    x = params["embed"][tokens]
    prefix_len = 0
    if cfg.family in ("vlm",):
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32)).astype(x.dtype)
    if cfg.frontend == "patch" and patch_embeds is not None:
        pe = patch_embeds.astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([pe, x], axis=1)
        prefix_len = patch_embeds.shape[1]
    if cfg.family == "audio":
        x = x + sinusoidal_pos_emb(positions if prefix_len == 0 else
                                   jnp.arange(x.shape[1])[None, :],
                                   cfg.d_model).astype(x.dtype)
    return x, prefix_len


def _logits(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return jnp.einsum("...d,vd->...v", x, head)


def _scan_layers(cfg: ModelConfig, params: Params, x, body,
                 unroll: bool = False):
    """lax.scan over stacked layer params (+ optional cache xs/ys).
    ``unroll=True`` linearizes the graph so compiled.cost_analysis()
    counts every layer (XLA under-counts while-loop bodies) — dry-run
    accuracy mode; runtime behaviour is identical."""
    return jax.lax.scan(body, x, params["layers"], unroll=unroll)


# --------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------- #


def train_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
               *, impl: str = "reference", moe_impl: str = "sparse",
               remat: bool = True, unroll: bool = False) -> jnp.ndarray:
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, prefix_len = _embed(cfg, params, tokens, positions,
                           batch.get("patch_embeds"))
    Sx = x.shape[1]
    pos_x = jnp.broadcast_to(jnp.arange(Sx)[None, :], (B, Sx))

    def body(xc, lp):
        xc, _ = _block_full(cfg, lp, xc, pos_x, prefix_len, impl, moe_impl, 0)
        return xc, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = _scan_layers(cfg, params, x, body, unroll=unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if prefix_len:
        x = x[:, prefix_len:]
    logits = _logits(cfg, params, x)
    return softmax_cross_entropy(logits, labels, cfg.vocab_size)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> Dict[str, Any]:
    """Empty cache pytree (used by the serving engine)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    cache: Dict[str, Any] = {"index": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "ssm":
        st = rwkv_mod.init_rwkv_state(cfg, batch)
        cache.update({k: jnp.stack([v] * L) for k, v in st.items()})
        return cache
    eff_len = min(cache_len, cfg.window) if cfg.window else cache_len
    cache["k"] = jnp.zeros((L, batch, eff_len, cfg.num_kv_heads, cfg.head_dim_), dtype)
    cache["v"] = jnp.zeros_like(cache["k"])
    if cfg.family == "hybrid":
        st = ssm_mod.init_ssm_state(cfg, batch)
        cache["h"] = jnp.stack([st["h"]] * L)
        cache["conv"] = jnp.stack([st["conv"]] * L)
    return cache


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            cache_len: int, *, impl: str = "reference",
            moe_impl: str = "sparse",
            unroll: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """Process the whole prompt; returns (last-token logits, cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, prefix_len = _embed(cfg, params, tokens, positions,
                           batch.get("patch_embeds"))
    Sx = x.shape[1]
    pos_x = jnp.broadcast_to(jnp.arange(Sx)[None, :], (B, Sx))

    def body(xc, lp):
        xc, layer_cache = _block_full(cfg, lp, xc, pos_x, prefix_len, impl,
                                      moe_impl, cache_len)
        return xc, layer_cache

    x, caches = _scan_layers(cfg, params, x, body, unroll=unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _logits(cfg, params, x[:, -1])
    length = batch.get("length")
    index = (jnp.full((B,), Sx, jnp.int32) if length is None
             else length.astype(jnp.int32) + prefix_len)
    cache = dict(caches)
    cache["index"] = index
    return logits, cache


def _block_decode_deferred(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                           layer_cache: Dict, cache_index: jnp.ndarray,
                           impl: str, moe_impl: str
                           ) -> Tuple[jnp.ndarray, Dict]:
    """Decode block with READ-ONLY KV cache; returns per-layer deltas
    (new k/v row, or full recurrent states) instead of updated caches."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        st = {"S": layer_cache["S"], "x_tm": layer_cache["x_tm"],
              "x_cm": layer_cache["x_cm"]}
        y, st = rwkv_mod.rwkv_time_mix_decode(lp, cfg, h, st)
        x = x + y
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        cm, x_cm = rwkv_mod.rwkv_channel_mix(lp, cfg, h2, st["x_cm"])
        x = x + cm
        st["x_cm"] = x_cm
        return x, st                       # states ARE the delta
    if cfg.family == "hybrid":
        kv_ro = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, delta = attn.attention_decode_deferred(lp["attn"], cfg, h, kv_ro,
                                                  cache_index, impl=impl)
        sst = {"h": layer_cache["h"], "conv": layer_cache["conv"]}
        s, sst = ssm_mod.apply_ssm_decode(lp["ssm"], cfg, h, sst)
        y = 0.5 * (rms_norm(a, lp["ln_attn"], cfg.norm_eps)
                   + rms_norm(s, lp["ln_ssm"], cfg.norm_eps))
        x = x + y
        delta = {"k_new": delta["k_new"], "v_new": delta["v_new"],
                 "h": sst["h"], "conv": sst["conv"]}
    else:
        kv_ro = {"k": layer_cache["k"], "v": layer_cache["v"]}
        a, delta = attn.attention_decode_deferred(lp["attn"], cfg, h, kv_ro,
                                                  cache_index, impl=impl)
        x = x + a
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + _mlp_or_moe(cfg, lp, h2, moe_impl)
    return x, delta


def decode_step_deferred(cfg: ModelConfig, params: Params,
                         tokens: jnp.ndarray, cache: Dict[str, Any], *,
                         impl: str = "reference", moe_impl: str = "sparse",
                         unroll: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """One decode step with DEFERRED cache append (§Perf cell A): the
    layer scan reads the cache, collects per-layer new-KV deltas, and a
    SINGLE scatter per step writes them — eliminating the per-layer
    full-buffer dynamic-update-slice that dominates the baseline's HBM
    bytes.  Numerically equivalent to ``decode_step`` (tested)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    B = tokens.shape[0]
    index = cache["index"]
    x, _ = _embed(cfg, params, tokens, index[:, None], None)
    layer_caches = {k: v for k, v in cache.items() if k != "index"}

    def body(xc, per_layer):
        lp, lc = per_layer
        xc, delta = _block_decode_deferred(cfg, lp, xc, lc, index, impl,
                                           moe_impl)
        return xc, delta

    x, deltas = jax.lax.scan(body, x, (params["layers"], layer_caches),
                             unroll=unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _logits(cfg, params, x[:, 0])

    out: Dict[str, Any] = {"index": index + 1}
    if cfg.family == "ssm":
        out.update(deltas)                  # full new states, no scatter
        return logits, out
    Smax = cache["k"].shape[2]
    slot = (jnp.mod(index, Smax) if cfg.window
            else jnp.minimum(index, Smax - 1))
    rows = jnp.arange(B)
    out["k"] = cache["k"].at[:, rows, slot].set(deltas["k_new"])
    out["v"] = cache["v"].at[:, rows, slot].set(deltas["v_new"])
    if cfg.family == "hybrid":
        out["h"] = deltas["h"]
        out["conv"] = deltas["conv"]
    return logits, out


def prefill_chunk(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                  cache: Dict[str, Any], *, impl: str = "reference",
                  moe_impl: str = "sparse", unroll: bool = False,
                  length: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Process the next c prompt tokens of each request against an
    existing cache (chunked prefill, paper §3 'chunked prefill').

    tokens: (B, c); cache["index"]: (B,) tokens already cached (= the
    absolute position of tokens[:, 0]).  Returns (last-token logits
    (B, V), updated cache with index += c).

    ``length`` (B,) enables SHAPE-STABLE bucketed chunks: only the first
    ``length[b]`` tokens of row b are real, the rest are padding.  The
    logits row is the *last valid* token's, ``index`` advances by
    ``length``, and every cache/state leaf is bit-equal to an unpadded
    call — one compiled signature serves all chunk sizes up to c.
    Rows with length 0 are inert (logits garbage, state untouched).
    """
    B, c = tokens.shape
    start = cache["index"]
    positions = start[:, None] + jnp.arange(c)[None, :]
    x, _ = _embed(cfg, params, tokens, positions, None)

    layer_caches = {k: v for k, v in cache.items() if k != "index"}

    def body(xc, per_layer):
        lp, lc = per_layer
        xc, new_lc = _block_chunk(cfg, lp, xc, lc, start, impl, moe_impl,
                                  length=length)
        return xc, new_lc

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches),
                                 unroll=unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if length is None:
        x_last = x[:, -1]
        advance = c
    else:
        last = jnp.maximum(length - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        advance = length
    logits = _logits(cfg, params, x_last)
    out = dict(new_caches)
    out["index"] = start + advance
    return logits, out


def decode_step(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                cache: Dict[str, Any], *, impl: str = "reference",
                moe_impl: str = "sparse",
                unroll: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """tokens (B,) or (B,1); one decode step. Returns (logits (B,V), cache)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    B = tokens.shape[0]
    index = cache["index"]
    x, _ = _embed(cfg, params, tokens, index[:, None], None)

    layer_caches = {k: v for k, v in cache.items() if k != "index"}

    def body(xc, per_layer):
        lp, lc = per_layer
        xc, new_lc = _block_decode(cfg, lp, xc, lc, index, impl, moe_impl)
        return xc, new_lc

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches),
                                 unroll=unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _logits(cfg, params, x[:, 0])
    out = dict(new_caches)
    out["index"] = index + 1
    return logits, out
