"""Shared layers/utilities for the model zoo (raw-JAX pytree params)."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def scaled_normal(key, shape, scale: float, dtype) -> jnp.ndarray:
    """``N(0, 1) * scale`` drawn in float32, then cast to ``dtype``.  The
    barrier keeps XLA from folding ``scale`` into the sampler's own
    constants, so a draw inside ``jit`` is bit-identical to the eager
    one."""
    z = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, dtype=jnp.float32))
    return (z * scale).astype(dtype)


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    return scaled_normal(key, (in_dim, out_dim), 1.0 / math.sqrt(in_dim), dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jnp.ndarray:
    return scaled_normal(key, (vocab, dim), 0.02, dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float) -> tuple:
    """positions: (..., S) int -> cos/sin of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S)."""
    d = x.shape[-1]
    cos, sin = rope_angles(positions, d, theta)  # (B, S, d/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions: jnp.ndarray, dim: int) -> jnp.ndarray:
    """positions (B, S) -> (B, S, dim) float32 sinusoidal embedding."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          vocab_size: int) -> jnp.ndarray:
    """Mean token loss; labels < 0 are masked.  logits (..., Vpad)."""
    logits = logits.astype(jnp.float32)
    # padded vocab entries must not receive probability mass
    if logits.shape[-1] > vocab_size:
        neg = jnp.full((logits.shape[-1] - vocab_size,), -1e9, dtype=jnp.float32)
        logits = logits.at[..., vocab_size:].set(neg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def gated_mlp_init(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, d_model, d_ff, dtype),
        "wi_up": dense_init(k2, d_model, d_ff, dtype),
        "wo": dense_init(k3, d_ff, d_model, dtype),
    }


def gated_mlp(params: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    gate = x @ params["wi_gate"]
    up = x @ params["wi_up"]
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    return (fn(gate) * up) @ params["wo"]


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: int = 0, prefix_len: int = 0) -> jnp.ndarray:
    """Boolean (…, Sq, Sk) mask. prefix-LM: keys/queries with pos <
    prefix_len are bidirectional (PaliGemma image prefix)."""
    m = q_pos[..., :, None] >= k_pos[..., None, :]
    if window:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    if prefix_len:
        # prefix-LM: prefix keys are visible to every query (bidirectional
        # within the prefix, and always-visible context for the suffix)
        m |= k_pos[..., None, :] < prefix_len
    return m
