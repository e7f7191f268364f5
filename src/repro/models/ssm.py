"""Selective-SSM (Mamba-style) branch used by the Hymba hybrid layer.

State: ``h (B, d_inner, N)`` with per-channel data-dependent decay
``a_t = exp(dt_t * A)`` and input injection ``b_t = dt_t * B_t * x_t``:
``h_t = a_t * h_{t-1} + b_t``, ``y_t = h_t @ C_t + D * x_t``.

Full-sequence mode uses an associative scan over the linear recurrence
(O(log S) depth — TPU-friendly); decode mode is the O(1) state update.
A short causal depthwise conv (kernel 4) precedes the SSM as in Mamba;
its 3-sample state is carried in the cache for decode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Params, dense_init, scaled_normal

CONV_K = 4


def init_ssm(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype),        # x, z gate
        "conv_w": scaled_normal(ks[1], (CONV_K, di), 0.2, dtype),
        "dt_proj": dense_init(ks[2], di, di, dtype),
        "dt_bias": jnp.zeros((di,), dtype),
        "bc_proj": dense_init(ks[3], di, 2 * N, dtype),        # B_t, C_t
        "A_log": jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32) *
                         jnp.ones((di, 1), jnp.float32)).astype(jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[4], di, d, dtype),
    }


def _conv_full(xin: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise conv over (B, S, di)."""
    pad = jnp.pad(xin, [(0, 0), (CONV_K - 1, 0), (0, 0)])
    out = sum(pad[:, i:i + xin.shape[1]] * w[i] for i in range(CONV_K))
    return out


def _conv_window(stream: jnp.ndarray, w: jnp.ndarray, S: int) -> jnp.ndarray:
    """Causal depthwise conv over a stream that already carries the
    CONV_K-1 samples of left context; returns the last S outputs."""
    return sum(stream[:, i:i + S] * w[i] for i in range(CONV_K))


def _ssm_core_scan(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Associative scan of h_t = a_t * h_{t-1} + b_t along axis=1 (time)."""

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def pick_chunk(S: int, pref: int) -> int:
    """Largest power-of-two divisor of S that is <= pref (>= 1)."""
    if S <= pref:  # repro: allow-recompile-hazard(S and pref are static Python ints from .shape; chunk picking is trace-time shape arithmetic)
        return S
    q = pref
    while q > 1 and S % q != 0:  # repro: allow-recompile-hazard(same trace-time shape arithmetic as above)
        q //= 2
    return max(q, 1)


def apply_ssm_full(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                   chunk: int = 128,
                   state: Optional[Dict] = None,
                   length: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, S, d) -> (y (B,S,d), final_state dict).

    The per-step state h is (di, N) — 2·ssm_expand·N times wider than the
    activation — so we never materialize it for all S.  Time is processed
    in chunks of ``chunk`` steps: an associative scan *within* the chunk
    (O(log chunk) depth) and a ``lax.scan`` carrying h *across* chunks.
    ``state`` (from a previous chunk / ``init_ssm_state``) makes this a
    continuation — the engine's chunked prefill path.

    ``length`` (B,) marks only the first ``length[b]`` steps of row b as
    real; trailing steps are shape padding whose state update is forced
    to the identity (dt = 0 → a = 1, b = 0) and whose samples never
    enter the carried conv window, so a row's final state equals the
    unpadded run's (length 0 = untouched row).
    """
    B, S, _ = x.shape
    di, N = cfg.d_inner, cfg.ssm_state
    Q = pick_chunk(S, chunk)
    xz = x @ params["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)                      # (B, S, di)
    conv_prev = (state["conv"] if state is not None
                 else jnp.zeros((B, CONV_K - 1, di), xin.dtype))
    xin_stream = jnp.concatenate([conv_prev.astype(xin.dtype), xin], axis=1)
    if length is None:
        new_conv = xin_stream[:, -(CONV_K - 1):]
    else:
        # last CONV_K-1 *valid* stream samples: indices length..length+K-2
        idx = length[:, None] + jnp.arange(CONV_K - 1)[None, :]
        new_conv = jnp.take_along_axis(xin_stream, idx[:, :, None], axis=1)
    xin = jax.nn.silu(_conv_window(xin_stream, params["conv_w"], S))

    dt = jax.nn.softplus((xin @ params["dt_proj"]).astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B,S,di)
    if length is not None:
        valid = jnp.arange(S)[None, :] < length[:, None]    # (B, S)
        dt = jnp.where(valid[..., None], dt, 0.0)
    bc = (xin @ params["bc_proj"]).astype(jnp.float32)
    Bt, Ct = jnp.split(bc, 2, axis=-1)                      # (B, S, N)
    A = -jnp.exp(params["A_log"])                           # (di, N)

    nchunk = S // Q
    dt_c = dt.reshape(B, nchunk, Q, di).swapaxes(0, 1)
    xin_c = xin.astype(jnp.float32).reshape(B, nchunk, Q, di).swapaxes(0, 1)
    Bt_c = Bt.reshape(B, nchunk, Q, N).swapaxes(0, 1)
    Ct_c = Ct.reshape(B, nchunk, Q, N).swapaxes(0, 1)

    def chunk_step(h0, inputs):
        dt_q, xin_q, B_q, C_q = inputs                      # (B,Q,...)
        a = jnp.exp(dt_q[..., None] * A[None, None])        # (B,Q,di,N)
        b = (dt_q * xin_q)[..., None] * B_q[:, :, None, :]
        # inject carry into the first step, then associative-scan the chunk
        b = b.at[:, 0].add(a[:, 0] * h0)
        h = _ssm_core_scan(a, b)                            # (B,Q,di,N)
        y = jnp.einsum("bqdn,bqn->bqd", h, C_q)
        return h[:, -1], y

    h0 = (state["h"] if state is not None
          else jnp.zeros((B, di, N), jnp.float32))
    h_last, y = jax.lax.scan(chunk_step, h0, (dt_c, xin_c, Bt_c, Ct_c))
    y = y.swapaxes(0, 1).reshape(B, S, di)
    y = y + params["D"] * xin.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = y @ params["out_proj"]
    return out, {"h": h_last, "conv": new_conv}


def init_ssm_state(cfg: ModelConfig, batch: int) -> Dict:
    return {
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, CONV_K - 1, cfg.d_inner),
                          jnp.dtype(cfg.dtype)),
    }


def apply_ssm_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                     state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, 1, d), state from init/prior step -> (y (B,1,d), state)."""
    B = x.shape[0]
    xz = x[:, 0] @ params["in_proj"]
    xin_new, z = jnp.split(xz, 2, axis=-1)                  # (B, di)
    window = jnp.concatenate([state["conv"], xin_new[:, None]], axis=1)
    xin = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, params["conv_w"]))

    dt = jax.nn.softplus((xin @ params["dt_proj"]).astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    bc = (xin @ params["bc_proj"]).astype(jnp.float32)
    Bt, Ct = jnp.split(bc, 2, axis=-1)
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt[..., None] * A[None])                    # (B,di,N)
    b = (dt * xin.astype(jnp.float32))[..., None] * Bt[:, None, :]
    h = a * state["h"] + b
    y = jnp.einsum("bdn,bn->bd", h, Ct) + params["D"] * xin.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = (y @ params["out_proj"])[:, None]
    return out, {"h": h, "conv": window[:, 1:]}
