"""RWKV6 "Finch" — attention-free time-mix with data-dependent decay.

Recurrence per head (state S in R^{D x D}):
    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    y_t = r_t S_{t-1} + (r_t . u . k_t) v_t        (u = per-channel bonus)

Full-sequence mode uses the *chunked* linear-attention form (GLA-style):
within a chunk of Q steps, cumulative decays W_t = prod_{s<=t} w_s give
    y_t = (r_t . W_{t-1}) S_0
          + sum_{s<t} <r_t . W_{t-1}/W_s, k_s> v_s + <r_t . u, k_t> v_t
    S_Q = diag(W_Q) S_0 + sum_s diag(W_Q/W_s) k_s^T v_s
so the state is materialized once per chunk, not per token.  Decode is the
O(1) recurrence.  Data-dependent decay w_t and token-shift mixes follow the
Finch low-rank parameterization (simplified: single LoRA per projection).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import Params, dense_init, rms_norm

LORA_R = 32


@functools.lru_cache(maxsize=None)
def _decay_ramp(d: int) -> np.ndarray:
    """The base log-decay ramp, linear in [-6, -1].  Compiled and run on
    its own, outside any enclosing trace: inlined into a jitted init,
    XLA would round the ramp differently from the eager one."""
    ramp = jax.jit(jnp.linspace, static_argnums=2).lower(-6.0, -1.0, d)
    return np.asarray(ramp.compile()(-6.0, -1.0), np.float32)


def _token_shift_mix(key, n: int, d: int) -> jnp.ndarray:
    """Token-shift mixing weights, uniform in [0.25, 0.75).  The barrier
    keeps a draw inside ``jit`` bit-identical to the eager one."""
    u = jax.lax.optimization_barrier(jax.random.uniform(key, (n, d)))
    return (u * 0.5 + 0.25).astype(jnp.float32)


def init_rwkv_layer(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 12)
    p = {
        # time-mix
        "mu": _token_shift_mix(ks[0], 4, d),
        "wr": dense_init(ks[1], d, d, dtype),
        "wk": dense_init(ks[2], d, d, dtype),
        "wv": dense_init(ks[3], d, d, dtype),
        "wg": dense_init(ks[4], d, d, dtype),
        "wo": dense_init(ks[5], d, d, dtype),
        "decay_lora_a": dense_init(ks[6], d, LORA_R, dtype),
        "decay_lora_b": dense_init(ks[7], LORA_R, d, dtype),
        "decay_base": jnp.asarray(_decay_ramp(d)),
        "bonus": (jnp.zeros((d,))).astype(jnp.float32),
        "ln_x": jnp.zeros((d,), dtype),  # per-head groupnorm scale
        # channel-mix
        "cm_mu": _token_shift_mix(ks[8], 2, d),
        "cm_r": dense_init(ks[9], d, d, dtype),
        "cm_k": dense_init(ks[10], d, cfg.d_ff, dtype),
        "cm_v": dense_init(ks[11], cfg.d_ff, d, dtype),
    }
    return p


def _token_shift(x: jnp.ndarray, prev: jnp.ndarray) -> jnp.ndarray:
    """x (B,S,d), prev (B,1,d) = last token of previous segment."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _mix(x, xs, mu):
    return x + (xs - x) * mu


def init_rwkv_state(cfg: ModelConfig, batch: int) -> Dict:
    H, D = cfg.ssm_heads, cfg.ssm_state
    return {
        "S": jnp.zeros((batch, H, D, D), jnp.float32),
        "x_tm": jnp.zeros((batch, 1, cfg.d_model), jnp.dtype(cfg.dtype)),
        "x_cm": jnp.zeros((batch, 1, cfg.d_model), jnp.dtype(cfg.dtype)),
    }


def _rkvwg(params, cfg, x, shifted):
    """Shared projection block. Returns r,k,v (B,S,H,D), w (decay), g."""
    B, S, d = x.shape
    H, D = cfg.ssm_heads, cfg.ssm_state
    mu = params["mu"]
    xr = _mix(x, shifted, mu[0].astype(x.dtype))
    xk = _mix(x, shifted, mu[1].astype(x.dtype))
    xv = _mix(x, shifted, mu[2].astype(x.dtype))
    xw = _mix(x, shifted, mu[3].astype(x.dtype))
    r = (xr @ params["wr"]).reshape(B, S, H, D)
    k = (xk @ params["wk"]).reshape(B, S, H, D)
    v = (xv @ params["wv"]).reshape(B, S, H, D)
    g = jax.nn.silu(xv @ params["wg"])
    dd = (xw @ params["decay_lora_a"]) @ params["decay_lora_b"]
    logw = params["decay_base"].astype(jnp.float32) + jnp.tanh(dd.astype(jnp.float32))
    w = jnp.exp(-jnp.exp(logw)).reshape(B, S, H, D)  # in (0,1)
    return r, k, v, w, g


def _out_norm(params, cfg, y, g, dtype):
    """Per-head RMS norm + gate + out projection. y (B,S,H,D) fp32."""
    B, S, H, D = y.shape
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-5)
    y = y.reshape(B, S, H * D) * (1.0 + params["ln_x"].astype(jnp.float32))
    return (y.astype(dtype) * g) @ params["wo"]


def rwkv_time_mix_full(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                       state: Dict, chunk: int = 64,
                       length=None) -> Tuple[jnp.ndarray, Dict]:
    """``length`` (B,) marks only the first ``length[b]`` steps of row b
    as real.  Padded steps are forced to the recurrence identity
    (w = 1, k = 0, so S_t = S_{t-1}) and the carried token-shift sample
    is the last *valid* token, making the final state bit-equal to an
    unpadded run (length 0 = untouched row)."""
    B, S, d = x.shape
    H, D = cfg.ssm_heads, cfg.ssm_state
    from repro.models.ssm import pick_chunk
    Q = pick_chunk(S, chunk)
    shifted = _token_shift(x, state["x_tm"])
    r, k, v, w, g = _rkvwg(params, cfg, x, shifted)
    if length is not None:
        valid = (jnp.arange(S)[None, :] < length[:, None])[..., None, None]
        w = jnp.where(valid, w, 1.0)
        k = jnp.where(valid, k, jnp.zeros_like(k))
    u = jnp.exp(params["bonus"]).reshape(H, D)

    nc = S // Q
    as_chunks = lambda t: t.reshape(B, nc, Q, H, D).transpose(1, 0, 3, 2, 4)
    r_c, k_c, v_c, w_c = map(as_chunks, (r.astype(jnp.float32), k.astype(jnp.float32),
                                         v.astype(jnp.float32), w))
    # (nc, B, H, Q, D) each

    def chunk_step(S0, inp):
        rq, kq, vq, wq = inp
        logW = jnp.cumsum(jnp.log(wq), axis=2)              # (B,H,Q,D)
        W = jnp.exp(logW)
        Wm1 = jnp.exp(logW - jnp.log(wq))                   # W_{t-1} = W_t / w_t
        # inter-chunk: y_inter[t] = (r_t . W_{t-1}) @ S0
        y_inter = jnp.einsum("bhqd,bhde->bhqe", rq * Wm1, S0)
        # intra-chunk (strictly lower triangular):
        att = jnp.einsum("bhqd,bhsd->bhqs", rq * Wm1, kq / W)
        tri = jnp.tril(jnp.ones((Q, Q), bool), k=-1)
        att = jnp.where(tri[None, None], att, 0.0)
        y_intra = jnp.einsum("bhqs,bhse->bhqe", att, vq)
        # current-token bonus
        y_diag = jnp.einsum("bhqd,bhqd->bhq", rq * u[None, :, None, :], kq)[..., None] * vq
        # carry: S_Q = diag(W_Q) S0 + sum_s diag(W_Q/W_s) k_s^T v_s
        WQ = W[:, :, -1]                                    # (B,H,D)
        S_new = WQ[..., None] * S0 + jnp.einsum(
            "bhsd,bhse->bhde", kq * (WQ[:, :, None, :] / W), vq)
        return S_new, y_inter + y_intra + y_diag

    S_last, y = jax.lax.scan(chunk_step, state["S"], (r_c, k_c, v_c, w_c))
    y = y.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D)      # back to (B,S,H,D)
    out = _out_norm(params, cfg, y, g, x.dtype)
    x_tm = _last_valid(x, length, state["x_tm"])
    return out, {"S": S_last, "x_tm": x_tm, "x_cm": state["x_cm"]}


def _last_valid(x: jnp.ndarray, length, fallback: jnp.ndarray) -> jnp.ndarray:
    """Token-shift carry: last valid token of x (B,S,d), or ``fallback``
    (B,1,d) for rows with length 0.  length None = whole row valid."""
    if length is None:
        return x[:, -1:]
    last = jnp.maximum(length - 1, 0)
    picked = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return jnp.where((length > 0)[:, None, None], picked, fallback)


def rwkv_time_mix_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                         state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, 1, d)."""
    B = x.shape[0]
    H, D = cfg.ssm_heads, cfg.ssm_state
    shifted = state["x_tm"]
    r, k, v, w, g = _rkvwg(params, cfg, x, shifted)
    u = jnp.exp(params["bonus"]).reshape(H, D)
    r1, k1, v1, w1 = (t[:, 0].astype(jnp.float32) for t in (r, k, v, w))
    S0 = state["S"]                                          # (B,H,D,D)
    y = jnp.einsum("bhd,bhde->bhe", r1, S0)
    y = y + jnp.einsum("bhd,bhd->bh", r1 * u[None], k1)[..., None] * v1
    S_new = w1[..., None] * S0 + jnp.einsum("bhd,bhe->bhde", k1, v1)
    out = _out_norm(params, cfg, y[:, None].reshape(B, 1, H, D), g, x.dtype)
    return out, {"S": S_new, "x_tm": x, "x_cm": state["x_cm"]}


def rwkv_channel_mix(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                     prev: jnp.ndarray,
                     length=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Channel-mix FFN with token shift; returns (out, new_prev).
    ``length`` (B,) makes new_prev the last *valid* token per row."""
    shifted = _token_shift(x, prev)
    mu = params["cm_mu"]
    xr = _mix(x, shifted, mu[0].astype(x.dtype))
    xk = _mix(x, shifted, mu[1].astype(x.dtype))
    rgate = jax.nn.sigmoid((xr @ params["cm_r"]).astype(jnp.float32)).astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ params["cm_k"]))
    return rgate * (kk @ params["cm_v"]), _last_valid(x, length, prev)
