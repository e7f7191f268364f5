"""Plain reference for the dense GQA decoder (Qwen3, SmolLM/Llama layout).

Written from the published architecture, not from the program: token
embedding, then per layer ``x += Attn(RMSNorm(x))`` and
``x += MLP(RMSNorm(x))``, a final RMSNorm and the (tied or untied)
vocabulary head.

* RMSNorm over the last axis; the stored scale ``s`` acts as the gain
  ``1 + s``, the weight format the served program reads.
* Attention: causal, grouped-query (query head ``h`` reads kv head
  ``h // G``), optional per-head RMSNorm of q and k before RoPE (Qwen3's
  ``qk_norm``), RoPE in the rotate-half form with inverse frequencies
  ``theta ** (-2i / head_dim)``, scores scaled by ``head_dim ** -0.5``.
* MLP: ``down(silu(gate(x)) * up(x))``.

Everything is computed in float32 with ``Precision.HIGHEST`` matrix
products, one layer at a time, so the bfloat16 weights are upcast layer by
layer and never held in float32 whole.  ``quant="fp8"`` is the control:
every matrix product takes its inputs rounded to float8 e4m3 (weights
scaled per output column, activations per row), the precision step below
the configuration's bfloat16.

The weights are made here too, on the device in one jitted program from
the seed, in bfloat16 and in the pytree layout the program serves, so the
reference and the program read the same numbers and the reference takes
nothing the program made.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Arch:
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    padded_vocab: int
    qk_norm: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool

    @classmethod
    def from_model(cls, m: dict) -> "Arch":
        """From the ``model`` section of a configuration file."""
        head_dim = m.get("head_dim") or m["d_model"] // m["num_heads"]
        mult = m.get("vocab_pad_multiple", 1)
        return cls(num_layers=m["num_layers"], d_model=m["d_model"],
                   num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                   head_dim=head_dim, d_ff=m["d_ff"],
                   vocab_size=m["vocab_size"],
                   padded_vocab=-(-m["vocab_size"] // mult) * mult,
                   qk_norm=bool(m.get("qk_norm", False)),
                   rope_theta=float(m["rope_theta"]),
                   norm_eps=float(m["norm_eps"]),
                   tie_embeddings=bool(m.get("tie_embeddings", False)))


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(jnp.bfloat16)


def _layer(a: Arch, key):
    d, q, kv, ff, hd = (a.d_model, a.num_heads * a.head_dim,
                        a.num_kv_heads * a.head_dim, a.d_ff, a.head_dim)
    ks = jax.random.split(key, 11)
    attn = {"wq": _normal(ks[0], (d, q), d ** -0.5),
            "wk": _normal(ks[1], (d, kv), d ** -0.5),
            "wv": _normal(ks[2], (d, kv), d ** -0.5),
            "wo": _normal(ks[3], (q, d), q ** -0.5)}
    if a.qk_norm:
        attn["q_norm"] = _normal(ks[4], (hd,), 0.1)
        attn["k_norm"] = _normal(ks[5], (hd,), 0.1)
    return {"ln1": _normal(ks[6], (d,), 0.1),
            "ln2": _normal(ks[7], (d,), 0.1),
            "attn": attn,
            "mlp": {"wi_gate": _normal(ks[8], (d, ff), d ** -0.5),
                    "wi_up": _normal(ks[9], (d, ff), d ** -0.5),
                    "wo": _normal(ks[10], (ff, d), ff ** -0.5)}}


@functools.partial(jax.jit, static_argnums=0)
def make_params(a: Arch, key):
    """Random bfloat16 weights from ``key``: one program, layers drawn one
    at a time so no float32 draw of a stacked weight is ever whole."""
    k_embed, k_head, k_final, k_layers = jax.random.split(key, 4)
    params = {"embed": _normal(k_embed, (a.padded_vocab, a.d_model), 0.02),
              "ln_f": _normal(k_final, (a.d_model,), 0.1),
              "layers": jax.lax.map(lambda k: _layer(a, k),
                                    jax.random.split(k_layers, a.num_layers))}
    if not a.tie_embeddings:
        params["head"] = _normal(k_head, (a.padded_vocab, a.d_model), 0.02)
    return params


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #

def _round_f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32; fp8 rounds x per row and w per
    output column first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _round_f8(x, -1), _round_f8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, s, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half RoPE.  x (T, H, D); pos (T,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(a: Arch, p, h, pos, quant):
    T = h.shape[0]
    H, Hkv, D = a.num_heads, a.num_kv_heads, a.head_dim
    q = _mm(h, p["wq"], quant).reshape(T, H, D)
    k = _mm(h, p["wk"], quant).reshape(T, Hkv, D)
    v = _mm(h, p["wv"], quant).reshape(T, Hkv, D)
    if a.qk_norm:
        q = _rms(q, p["q_norm"], a.norm_eps)
        k = _rms(k, p["k_norm"], a.norm_eps)
    q, k = _rope(q, pos, a.rope_theta), _rope(k, pos, a.rope_theta)
    kh = jnp.repeat(k, H // Hkv, axis=1)              # (T, H, D)
    vh = jnp.repeat(v, H // Hkv, axis=1)
    if quant == "fp8":
        q, kh, vh = (_round_f8(t, -1) for t in (q, kh, vh))
    s = jnp.einsum("qhd,khd->hqk", q, kh, precision=HIGHEST) / math.sqrt(D)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    if quant == "fp8":
        w = _round_f8(w, -1)
    o = jnp.einsum("hqk,khd->qhd", w, vh, precision=HIGHEST)
    return _mm(o.reshape(T, H * D), p["wo"], quant)


def _mlp(p, h, quant):
    g = _mm(h, p["wi_gate"], quant)
    u = _mm(h, p["wi_up"], quant)
    return _mm(jax.nn.silu(g) * u, p["wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits_at(a: Arch, params, tokens, read_pos, quant=None):
    """Logits (len(read_pos), vocab_size) in float32 of the sequence
    ``tokens`` (T,), read at positions ``read_pos``.  Padding after the
    real tokens changes no earlier position (the mask is causal)."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        x = x + _attention(a, p["attn"], _rms(x, p["ln1"], a.norm_eps),
                           pos, quant)
        x = x + _mlp(p["mlp"], _rms(x, p["ln2"], a.norm_eps), quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[read_pos], params["ln_f"], a.norm_eps)
    head = params["embed"] if a.tie_embeddings else params["head"]
    return _mm(x, head[:a.vocab_size].T, quant)
