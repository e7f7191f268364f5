"""Distributed flash-decode combine for sequence-sharded KV caches.

The paper's central hardware observation — decode attention is
memory-bandwidth-bound (§5.2) and "bandwidth matters more than capacity"
(§8) — maps onto a TPU pod as: shard the KV cache's SEQUENCE dimension
over the ``model`` axis so k chips stream k× the aggregate HBM bandwidth,
then combine the per-shard partial softmax with one small ``psum``
(numerator, sum-of-exp, running max).  This is flash-decoding re-expressed
as a jax collective instead of CUDA split-k blocks.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def flash_decode_seqsharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            valid: jnp.ndarray, *, axis_name: str
                            ) -> jnp.ndarray:
    """One-token decode attention over a sequence-sharded KV cache.

    Inside shard_map: q (B, H, D) replicated over ``axis_name``; k/v
    (B, S_local, Hkv, D) hold this shard's slice of the sequence;
    valid (B, S_local) marks real entries.  Returns (B, H, D) (full).
    """
    B, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(k.dtype)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)

    m_local = jnp.max(s, axis=-1)                      # (B, Hkv, G)
    m_global = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(s - m_global[..., None])
    l_local = jnp.sum(p, axis=-1)                      # (B, Hkv, G)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    l = jax.lax.psum(l_local, axis_name)
    out = jax.lax.psum(acc, axis_name) / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, H, D).astype(q.dtype)


def make_seqsharded_decode_attn(mesh: Mesh, *, seq_axis: str = "model"):
    """shard_map wrapper: full arrays in, sequence sharded internally.

    q (B, H, D); k/v (B, S, Hkv, D) sharded P(dp, seq, None, None);
    lengths (B,) = valid context per request.  Returns (B, H, D).
    """
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    def fn(q, k, v, lengths):
        S = k.shape[1]
        n = mesh.shape[seq_axis]
        S_local = S // n

        def local(qs, ks, vs, ln):
            idx = jax.lax.axis_index(seq_axis)
            pos = idx * S_local + jnp.arange(S_local)[None, :]
            valid = pos < ln[:, None]
            return flash_decode_seqsharded(qs, ks, vs, valid,
                                           axis_name=seq_axis)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, seq_axis, None, None),
                      P(dp, seq_axis, None, None), P(dp)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(q, k, v, lengths)

    return fn


def decode_attn_partials(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         valid: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode attention returning (out, running max m, sum-exp l) so a
    caller can merge additional softmax groups (deferred-append decode).
    q (B,H,D); k/v (B,S,Hkv,D); valid (B,S) -> out (B,H,D), m/l (B,H)."""
    B, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(k.dtype)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return (out.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def flash_decode_seqsharded_partials(q, k, v, valid, *, axis_name: str):
    """Sequence-sharded flash decode returning global (out, m, l)."""
    B, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(k.dtype)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_local = jnp.max(s, axis=-1)
    m = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(s - m[..., None])
    l = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)
    acc = jax.lax.psum(
        jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32), axis_name)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return (out.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def make_seqsharded_decode_attn_partials(mesh: Mesh, *,
                                         seq_axis: str = "model"):
    """shard_map wrapper of the partials variant (full arrays in/out)."""
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    def fn(q, k, v, lengths):
        S = k.shape[1]
        n = mesh.shape[seq_axis]
        S_local = S // n

        def local(qs, ks, vs, ln):
            idx = jax.lax.axis_index(seq_axis)
            pos = idx * S_local + jnp.arange(S_local)[None, :]
            valid = pos < ln[:, None]
            return flash_decode_seqsharded_partials(qs, ks, vs, valid,
                                                    axis_name=seq_axis)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, seq_axis, None, None),
                      P(dp, seq_axis, None, None), P(dp)),
            out_specs=(P(dp, None, None), P(dp, None), P(dp, None)),
            check_vma=False,
        )(q, k, v, lengths)

    return fn


# --------------------------------------------------------------------- #
# reference (single-device oracle)
# --------------------------------------------------------------------- #

def decode_attn_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          lengths: jnp.ndarray) -> jnp.ndarray:
    """q (B,H,D); k/v (B,S,Hkv,D); lengths (B,) -> (B,H,D), fp32 softmax."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
