"""Mixture-of-Experts layer (GShard-style dense dispatch, EP-shardable).

Expert weights are stacked ``(E, d_model, moe_ff)`` so the expert axis can
be sharded over the ``model`` mesh axis (expert parallelism).  Routing uses
top-k with softmax-after-topk (Qwen style) and a capacity-free dense
dispatch: every token's expert contributions are computed with one-hot
combine einsums.  Padding experts (qwen2-moe 60->64) receive -inf router
logits and therefore exactly zero weight.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (Params, dense_init, gated_mlp,
                                 gated_mlp_init, scaled_normal)


def init_moe(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    E = cfg.padded_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], cfg.d_model, E, dtype),
        "wi_gate": scaled_normal(ks[1], (E, cfg.d_model, cfg.moe_d_ff),
                                 cfg.d_model ** -0.5, dtype),
        "wi_up": scaled_normal(ks[2], (E, cfg.d_model, cfg.moe_d_ff),
                               cfg.d_model ** -0.5, dtype),
        "wo": scaled_normal(ks[3], (E, cfg.moe_d_ff, cfg.d_model),
                            cfg.moe_d_ff ** -0.5, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = gated_mlp_init(
            ks[4], cfg.d_model, cfg.num_shared_experts * cfg.moe_d_ff, dtype)
        p["shared_gate"] = dense_init(ks[4], cfg.d_model, 1, dtype)
    return p


def apply_moe(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    E, K = cfg.padded_experts, cfg.experts_per_token
    xt = x.reshape(B * S, d)

    logits = (xt @ params["router"]).astype(jnp.float32)  # (T, E)
    if E > cfg.num_experts:  # mask padding experts out of routing
        pad_mask = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    topv, topi = jax.lax.top_k(logits, K)                  # (T, K)
    weights = jax.nn.softmax(topv, axis=-1)                # softmax over top-k
    # combine weights as a dense (T, E) matrix
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)    # (T, K, E)
    combine = jnp.einsum("tk,tke->te", weights, onehot)    # (T, E)

    # dense dispatch: every expert sees every token, weighted combine.
    gate = jnp.einsum("td,edf->tef", xt, params["wi_gate"])
    up = jnp.einsum("td,edf->tef", xt, params["wi_up"])
    h = jax.nn.silu(gate) * up                              # (T, E, f)
    out = jnp.einsum("tef,efd->ted", h, params["wo"])       # (T, E, d)
    y = jnp.einsum("te,ted->td", combine.astype(out.dtype), out)

    if cfg.num_shared_experts:
        sg = jax.nn.sigmoid((xt @ params["shared_gate"]).astype(jnp.float32))
        y = y + (sg.astype(xt.dtype) * gated_mlp(params["shared"], xt))
    return y.reshape(B, S, d)


def apply_moe_ep(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 capacity_factor: float = 1.25,
                 ep_axis: str = "model") -> jnp.ndarray:
    """Expert-parallel MoE via shard_map + all_to_all (§Perf cell B).

    Under plain GSPMD the scatter-add token buffers of
    ``apply_moe_sparse`` force replication of the expert einsums
    (measured: ~1000x the active FLOPs at 256 chips).  This is the
    production dispatch: experts live sharded over ``model``; each
    device routes its local tokens, packs per-destination-shard
    capacity buffers, exchanges them with ONE all_to_all, computes its
    local experts, and returns results with a second all_to_all.
    Per-device expert FLOPs ~= capacity_factor^2 * T_local * K / E_shards
    rows — i.e. the active compute, not E copies of it.

    Tokens overflowing a (src, dst) pair's capacity are dropped (GShard
    semantics); parity with ``apply_moe`` holds when nothing overflows.
    """
    from repro.distributed.context import get_mesh
    mesh = get_mesh()
    if mesh is None:
        raise ValueError("apply_moe_ep requires distributed.context"
                         ".set_mesh(mesh)")
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n = mesh.shape[ep_axis]
    B, S, d = x.shape
    E, K, ff = cfg.padded_experts, cfg.experts_per_token, cfg.moe_d_ff
    e_loc = E // n

    def local(xb, router, wg, wu, wo):
        T = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(T, d)
        # ---- routing: full logits from the (replicated, tiny) router -- #
        # (router must NOT be expert-sharded here: with tokens row-
        # sharded over the ep axis, gathering column blocks would mix
        # different ranks' tokens)
        logits = (xt @ router).astype(jnp.float32)          # (T, E)
        if E > cfg.num_experts:
            pad = jnp.arange(E) >= cfg.num_experts
            logits = jnp.where(pad[None, :], -1e30, logits)
        topv, topi = jax.lax.top_k(logits, K)               # (T, K)
        weights = jax.nn.softmax(topv, axis=-1)
        dest = topi // e_loc                                 # target shard
        local_eid = topi % e_loc

        # ---- pack per destination shard ------------------------------- #
        cap = max(1, int(capacity_factor * T * K / n))
        flat_dest = dest.reshape(-1)                         # (T*K,)
        oh = jax.nn.one_hot(flat_dest, n, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)
        keep = pos < cap
        slot = flat_dest * cap + jnp.where(keep, pos, 0)
        tok_idx = jnp.repeat(jnp.arange(T), K)
        send_x = jnp.zeros((n * cap, d), xt.dtype).at[slot].add(
            jnp.where(keep[:, None], xt[tok_idx], 0))
        send_e = jnp.zeros((n * cap,), jnp.int32).at[slot].add(
            jnp.where(keep, local_eid.reshape(-1) + 1, 0))

        recv_x = jax.lax.all_to_all(send_x.reshape(n, cap, d), ep_axis,
                                    0, 0).reshape(n * cap, d)
        recv_e = jax.lax.all_to_all(send_e.reshape(n, cap), ep_axis,
                                    0, 0).reshape(n * cap)

        # ---- local expert compute (capacity buffers) ------------------ #
        R = n * cap
        valid = recv_e > 0
        eid = jnp.maximum(recv_e - 1, 0)
        oh2 = jax.nn.one_hot(eid, e_loc, dtype=jnp.int32) * valid[:, None]
        pos2 = jnp.sum((jnp.cumsum(oh2, axis=0) - 1) * oh2, axis=-1)
        cap2 = max(1, int(capacity_factor * R / e_loc))
        keep2 = (pos2 < cap2) & valid
        slot2 = eid * cap2 + jnp.where(keep2, pos2, 0)
        buf = jnp.zeros((e_loc * cap2, d), xt.dtype).at[slot2].add(
            jnp.where(keep2[:, None], recv_x, 0)).reshape(e_loc, cap2, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg,
                                   preferred_element_type=jnp.float32))
        h = (h * jnp.einsum("ecd,edf->ecf", buf, wu,
                            preferred_element_type=jnp.float32)).astype(xt.dtype)
        out = jnp.einsum("ecf,efd->ecd", h, wo,
                         preferred_element_type=jnp.float32
                         ).reshape(e_loc * cap2, d).astype(xt.dtype)
        y_rows = out[slot2] * keep2[:, None].astype(out.dtype)

        # ---- return + combine at source ------------------------------- #
        ret = jax.lax.all_to_all(y_rows.reshape(n, cap, d), ep_axis,
                                 0, 0).reshape(n * cap, d)
        y_tk = ret[slot] * keep[:, None].astype(ret.dtype)
        y_tk = y_tk * weights.reshape(-1)[:, None].astype(ret.dtype)
        y = jnp.zeros((T, d), ret.dtype).at[tok_idx].add(y_tk)
        return y.reshape(xb.shape)

    # Shard the SEQUENCE over the expert axis for dispatch whenever it
    # divides: otherwise every model-rank routes (and all_to_alls) the
    # same replicated tokens — n x duplicate traffic (measured 16x on
    # cell B).  Decode steps (S=1) fall back to replicated dispatch.
    seq_spec = ep_axis if S % n == 0 else None
    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, seq_spec, None), P(None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=P(dp, seq_spec, None),
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"],
      params["wo"])

    if cfg.num_shared_experts:
        xt = x.reshape(B * S, d)
        sg = jax.nn.sigmoid((xt @ params["shared_gate"]).astype(jnp.float32))
        y = y + (sg.astype(xt.dtype)
                 * gated_mlp(params["shared"], xt)).reshape(B, S, d)
    return y


def apply_moe_sparse(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                     capacity_factor: float = 1.25) -> jnp.ndarray:
    """Capacity-based sparse dispatch (per-expert token buffers).

    FLOPs ~= K/E of the dense dispatch; used for the optimized serving path
    and the perf hillclimb.  Tokens overflowing an expert's capacity are
    dropped (standard GShard semantics) — parity with ``apply_moe`` holds
    whenever no overflow occurs.
    """
    B, S, d = x.shape
    E, K = cfg.padded_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)
    C = max(1, int(capacity_factor * T * K / E))  # repro: allow-recompile-hazard(capacity_factor is a static Python float kwarg; C is host arithmetic fixing the dispatch shape, one trace per factor)

    logits = (xt @ params["router"]).astype(jnp.float32)
    if E > cfg.num_experts:
        logits = jnp.where((jnp.arange(E) >= cfg.num_experts)[None, :], -1e30, logits)
    topv, topi = jax.lax.top_k(logits, K)
    weights = jax.nn.softmax(topv, axis=-1)  # (T, K)

    # position of each (token, k) inside its expert's buffer
    flat_e = topi.reshape(-1)                                  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (T*K, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1) * onehot       # (T*K, E)
    pos = jnp.sum(pos_in_e, axis=-1)                           # (T*K,)
    keep = pos < C
    buf_idx = flat_e * C + jnp.where(keep, pos, 0)             # (T*K,)

    tok_idx = jnp.repeat(jnp.arange(T), K)
    gathered = xt[tok_idx]                                     # (T*K, d)
    buffers = jnp.zeros((E * C, d), xt.dtype)
    buffers = buffers.at[buf_idx].add(jnp.where(keep[:, None], gathered, 0))
    buffers = buffers.reshape(E, C, d)

    gate = jnp.einsum("ecd,edf->ecf", buffers, params["wi_gate"])
    up = jnp.einsum("ecd,edf->ecf", buffers, params["wi_up"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, params["wo"]).reshape(E * C, d)

    y_tk = out[buf_idx] * jnp.where(keep[:, None], 1.0, 0.0).astype(out.dtype)
    y_tk = y_tk * weights.reshape(-1)[:, None].astype(out.dtype)
    y = jnp.zeros((T, d), out.dtype).at[tok_idx].add(y_tk)

    if cfg.num_shared_experts:
        sg = jax.nn.sigmoid((xt @ params["shared_gate"]).astype(jnp.float32))
        y = y + (sg.astype(xt.dtype) * gated_mlp(params["shared"], xt))
    return y.reshape(B, S, d)
