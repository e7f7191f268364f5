"""The work a step needs, counted from the requests and never from the
kernel's grid: padded rows, padded pages and padded chunk positions cost
nothing here, so a change of kernel cannot change what it is charged for.

Shapes come from the configuration file's ``model`` section.  KV and
activations are bfloat16 (2 bytes).  Per layer and per row:

* decode, one query at position ``c`` over ``c + 1`` keys:
  FLOPs ``4 H D (c + 1)`` (QK and PV); bytes: K and V of ``c + 1``
  positions read, ``2 (c + 1) kv_dim 2``, plus q read and out written,
  ``2 H D 2``.
* prefill chunk of ``n`` real tokens from position ``s``: causal FLOPs
  ``4 H D (n s + n (n + 1) / 2)``; bytes: the ``s`` prior positions' K
  and V read, the chunk's K and V read and written into the pool,
  ``2 (s + 2 n) kv_dim 2``, plus q read and out written, ``2 n H D 2``.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BYTES = 2  # bfloat16


def dims(m: dict) -> Tuple[int, int, int, int]:
    """(layers, query heads, head dim, kv dim) of a ``model`` section."""
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return m["num_layers"], m["num_heads"], hd, m["num_kv_heads"] * hd


def decode_work(m: dict, ctxs: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode call of every layer over rows whose
    new token sits at position ``c`` for each ``c`` in ``ctxs``."""
    L, H, D, kv = dims(m)
    flops = nbytes = 0.0
    for c in ctxs:
        flops += 4.0 * H * D * (c + 1)
        nbytes += 2.0 * (c + 1) * kv * BYTES + 2.0 * H * D * BYTES
    return L * flops, L * nbytes


def prefill_work(m: dict, rows: Iterable[Tuple[int, int]]
                 ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill call of every layer over rows of
    ``(start, n)``: ``n`` real tokens from position ``start``."""
    L, H, D, kv = dims(m)
    flops = nbytes = 0.0
    for s, n in rows:
        flops += 4.0 * H * D * (n * s + n * (n + 1) / 2.0)
        nbytes += 2.0 * (s + 2 * n) * kv * BYTES + 2.0 * n * H * D * BYTES
    return L * flops, L * nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of the compute and the
    bandwidth bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def matmul_params(m: dict) -> Tuple[int, int]:
    """(parameters in the layers' matrix products, parameters of the
    vocabulary head).  Norm gains are left out: they are no products."""
    L, H, D, kv = dims(m)
    d, ff = m["d_model"], m["d_ff"]
    per_layer = d * H * D + 2 * d * kv + H * D * d + 3 * d * ff
    return L * per_layer, m["vocab_size"] * d


def model_flops(m: dict, tokens: int, emitted: int, decode_ctxs, prefill_rows
                ) -> float:
    """Model FLOPs a step needs: ``2 P`` per token processed for the
    layers, ``2 P_head`` per token emitted (only those need logits), and
    the causal attention FLOPs of its rows."""
    p_layers, p_head = matmul_params(m)
    return (2.0 * p_layers * tokens + 2.0 * p_head * emitted
            + decode_work(m, decode_ctxs)[0]
            + prefill_work(m, prefill_rows)[0])
