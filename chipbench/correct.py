"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the
configuration's reference once each, over the prompt followed by the
tokens the program served.  At every served position the reference's
float32 logits give the gap by which the served token's logit lies below
the reference's best; greedy serving at the configuration's precision
keeps that gap near rounding.  The widest gap over the sample is held to
the configuration's limit.

The control (``quant="fp8"``) reads the same positions from the
reference computed one precision step below the configuration's, and takes
the gap of the token it ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

T_BLOCK = 512        # sequences are padded to a multiple of this length


def pick(finished: Sequence, seed: int, min_tokens: int, max_requests: int
         ) -> List:
    """A sample of finished requests drawn from ``seed``: the longest, up
    to three that were preempted, then others at random until it holds
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    rng = np.random.default_rng([seed % (1 << 63), 12])
    longest = max(finished, key=lambda r: (r.input_len + r.output_len, r.rid))
    chosen = [longest]
    pre = [r for r in finished if r is not longest
           and (r.preemptions or r.partial_preemptions)]
    for i in rng.permutation(len(pre))[:3]:
        chosen.append(pre[i])
    rest = [r for r in finished if all(r is not c for c in chosen)]
    for i in rng.permutation(len(rest)):
        if (sum(r.output_len for r in chosen) >= min_tokens
                or len(chosen) >= max_requests):
            break
        chosen.append(rest[i])
    return chosen


def _gap_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(ref, other_logits, served, n):
        """Per position: the reference's best logit less its logit of the
        served token, and less its logit of the token ``other_logits``
        ranks first.  Positions at or past ``n`` read 0."""
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        top = jnp.argmax(other_logits, axis=-1)
        alt = jnp.take_along_axis(ref, top[:, None], axis=-1)[:, 0]
        live = jnp.arange(ref.shape[0]) < n
        return (jnp.where(live, best - got, 0.0),
                jnp.where(live, best - alt, 0.0))
    return gaps


def compare(ref_mod, arch, params, items, k_read: int, control: bool = False
            ) -> Dict[str, float]:
    """Widest gap over ``items``, a list of ``(prompt, served)`` pairs.
    ``k_read`` is the fixed number of read positions (at least the
    longest served count), so one program serves every request of a
    length block.  With ``control`` also the control's widest gap."""
    import jax.numpy as jnp
    gaps = _gap_fn()
    widest = widest_ctl = 0.0
    for prompt, served in items:
        n_in, n_out = len(prompt), len(served)
        seq = list(prompt) + list(served[:-1])
        t_pad = -(-len(seq) // T_BLOCK) * T_BLOCK
        toks = np.zeros(t_pad, np.int32)
        toks[:len(seq)] = seq
        pos = np.full(k_read, n_in - 1, np.int32)
        pos[:n_out] = np.arange(n_in - 1, n_in - 1 + n_out)
        srv = np.zeros(k_read, np.int32)
        srv[:n_out] = served
        toks, pos, srv = jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(srv)
        ref = ref_mod.logits_at(arch, params, toks, pos)
        other = ref_mod.logits_at(arch, params, toks, pos, "fp8") \
            if control else ref
        g, gc_ = gaps(ref, other, srv, n_out)
        widest = max(widest, float(jnp.max(g)))
        widest_ctl = max(widest_ctl, float(jnp.max(gc_)))
        del ref, other
    out = {"logit_gap": widest}
    if control:
        out["control_logit_gap"] = widest_ctl
    return out
