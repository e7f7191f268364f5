"""Model FLOPs of the traced steps over their host wall at peak, in %.

Layer: the whole step (``serving/paged_plane.py`` programs and the host
work around them).  The FLOPs are those the steps' requests needed
(``chipbench/work.py`` ``model_flops``: 2 x layer parameters per token
processed, 2 x head parameters per token emitted, causal attention), the
time is the sum of the ``Engine.step()`` spans inside the trace.  A
kernel taken off the path leaves its roofline silent; this number still
judges the whole step.  Moves ``itl_p95_ms``.
"""
from chipbench.work import model_flops


def read(run):
    steps = [s for s in run.traced_steps if s.tokens]
    wall = sum(s.t1 - s.t0 for s in steps)
    if run.trace is None or wall <= 0:
        return None
    flops = sum(model_flops(run.model, s.tokens, s.emitted, s.decode_ctxs,
                            [r for c in s.prefill_calls for r in c])
                for s in steps)
    return 100.0 * flops / (wall * run.peak["bf16_flops"])
