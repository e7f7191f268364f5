"""Fused paged prefill kernel's share of its roofline, in %.

Layer: kernels (``kernels/paged_attention``, fused paged prefill).  The
least time of each call is the work its real rows needed
(``chipbench/work.py`` ``prefill_work``: causal QK and PV FLOPs, the prior
K and V read, the chunk's K and V read and written) at the chip's peaks:
full 256-token chunks over a long prefix are bound by compute, short
chunks by bandwidth, and each call takes its own bound.  The time is the
device time of the prefill kernel's ops in the trace.  Moves
``ttft_p90_ms``.
"""
from chipbench.work import least_seconds, prefill_work

KERNEL = r"^paged_prefill_attention"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    least = sum(least_seconds(*prefill_work(run.model, call), run.peak)
                for s in run.traced_steps for call in s.prefill_calls if call)
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t
