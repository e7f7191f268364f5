"""Paged decode kernel's share of its roofline, in %.

Layer: kernels (``kernels/paged_attention``, decode).  The least time is
the work the decode rows needed (``chipbench/work.py``
``decode_work``: K and V of every context position read once, QK and PV
FLOPs) at the chip's peaks; decode reads far more bytes than it has
FLOPs per byte, so bandwidth bounds it.  The time is the device time of
the decode kernel's ops in the trace.  Moves ``itl_p95_ms``.
"""
from chipbench.work import decode_work, least_seconds

KERNEL = r"^paged_decode_attention"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    least = sum(least_seconds(*decode_work(run.model, s.decode_ctxs),
                              run.peak)
                for s in run.traced_steps if s.decode_ctxs)
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t
