"""Prefill program's share of the device time of all programs, in the
traced tail.

Layer: the whole step (``serving/paged_plane.py`` programs).  Source:
the trace's ``XLA Modules`` line (``chipbench/engine_trace.py``): device
seconds of ``jit_paged_prefill_step`` runs over those of every program
run inside the harness's window span.  Moves ``itl_p95_ms``: a decode
row waits out every prefill round that shares its step.  A program
without these names gives nothing.
"""
from chipbench import engine_trace


def read(run):
    loaded = engine_trace.for_run(run)
    if loaded is None:
        return None
    secs = engine_trace.module_seconds(loaded.modules, *loaded.window)
    total = sum(secs.values())
    if engine_trace.DECODE_MODULE not in secs or total <= 0:
        return None
    return secs.get(engine_trace.PREFILL_MODULE, 0.0) / total
