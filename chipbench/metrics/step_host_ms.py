"""Mean host time of one ``Engine.step()`` in the traced tail, in ms.

Layer: the engine's step driver (``serving/engine.py`` ``Engine.step``).
Source: the trace; each ``repro.engine.step`` span the engine opens,
less the time its ``repro.engine.fetch`` spans cover (the sampled-id
fetches, the one place the host waits on the device), averaged over the
steps inside the harness's window span (``chipbench/engine_trace.py``).
What is left is host work: the transaction snapshot, scheduling,
admission, uploads, dispatch, registration and the step's own
bookkeeping.  Moves ``itl_p95_ms``: a decode row waits it out every
step the device does not hide it.
"""
from chipbench import engine_trace


def read(run):
    loaded = engine_trace.for_run(run)
    if loaded is None:
        return None
    host = engine_trace.step_host_seconds(loaded.program, *loaded.window)
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
