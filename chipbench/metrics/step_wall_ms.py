"""Mean host wall of one ``Engine.step()`` in the window, in ms.

Layer: the engine's step driver (``serving/engine.py`` ``Engine.step``).
Source: the harness's host span around each call.  Moves ``itl_p95_ms``:
a decode row waits one step per token.  Bounded by neither compute nor
bandwidth alone: it is host work plus the device time it waits for.
"""


def read(run):
    if not run.steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in run.steps) / len(run.steps)
