"""Share of the ``Engine.step()`` time in which no op ran on the device.

Layer: the device.  Source: the trace; 1 - device busy inside the union of
the harness's step spans over that union.  Idle time while the harness
waits for an arrival is not the program's, so it is left out.  Moves
``itl_p95_ms``.
"""


def read(run):
    t = run.trace
    if t is None or t.step_s <= 0:
        return None
    return 1.0 - t.busy_in_steps_s / t.step_s
