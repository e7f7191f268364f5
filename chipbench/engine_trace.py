"""The program's own spans and program runs in a traced run's profile.

``trace.py`` reduces a trace by the harness's ``chipbench.*`` spans.  The
engine marks each host part of ``Engine.step`` with a
``jax.profiler.TraceAnnotation`` named ``repro.engine.<part>``
(``step``, ``txn``, ``schedule``, ``preempt``, ``swap_in``, ``admit``,
``upload``, ``prefill_call``, ``decode_call``, ``fetch``, ``register``,
``drain``), on the clock of the device ops; and the device plane's
``XLA Modules`` line holds one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``.  This module reads both, beside the
harness's spans, from the ``.xplane.pb`` a ``--trace 1`` run leaves under
``.chipbench_out/trace/<cell>/``.

Program spans keep their whole name (``repro.engine.txn``), so none is
taken for a harness step span (``step`` or ``step.*``) and the union of
step spans, the device busy time and every number ``trace.reduce`` gives
stay as they were; only the labels of idle gaps get finer.  A trace from
a program without these spans or program names gives empty readings,
and the metric readers then give nothing.  Times are nanoseconds until a
function returns seconds.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as tr
from chipbench.harness import OUT

PROGRAM_PREFIX = "repro."
STEP = "repro.engine.step"
FETCH = "repro.engine.fetch"
MODULES_LINE = "XLA Modules"
PREFILL_MODULE = "jit_paged_prefill_step"
DECODE_MODULE = "jit_paged_decode_step"


def module_name(event: str) -> str:
    """``jit_paged_decode_step(1234)`` -> ``jit_paged_decode_step``."""
    return event.split("(", 1)[0]


@dataclass
class Loaded:
    ops: List[tr.Op]             # XLA Ops of the device planes
    harness: List[tr.Span]       # chipbench.* spans, prefix cut
    program: List[tr.Span]       # repro.* spans, whole names
    modules: List[tr.Op]         # XLA Modules runs, fingerprint cut

    @property
    def window(self) -> Tuple[float, float]:
        wins = [s for s in self.harness if s.name == "window"]
        if not wins:
            raise ValueError("no chipbench.window span in the trace")
        return min(s.start for s in wins), max(s.end for s in wins)


def load(path: str) -> Loaded:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = Loaded([], [], [], [])
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    out.ops.extend(tr.Op(tr.short_name(ev.name),
                                         ev.start_ns, ev.end_ns, plane.name)
                                   for ev in line.events)
                elif line.name == MODULES_LINE:
                    out.modules.extend(tr.Op(module_name(ev.name),
                                             ev.start_ns, ev.end_ns,
                                             plane.name)
                                       for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tr.SPAN_PREFIX):
                        out.harness.append(tr.Span(
                            ev.name[len(tr.SPAN_PREFIX):], ev.start_ns,
                            ev.end_ns))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        out.program.append(tr.Span(ev.name, ev.start_ns,
                                                   ev.end_ns))
    return out


def module_seconds(modules: Sequence[tr.Op], lo: float, hi: float
                   ) -> Dict[str, float]:
    """Device seconds of each program inside [lo, hi], averaged over the
    devices."""
    ndev = max(1, len({m.device for m in modules}))
    out: Dict[str, float] = {}
    for m in modules:
        a, b = max(m.start, lo), min(m.end, hi)
        if b > a:
            out[m.name] = out.get(m.name, 0.0) + (b - a) * 1e-9 / ndev
    return out


def step_host_seconds(program: Sequence[tr.Span], lo: float, hi: float
                      ) -> List[float]:
    """Host seconds of each engine step inside [lo, hi]: the step span
    less the time its sampled-id fetches, where the host waits on the
    device, cover."""
    fetches = tr.merge([(s.start, s.end) for s in program
                        if s.name == FETCH])
    out = []
    for s in program:
        if s.name != STEP or s.start < lo or s.end > hi:
            continue
        waited = tr.overlap([(s.start, s.end)], fetches)
        out.append((s.end - s.start - waited) * 1e-9)
    return out


def self_seconds(spans: Sequence[tr.Span], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds inside [lo, hi] in which each span name is the innermost
    open span (the latest to start), the rule ``trace.labeler`` labels
    idle gaps by."""
    events = sorted([(max(s.start, lo), 1, i) for i, s in enumerate(spans)
                     if s.end > lo and s.start < hi]
                    + [(min(s.end, hi), 0, i) for i, s in enumerate(spans)
                       if s.end > lo and s.start < hi])
    out: Dict[str, float] = {}
    stack: List[int] = []
    prev = None
    for t, is_start, i in events:
        if stack and prev is not None and t > prev:
            top = max(stack, key=lambda j: spans[j].start)
            name = spans[top].name
            out[name] = out.get(name, 0.0) + (t - prev) * 1e-9
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        prev = t
    return out


def reduce(loaded: Loaded, top: int = 10) -> tr.Reduced:
    """``trace.reduce`` over the harness's and the program's spans
    together: idle gaps take the innermost span of either kind."""
    return tr.reduce(loaded.ops, loaded.harness + loaded.program, top=top)


# --------------------------------------------------------------------- #
# the trace of the run a metric reader is given
# --------------------------------------------------------------------- #

_CACHE: Dict[Tuple[str, float], Loaded] = {}


def newest(root: str) -> Optional[str]:
    """The ``.xplane.pb`` the last traced run under ``root`` left."""
    paths = glob.glob(os.path.join(root, OUT.name, "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _load_cached(path: str) -> Loaded:
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(path)
    return _CACHE[key]


def find(window_s: float, root: Optional[str] = None) -> Optional[Loaded]:
    """The trace of the last traced run under ``root`` (the checkout by
    default), if its window span lasts ``window_s`` seconds, to the
    nanosecond.  The harness writes its cell's trace just before it
    reads the metrics, and a checkout runs one cell at a time, so that
    is the run's own trace; the window's length guards the match."""
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    path = newest(root)
    if path is None:
        return None
    try:
        loaded = _load_cached(path)
        lo, hi = loaded.window
    except ValueError:           # no window span: not a harness trace
        return None
    return loaded if abs((hi - lo) * 1e-9 - window_s) <= 1e-9 else None


def for_run(run) -> Optional[Loaded]:
    """The trace the harness reduced into ``run.trace``, or None for an
    untraced run."""
    return None if run.trace is None else find(run.trace.window_s)
