"""Reduce a ``jax.profiler`` trace to device busy time, time per device op
and per kernel, and idle gaps labelled with what the host was doing.

Device operations are the events of the ``XLA Ops`` line of each
``/device:...`` plane.  Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events, named ``chipbench.<what>``; the
profiler puts both on one clock.  All times are nanoseconds until
``reduce`` turns them into seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


@dataclass
class Op:
    name: str
    start: float
    end: float
    device: str = ""


@dataclass
class Span:
    name: str
    start: float
    end: float


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Tuple[List[Op], List[Span]]:
    """Device ops and harness spans of one ``.xplane.pb`` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(short_name(ev.name), ev.start_ns,
                                  ev.end_ns, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name[len(SPAN_PREFIX):],
                                          ev.start_ns, ev.end_ns))
    return ops, spans


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def labeler(spans: Sequence[Span]):
    """A function of time giving the innermost harness span open then
    (the latest to start among those open), or ``"none"``."""
    events = sorted([(sp.start, 1, i) for i, sp in enumerate(spans)]
                    + [(sp.end, 0, i) for i, sp in enumerate(spans)])
    bounds: List[float] = []
    names: List[str] = []
    stack: List[int] = []
    for t, is_start, i in events:
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        top = max(stack, key=lambda j: spans[j].start) if stack else None
        bounds.append(t)
        names.append(spans[top].name if top is not None else "none")

    def at(t: float) -> str:
        k = bisect.bisect_right(bounds, t) - 1
        return names[k] if k >= 0 else "none"
    return at


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over the devices
    step_s: float                      # union of step spans
    busy_in_steps_s: float             # device busy inside step spans
    op_s: Dict[str, float]             # device seconds per op (loops out)
    idle_by_label: Dict[str, float]    # idle seconds per host label
    gaps: List[Tuple[str, float]]      # longest idle gaps, labelled
    ops: List[Op]

    def kernel_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern`` (a
        regular expression), averaged over the devices."""
        rx = re.compile(pattern)
        return sum((o.end - o.start) for o in self.ops
                   if rx.search(o.name)) * 1e-9 \
            / max(1, len({o.device for o in self.ops}))


def reduce(ops: Sequence[Op], spans: Sequence[Span], window: str = "window",
           step: str = "step", top: int = 10) -> Reduced:
    """Reduce to the harness span named ``window``; device idle inside the
    spans named ``step`` or ``step.*`` is the program's, the rest is
    waiting for work."""
    wins = [s for s in spans if s.name == window]
    if not wins:
        raise ValueError(f"no {SPAN_PREFIX}{window} span in the trace")
    lo, hi = min(s.start for s in wins), max(s.end for s in wins)
    inner = [s for s in spans if s.name != window]
    devices = sorted({o.device for o in ops}) or [""]
    in_win = [o for o in ops if o.end > lo and o.start < hi]
    busy_per_dev = {d: merge(_clip([(o.start, o.end) for o in in_win
                                    if o.device == d], lo, hi))
                    for d in devices}
    busy = sum(e - s for iv in busy_per_dev.values() for s, e in iv) \
        / len(devices)
    steps = merge(_clip([(s.start, s.end) for s in inner
                         if s.name == step or s.name.startswith(step + ".")],
                        lo, hi))
    busy_in_steps = sum(overlap(iv, steps) for iv in busy_per_dev.values()) \
        / len(devices)
    op_s: Dict[str, float] = {}
    for o in in_win:
        if CONTAINER.match(o.name):
            continue                 # its body's ops are counted instead
        op_s[o.name] = op_s.get(o.name, 0.0) + \
            (min(o.end, hi) - max(o.start, lo)) * 1e-9 / len(devices)
    # idle gaps of the first device, each labelled by the host span open
    # at its midpoint
    iv = busy_per_dev[devices[0]]
    edges = [lo] + [t for s, e in iv for t in (s, e)] + [hi]
    gaps = []
    idle: Dict[str, float] = {}
    label = labeler(inner)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            name = label((a + b) / 2)
            gaps.append((name, (b - a) * 1e-9))
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   step_s=sum(e - s for s, e in steps) * 1e-9,
                   busy_in_steps_s=busy_in_steps * 1e-9, op_s=op_s,
                   idle_by_label=idle, gaps=gaps[:top], ops=list(in_win))
