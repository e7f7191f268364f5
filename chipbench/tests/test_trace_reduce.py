"""``chipbench/trace.py`` on a hand-built trace and on a small trace
recorded on a TPU v5e (``data/``).  The recorded trace is checked against
a second, plain computation over a 1 us timeline; no device number is
asserted from it beyond what the file holds."""
import glob
import gzip
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import trace as tr  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
MS = 1e6            # nanoseconds


def hand_built():
    ops = [tr.Op("fusion.1", 1 * MS, 3 * MS),
           tr.Op("paged_decode_attention.3", 2 * MS, 4 * MS),     # overlaps fusion.1
           tr.Op("fusion.1", 6 * MS, 7 * MS),
           tr.Op("paged_prefill_attention.1", 9 * MS, 12 * MS)]    # runs past the window
    spans = [tr.Span("window", 0, 10 * MS),
             tr.Span("step", 0.5 * MS, 5 * MS),
             tr.Span("step.decode_call", 1 * MS, 2 * MS),
             tr.Span("wait", 5 * MS, 8.5 * MS),
             tr.Span("step", 8.5 * MS, 10 * MS)]
    return ops, spans


def test_busy_idle_and_kernel_time_on_a_hand_built_trace():
    r = tr.reduce(*hand_built())
    assert r.window_s == pytest.approx(0.010)
    # busy: [1, 4] + [6, 7] + [9, 10] inside the window
    assert r.busy_s == pytest.approx(0.005)
    assert r.step_s == pytest.approx(0.0045 + 0.0015)
    assert r.busy_in_steps_s == pytest.approx(0.003 + 0.001)
    assert r.op_s["fusion.1"] == pytest.approx(0.003)
    assert r.op_s["paged_prefill_attention.1"] == pytest.approx(0.001)
    assert r.kernel_seconds("^paged_decode_attention") == pytest.approx(0.002)
    # idle [0, 1] under step; [4, 6] wait/step at 5; [7, 9] under wait
    assert r.idle_by_label == pytest.approx({"step": 0.001, "wait": 0.004})
    assert r.gaps[0] == ("wait", pytest.approx(0.002))


def test_labels_pick_the_innermost_open_span():
    _, spans = hand_built()
    label = tr.labeler(spans)
    assert label(1.5 * MS) == "step.decode_call"
    assert label(4.5 * MS) == "step"
    assert label(9 * MS) == "step"
    assert label(20 * MS) == "none"
    assert tr.labeler([])(0) == "none"


def test_merge_and_overlap():
    assert tr.merge([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.overlap([(1, 4), (5, 6)], [(3, 5.5)]) == pytest.approx(1.5)


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_trace_agrees_with_a_plain_timeline(path, tmp_path):
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(gzip.decompress(open(path, "rb").read()))
    ops, spans = tr.load(str(pb))
    assert ops and any(s.name == "window" for s in spans)
    r = tr.reduce(ops, spans)
    win = [s for s in spans if s.name == "window"]
    lo = min(s.start for s in win)
    hi = max(s.end for s in win)
    us = 1e3
    n = int(np.ceil((hi - lo) / us))
    dev = sorted({o.device for o in ops})[0]
    busy = np.zeros(n, bool)
    for o in ops:
        if o.device != dev:
            continue
        a = int(max(0, np.floor((o.start - lo) / us)))
        b = int(min(n, np.ceil((o.end - lo) / us)))
        busy[a:b] = True
    # the timeline rounds each op out to whole microseconds
    n_ops = sum(1 for o in ops if o.device == dev and o.end > lo
                and o.start < hi)
    assert r.window_s == pytest.approx((hi - lo) * 1e-9)
    assert busy.sum() * 1e-6 >= r.busy_s - 1e-9
    assert busy.sum() * 1e-6 <= r.busy_s + 2e-6 * n_ops
    # idle by label adds up to the window less busy time
    assert sum(r.idle_by_label.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6, abs=1e-9)
    # each kernel's time is the sum of its events
    for pattern in ("paged_decode_attention", "paged_prefill_attention"):
        want = sum(o.end - o.start for o in ops
                   if o.name.startswith(pattern)) * 1e-9
        assert r.kernel_seconds("^" + pattern) == pytest.approx(want)
    assert r.kernel_seconds("^paged_prefill_attention") > 0
    # every labelled gap lies under a span of that name, or under none
    inner = [s for s in spans if s.name != "window"]
    for name, secs in r.gaps:
        assert secs > 0
        assert name == "none" or any(s.name == name for s in inner)
