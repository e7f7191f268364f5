"""``chipbench/engine_trace.py`` and the two metrics it feeds, on a
hand-built trace with the engine's ``repro.*`` spans nested inside the
harness's step spans and an ``XLA Modules`` line; on the small trace
recorded on a TPU v5e (``data/``); and end to end on the CPU through
``tools/engine_view.py`` (``cpu_engine_view.py``), where no number is a
device number."""
import gzip
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import engine_trace as et  # noqa: E402
from chipbench import trace as tr  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
RECORDED = os.path.join(DATA, "smollm-chat-burst.xplane.pb.gz")
MS = 1e6            # nanoseconds


def hand_built():
    ops = [tr.Op("fusion.1", 1 * MS, 3 * MS),
           tr.Op("paged_decode_attention.3", 2 * MS, 4 * MS),
           tr.Op("fusion.1", 6 * MS, 7 * MS),
           tr.Op("paged_prefill_attention.1", 9 * MS, 12 * MS)]
    harness = [tr.Span("window", 0, 10 * MS),
               tr.Span("step", 0.2 * MS, 5 * MS),
               tr.Span("step.decode_call", 1 * MS, 2 * MS),
               tr.Span("wait", 5 * MS, 8.5 * MS),
               tr.Span("step", 8.5 * MS, 10 * MS)]
    program = [tr.Span("repro.engine.step", 0.3 * MS, 4.9 * MS),
               tr.Span("repro.engine.txn", 0.4 * MS, 0.8 * MS),
               tr.Span("repro.engine.decode_call", 0.95 * MS, 2.1 * MS),
               tr.Span("repro.engine.fetch", 2.1 * MS, 4.5 * MS),
               tr.Span("repro.engine.step", 8.6 * MS, 10 * MS),
               tr.Span("repro.engine.admit", 8.7 * MS, 9.5 * MS)]
    modules = [tr.Op("jit_paged_decode_step", 1 * MS, 4 * MS),
               tr.Op("jit_add", 6 * MS, 7 * MS),
               tr.Op("jit_paged_prefill_step", 9 * MS, 12 * MS)]
    return et.Loaded(ops, harness, program, modules)


def test_program_spans_change_only_the_idle_labels():
    loaded = hand_built()
    before = tr.reduce(loaded.ops, loaded.harness)
    after = et.reduce(loaded)
    for name in ("window_s", "busy_s", "step_s", "busy_in_steps_s",
                 "op_s"):
        assert getattr(after, name) == getattr(before, name), name
    assert before.idle_by_label == pytest.approx(
        {"step": 0.001, "wait": 0.004})
    # idle [0, 1] ms, midpoint 0.5 ms, lies under the txn span
    assert after.idle_by_label == pytest.approx(
        {"repro.engine.txn": 0.001, "wait": 0.004})
    assert sum(after.idle_by_label.values()) == pytest.approx(
        after.window_s - after.busy_s)


def test_labels_take_the_innermost_span_of_either_kind():
    loaded = hand_built()
    label = tr.labeler(loaded.harness + loaded.program)
    assert label(0.25 * MS) == "step"
    assert label(0.6 * MS) == "repro.engine.txn"
    assert label(0.85 * MS) == "repro.engine.step"
    assert label(0.97 * MS) == "repro.engine.decode_call"
    assert label(1.5 * MS) == "step.decode_call"     # opened later
    assert label(3 * MS) == "repro.engine.fetch"
    assert label(6 * MS) == "wait"


def test_module_seconds_step_host_time_and_self_time():
    loaded = hand_built()
    lo, hi = loaded.window
    assert et.module_seconds(loaded.modules, lo, hi) == pytest.approx(
        {"jit_paged_decode_step": 0.003, "jit_add": 0.001,
         "jit_paged_prefill_step": 0.001})              # clipped at 10 ms
    # 4.6 ms less the 2.4 ms fetch; then 1.4 ms with no fetch
    assert et.step_host_seconds(loaded.program, lo, hi) == pytest.approx(
        [0.0022, 0.0014])
    assert et.step_host_seconds(loaded.program, lo, 9 * MS) == \
        pytest.approx([0.0022])
    own = et.self_seconds(loaded.program, lo, hi)
    assert own == pytest.approx({
        "repro.engine.step": (0.1 + 0.15 + 0.4 + 0.1 + 0.5) * 1e-3,
        "repro.engine.txn": 0.4e-3, "repro.engine.decode_call": 1.15e-3,
        "repro.engine.fetch": 2.4e-3, "repro.engine.admit": 0.8e-3})
    assert sum(own.values()) == pytest.approx((4.6 + 1.4) * 1e-3)
    assert et.module_name("jit_paged_decode_step(433822868)") == \
        "jit_paged_decode_step"


def _reader(name):
    path = os.path.join(ROOT, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    def __init__(self, trace):
        self.trace = trace


def test_readers_on_the_hand_built_trace(monkeypatch):
    loaded = hand_built()
    monkeypatch.setattr(et, "for_run", lambda run: loaded)
    run = Run(et.reduce(loaded))
    assert _reader("step_host_ms")(run) == pytest.approx(1.8)
    assert _reader("prefill_device_frac")(run) == pytest.approx(0.2)
    # a program without the paged programs' names gives nothing
    loaded.modules = [tr.Op("jit_prefill_packed", 1 * MS, 4 * MS)]
    loaded.program = []
    assert _reader("prefill_device_frac")(run) is None
    assert _reader("step_host_ms")(run) is None
    monkeypatch.setattr(et, "for_run", lambda run: None)
    assert _reader("step_host_ms")(run) is None


def test_recorded_trace_module_seconds_and_lookup(tmp_path):
    d = tmp_path / ".chipbench_out" / "trace" / "cell" / "plugins" / \
        "profile" / "t"
    d.mkdir(parents=True)
    pb = d / "t.xplane.pb"
    pb.write_bytes(gzip.decompress(open(RECORDED, "rb").read()))
    loaded = et.find(tr.reduce(*tr.load(str(pb))).window_s,
                     root=str(tmp_path))
    assert loaded is not None
    assert et.find(1.0, root=str(tmp_path)) is None
    lo, hi = loaded.window
    # plain computation: each module run's clipped length, straight
    # from the profile
    import jax
    want = {}
    for plane in jax.profiler.ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if b > a:
                    key = ev.name.split("(")[0]
                    want[key] = want.get(key, 0.0) + (b - a) * 1e-9
    got = et.module_seconds(loaded.modules, lo, hi)
    assert got == pytest.approx(want) and got["jit_prefill_packed"] > 0
    # program runs cover the ops they run, inside the window
    r = et.reduce(loaded)
    assert r.busy_s - 1e-6 <= sum(got.values()) <= r.window_s
    # a trace of a program without engine spans: readings are empty and
    # the reduction is the harness's own
    assert loaded.program == []
    assert et.step_host_seconds(loaded.program, lo, hi) == []
    base = tr.reduce(*tr.load(str(pb)))
    assert r.idle_by_label == base.idle_by_label
    assert r.busy_in_steps_s == base.busy_in_steps_s


def test_engine_view_on_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "tests",
                                      "cpu_engine_view.py"),
         "qwen3-4b.analytics-prefix", "--seconds", "6"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["step_host_ms"]["value"] > 0
    view = out["engine"]
    c = view["counters"]
    assert c["decode_calls"] > 0 and c["prefill_rounds"] > 0
    assert 0 < c["decode_occupancy"] <= 1
    assert 0 < c["prefill_grid_fill"] <= 1
    assert c["prefill_grid_tokens"] % 4 == 0          # 4 slots
    assert view["queue_wait_ms"]["n"] > 0
    assert 0 <= view["queue_wait_ms"]["p50"] <= view["queue_wait_ms"]["p90"]
    setup = view["setup_s"]
    assert sum(v for k, v in setup.items() if k != "total") == \
        pytest.approx(setup["total"])
    assert min(setup[k] for k in ("imports", "weights", "engine", "warm",
                                  "prime")) > 0
    assert set(view["host_bytes_per_s"]) == {"kv_demoted", "kv_promoted",
                                             "kv_out", "kv_in"}
    # the engine's spans sit inside the harness's steps and label the
    # time there
    names = {k for k, _ in view["step_self_s"]}
    assert {"repro.engine.step", "repro.engine.txn",
            "repro.engine.schedule", "repro.engine.admit",
            "repro.engine.decode_call", "repro.engine.fetch"} <= names
    idle = view["idle_gaps"]
    assert idle["engine_labelled_share"] > 0.5
    assert any(k.startswith("repro.engine.") for k, _ in idle["by_label"])
