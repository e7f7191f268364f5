"""The harness end to end on the CPU at a reduced size
(``cpu_cell.py``), the command's refusal without a TPU, and the check
failing with the timed path broken underneath.  Each run is its own
process, so JAX's settings and the compile cache stay out of the test
process."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU_CELL = os.path.join(ROOT, "chipbench", "tests", "cpu_cell.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run(*args, timeout=600):
    p = subprocess.run([sys.executable, CPU_CELL, *args], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return [json.loads(x) for x in lines if x.startswith("{")], p.stderr


def test_command_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "qwen3-4b.analytics-prefix", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(x.startswith("{") for x in p.stdout.splitlines())


@pytest.mark.parametrize("cell,loop", [
    ("qwen3-4b.analytics-prefix", "open"),
    ("qwen3-4b.analytics-prefix", "backlog"),
])
def test_driver_serves_the_window_and_is_correct(cell, loop):
    (out,), err = run(cell, "--loop", loop, "--seconds", "8")
    assert out["correct"] is True, err[-2000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    if loop == "open":
        assert m["ttft_p90_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
    else:
        assert "ttft_p90_ms" not in m or m["ttft_p90_ms"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["max"]
    assert "XLA compiles inside the window" in err
    assert err.strip().splitlines()[-1].startswith("[chipbench] check ")


def test_traced_run_reports_per_layer_metrics_and_device_times():
    (out,), _ = run("qwen3-4b.analytics-prefix", "--seconds", "8",
                    "--trace", "1")
    assert out["correct"] is True
    assert {"step_wall_ms", "prefix_hit_frac", "step_mfu"} <= \
        set(out["metrics"])
    assert "window_s" in out["device"] and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", ["token", "frozen", "half"])
def test_check_fails_when_the_timed_path_is_broken(fault):
    (out,), _ = run("qwen3-4b.analytics-prefix", "--fault", fault,
                    "--seconds", "8")
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["max"]


def test_control_reads_far_above_the_program():
    (out,), err = run("qwen3-4b.analytics-prefix", "--control",
                      "--seconds", "8")
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["max"]
    assert "the control's stands in" in err
