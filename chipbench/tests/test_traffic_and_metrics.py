"""The traffic generator, the statistics and the work functions, on the
CPU, and the benchmark's files against each other."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, traffic, work  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(
    ROOT, "chipbench", "traffic")) if f.endswith(".json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def mix(name):
    return json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                       f"{name}.json")))


def shape(reqs):
    return [(r.due, len(r.prompt), r.output_len, r.template) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_mix_is_deterministic_and_seeds_differ(name):
    m = mix(name)
    a = traffic.generate(m, 2 ** 40 + 3, 200, 5000)
    b = traffic.generate(m, 2 ** 40 + 3, 200, 5000)
    c = traffic.generate(m, 7, 200, 5000)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # every seed offers the same sizes at the same times, in one order
    assert shape(a) == shape(c)
    assert all(0 <= t < 5000 for r in a for t in r.prompt)


def test_analytics_lengths_templates_and_zipf_shares():
    m = mix("analytics-prefix")
    n = 4000
    reqs = traffic.generate(m, 3, n, 151936)
    pr = m["prompt"]
    lo, hi = pr["len"]["uniform"]
    slo, shi = pr["suffix_len"]["uniform"]
    temps = {}
    for r in reqs:
        assert 8 <= r.output_len <= 64
        temps.setdefault(r.template, r.prompt[:lo])
        assert r.prompt[:lo] == temps[r.template]     # a shared prefix
        assert lo + slo <= len(r.prompt) <= hi + shi
    counts = np.bincount([r.template for r in reqs], minlength=pr["count"])
    want = traffic.zipf_probs(pr["count"], pr["zipf_alpha"]) * n
    assert np.all(np.abs(counts - want) < 5 * np.sqrt(want) + 3)
    assert counts[0] > counts[5] > counts[-1]


def test_chat_clips_and_gamma_burstiness():
    m = mix("chat-burst")
    reqs = traffic.generate(m, 5, 6000, 49152)
    ins = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.output_len for r in reqs])
    assert ins.min() >= 1 and ins.max() == 1536
    assert outs.min() >= 1 and outs.max() == 512
    assert 700 < ins.mean() < 1000        # lognormal mean 1200, clipped
    assert 150 < outs.mean() < 220
    gaps = np.diff([r.due for r in reqs])
    cv = gaps.std() / gaps.mean()
    assert abs(cv - m["arrivals"]["cv"]) < 0.25
    assert abs(1.0 / gaps.mean() - m["rate_rps"]) < 0.1 * m["rate_rps"]


def test_ttft_censoring_and_percentiles():
    due = {0: 0.0, 1: 1.0, 2: 2.0, 3: 9.5, 4: 10.5}
    first = {0: 0.5, 1: 4.0, 3: 10.2}            # 2 never starts
    got = harness.ttft_samples(due, first, t_end=10.0)
    # 3's token came after the window closed: censored at the close;
    # 4 fell due after the close: not in the sample
    assert sorted(got) == [0.5, 0.5, 3.0, 8.0]
    assert harness.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert harness.percentile([10.0], 95) == 10.0
    times = {0: [1.0, 1.5, 2.5, 11.0], 1: [3.0]}
    assert sorted(harness.itl_samples(times, 10.0)) == [0.5, 1.0]


MODEL = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10}
PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_work_functions_hand_worked():
    # H=4, D=2, kv_dim=4, L=2: decode at position 3 reads 4 positions
    f, b = work.decode_work(MODEL, [3])
    assert f == 2 * 4 * 4 * 2 * 4
    assert b == 2 * (2 * 4 * 4 * 2 + 2 * 4 * 2 * 2)
    # prefill of 2 tokens from position 3: keys 4 and 5 -> 9 pairs
    f, b = work.prefill_work(MODEL, [(3, 2)])
    assert f == 2 * 4 * 4 * 2 * 9
    assert b == 2 * (2 * (3 + 4) * 4 * 2 + 2 * 2 * 4 * 2 * 2)
    # padded rows cost nothing
    assert work.prefill_work(MODEL, [(3, 2), (0, 0)]) == (f, b)
    assert work.least_seconds(1000.0, 10.0, PEAK) == 10.0    # compute
    assert work.least_seconds(10.0, 1000.0, PEAK) == 100.0   # bandwidth
    layers, head = work.matmul_params(MODEL)
    assert layers == 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    assert head == 10 * 8
    mf = work.model_flops(MODEL, tokens=3, emitted=1, decode_ctxs=[3],
                          prefill_rows=[(3, 2)])
    assert mf == 2 * layers * 3 + 2 * head + 4 * 4 * 2 * 2 * (4 + 9)


def run_record(trace=None, steps=()):
    return harness.RunRecord(model=MODEL, peak=PEAK, steps=list(steps),
                             traced_steps=list(steps),
                             counters={"prefix_shared_tokens": 30,
                                       "prompt_tokens_admitted": 120,
                                       "finished": 60},
                             trace=trace)


def test_metric_readers_on_a_hand_built_record():
    from chipbench import trace as tr
    st = harness.StepRec(t0=0.0, t1=2.0, decode_ctxs=[3],
                         prefill_calls=[[(3, 2)]], emitted=1)
    ops = [tr.Op("fusion.1", 0.0, 0.5e9),
           tr.Op("paged_decode_attention.2", 0.5e9, 0.6e9),
           tr.Op("paged_prefill_attention.1", 1.0e9, 1.5e9)]
    spans = [tr.Span("window", 0.0, 2e9), tr.Span("step", 0.0, 2e9)]
    red = tr.reduce(ops, spans)
    rec = run_record(red, [st])
    read = {m["name"]: harness.load_reader(m["name"])
            for m in BENCH["per_layer"]}
    assert read["step_wall_ms"](rec) == 2000.0
    assert read["prefix_hit_frac"](rec) == 0.25
    assert read["device_idle_frac"](rec) == pytest.approx(1 - 1.1 / 2.0)
    f, b = work.decode_work(MODEL, [3])
    assert read["paged_decode_roofline"](rec) == pytest.approx(
        100 * work.least_seconds(f, b, PEAK) / 0.1)
    f, b = work.prefill_work(MODEL, [(3, 2)])
    assert read["paged_prefill_roofline"](rec) == pytest.approx(
        100 * work.least_seconds(f, b, PEAK) / 0.5)
    mf = work.model_flops(MODEL, 3, 1, [3], [(3, 2)])
    assert read["step_mfu"](rec) == pytest.approx(100 * mf / (2.0 * 100.0))
    # a reader that finds nothing to read returns nothing
    empty = harness.RunRecord(model=MODEL, peak=PEAK, steps=[],
                              traced_steps=[], counters={}, trace=None)
    assert all(r(empty) is None for r in read.values())


def test_benchmark_files_resolve_and_keep_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("chipbench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "references", f"{cfg['reference']}.py"))
        names.add(c["name"])
    cells = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic",
                                           f"{w['traffic']}.json"))
        cells.add(w["name"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0 < m["bound"] <= 0.25 and m["source"] == "host_clock"
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics",
                                           f"{m['name']}.py"))
    for w in cells:
        cell = harness.load_cell(w)
        assert cell.per_layer and len(cell.end_to_end) >= 2


def test_run_length_fits_the_check_with_every_cell():
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
