"""The harness: one cell of ``BENCHMARK.json``, driven on the host clock.

``run_cell`` builds the cell from its files (configuration, traffic mix,
per-layer metric readers), makes the weights on the device from the seed,
builds ``repro.serving.Engine`` on the paged plane with the
configuration's engine settings, warms every shape the cell uses, then
drives ``Engine.submit`` / ``Engine.step()`` for the window:

* open loop: each request is submitted once ``now >= due``; the engine
  steps whenever its scheduler has work, and waits for the next due time
  otherwise.
* backlog: at least ``backlog_factor`` x ``nslots`` requests wait at all
  times.

After each step the new output tokens are stamped with
``time.perf_counter()`` (lengths of ``engine.outputs`` against those
before the step).  The engine's virtual clock is never read.

With ``trace`` the last ``TRACE_SECONDS`` of the window run under the
profiler, the calls into the step programs are recorded (rows, positions,
lengths) to count the work they needed, and the per-layer metrics are
read from the record and the reduced trace.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".chipbench_out"        # traces; ignored by git
CACHE = ROOT / ".jax_cache"          # JAX's persistent compilation cache
TRACE_SECONDS = 6.0                  # traced tail of the window
WARM_RID = 1 << 30                   # rid of the warm-up request
PRIME_RID = 1 << 29                  # rids of the template primers
CHECK_TOKENS = 384                   # served tokens the sample aims at
CHECK_MIN_TOKENS = 128               # fewer compared is not correct
CHECK_MAX_REQUESTS = 12


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------- #
# the cell, from its files
# --------------------------------------------------------------------- #

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT

    @property
    def engine(self) -> dict:
        es = dict(self.config["engine"])
        es.update(self.mix.get("engine", {}))
        return es


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=w["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((root / BENCH.name / "traffic" /
                        f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of ``metrics/<name>.py``: run record -> number or None."""
    return _load_module(root / BENCH.name / "metrics" / f"{name}.py",
                        f"chipbench_metric_{name}").read


def load_reference(name: str, root: Path = ROOT):
    return _load_module(root / BENCH.name / "references" / f"{name}.py",
                        f"chipbench_ref_{name}")


def load_peaks(root: Path = ROOT) -> dict:
    return json.loads((root / BENCH.name / "peaks.json").read_text())


def jax_key(seed: int):
    """A JAX key from any whole-number seed, however large."""
    import jax
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


# --------------------------------------------------------------------- #
# statistics
# --------------------------------------------------------------------- #

def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(due_abs: Dict[int, float], first: Dict[int, float],
                 t_end: float) -> List[float]:
    """Time to first token of every request due before ``t_end``, from
    its due time; one still without a token at ``t_end`` counts as
    ``t_end - due`` (a stall shows, and is not dropped)."""
    out = []
    for rid, d in due_abs.items():
        if d >= t_end:
            continue
        f = first.get(rid)
        out.append((f if f is not None and f <= t_end else t_end) - d)
    return out


def itl_samples(times: Dict[int, List[float]], t_end: float) -> List[float]:
    """Every gap between consecutive output tokens of a request, both
    produced inside the window."""
    out = []
    for ts in times.values():
        ts = [t for t in ts if t <= t_end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


# --------------------------------------------------------------------- #
# the run record the per-layer readers see
# --------------------------------------------------------------------- #

@dataclass
class StepRec:
    t0: float
    t1: float = 0.0
    decode_ctxs: List[int] = field(default_factory=list)
    prefill_calls: List[List[tuple]] = field(default_factory=list)
    emitted: int = 0

    @property
    def tokens(self) -> int:
        return len(self.decode_ctxs) + sum(n for call in self.prefill_calls
                                           for _, n in call)


@dataclass
class RunRecord:
    model: dict
    peak: dict
    steps: List[StepRec]                       # every step of the window
    traced_steps: List[StepRec]                # steps inside the trace
    counters: Dict[str, float]                 # window deltas
    trace: object = None                       # trace.Reduced or None


class CompileClock:
    """XLA compile seconds and count, and the persistent cache's hits and
    misses, from JAX's monitoring events."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return (f"{self.count} XLA compiles, {self.seconds!r} s compiling, "
                f"persistent cache {self.hits} hits / {self.misses} misses")


# --------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------- #

def setup_jax_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout; every program is kept, however fast it compiled."""
    import jax
    path = str(root / CACHE.name)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # no eviction
    return path


def model_config(cell: Cell):
    from repro.configs.base import ModelConfig
    return ModelConfig(**cell.config["model"])


def build_engine(cfg, params, es: dict, device_kind: str):
    from repro.core import (TheoreticalCostModel, hardware_for_device,
                            make_scheduler)
    from repro.serving import Engine, EngineConfig
    sched = make_scheduler(
        es["scheduler"], es["pool_tokens"], S=es["max_batch_tokens"],
        page_size=es["page_size"], preempt_mode=es["preempt_mode"],
        cache_policy=es["cache_policy"], cache_demotion=es["cache_demotion"],
        prefix_lookup=es["prefix_lookup"])
    return Engine(
        cfg, params, sched,
        EngineConfig(nslots=es["nslots"], cache_len=es["cache_len"],
                     chunk=es["chunk"], min_bucket=es["min_bucket"],
                     page_size=es["page_size"], plane="paged",
                     prefix_sharing=es["prefix_sharing"],
                     check_invariants=es["check_invariants"],
                     share_jits=True),
        cost_model=TheoreticalCostModel(cfg, hardware_for_device(device_kind)))


def page_runs(eng, es: dict, mix: dict) -> range:
    """Page-run lengths the cell's suspends, restores, demotions and
    promotions can gather or scatter.  A mix with ``"preempts": false``
    is sized so that its live rows stay inside the pool: no request is
    suspended, and no suspend's page runs are warmed."""
    tight = es["pool_tokens"] < es["nslots"] * es["cache_len"]
    if (tight and mix.get("preempts", True)
            and es["preempt_mode"] in ("swap", "auto")):
        return range(1, eng.max_pages + 1)
    if es["cache_demotion"] and es["prefix_sharing"]:
        return range(1, 2)
    return range(0)


def prime(eng, mix: dict, seed: int, vocab: int) -> None:
    """Serve each of the mix's templates once, the rarest first, so the
    window opens on a warm prefix cache (``prime_templates`` in the mix)."""
    from chipbench import traffic
    from repro.core import Request
    temps = traffic.template_prompts(mix, seed, vocab)
    for k in reversed(range(len(temps))):
        eng.submit(Request(rid=PRIME_RID + k, input_len=len(temps[k]),
                           output_len=1, arrival=0.0, prompt=temps[k]))
    while eng.sched.has_work():
        eng.step()


def warm(eng, es: dict, mix: dict, vocab: int) -> None:
    """Compile or load every program the window runs: one prefill per
    bucket, the decode step, the step loop's small host-side helpers (one
    short request through ``step()``), and the page-run gathers and
    scatters the cell can reach."""
    import jax
    import jax.numpy as jnp
    from repro.core import Request
    ns = es["nslots"]
    bt = jnp.zeros((ns, eng.max_pages), jnp.int32)
    zi = jnp.zeros((ns,), jnp.int32)
    for b in eng.buckets:
        jax.block_until_ready(eng._paged_prefill(
            eng.params, eng.k_pools, eng.v_pools,
            jnp.zeros((ns, b + 2), jnp.int32), bt))
    jax.block_until_ready(eng._paged_decode(
        eng.params, eng.k_pools, eng.v_pools, zi, zi, bt,
        jnp.zeros((ns,), bool)))
    n = 2 * es["page_size"] + 3
    prompt = np.random.default_rng(0).integers(0, vocab, size=n).tolist()
    eng.submit(Request(rid=WARM_RID, input_len=n, output_len=4, arrival=0.0,
                       prompt=prompt))
    while eng.sched.has_work():
        eng.step()
    if es["cache_demotion"] and es["prefix_sharing"]:
        jnp.asarray([0], jnp.int32)       # a demotion's page index, as built
    for npg in page_runs(eng, es, mix):
        ids = jnp.zeros((npg,), jnp.int32)
        run = eng.k_pools[:, ids]
        jax.block_until_ready(eng.k_pools.at[:, ids].set(run))


class Recorder:
    """Wraps the engine's two step programs to record, per call, the rows
    they served: decode positions, prefill (start, length) pairs.  Only
    traced runs install it; each call is also a host span."""

    def __init__(self, eng) -> None:
        import jax
        self.cur: Optional[StepRec] = None
        pf, df = eng._paged_prefill, eng._paged_decode

        def prefill(params, kp, vp, grid, bt):
            with jax.profiler.TraceAnnotation("chipbench.step.prefill_call"):
                out = pf(params, kp, vp, grid, bt)
            if self.cur is not None:
                g = np.asarray(grid)
                self.cur.prefill_calls.append(
                    [(int(s), int(n)) for n, s in zip(g[:, -2], g[:, -1])
                     if n > 0])
            return out

        def decode(params, kp, vp, toks, ctx, bt, active):
            with jax.profiler.TraceAnnotation("chipbench.step.decode_call"):
                out = df(params, kp, vp, toks, ctx, bt, active)
            if self.cur is not None:
                c, a = np.asarray(ctx), np.asarray(active)
                self.cur.decode_ctxs.extend(int(x) for x in c[a])
            return out

        eng._paged_prefill, eng._paged_decode = prefill, decode


# --------------------------------------------------------------------- #
# drivers
# --------------------------------------------------------------------- #

@dataclass
class Window:
    t0: float
    t_end: float = 0.0
    due_abs: Dict[int, float] = field(default_factory=dict)
    first: Dict[int, float] = field(default_factory=dict)
    times: Dict[int, List[float]] = field(default_factory=dict)
    steps: List[StepRec] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)
    finished: List = field(default_factory=list)
    reqs: Dict[int, object] = field(default_factory=dict)
    queue: List[tuple] = field(default_factory=list)   # (t, waiting)
    trace_t: tuple = (0.0, 0.0)
    tokens: int = 0


def drive(eng, reqs, mix: dict, es: dict, seconds: float, *,
          recorder: Optional[Recorder] = None,
          on_trace: Optional[Callable[[bool], None]] = None) -> Window:
    """Run the window.  ``on_trace(True)`` is called when the traced tail
    starts, ``on_trace(False)`` when the window closes."""
    import jax
    from repro.core import Request
    span = jax.profiler.TraceAnnotation
    open_loop = mix["loop"] == "open"
    backlog = mix.get("backlog_factor", 2) * es["nslots"]
    live: Dict[int, int] = {}            # rid -> tokens stamped so far
    t0 = time.perf_counter()
    w = Window(t0=t0)
    t_close = t0 + seconds
    t_trace = t_close - TRACE_SECONDS if on_trace else math.inf
    tracing = False
    i = 0

    def submit(r, now):
        er = Request(rid=r.rid, input_len=len(r.prompt),
                     output_len=r.output_len, arrival=now - t0,
                     prompt=r.prompt)
        eng.submit(er)
        w.reqs[r.rid] = er
        live[r.rid] = 0
        w.times[r.rid] = []

    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if not tracing and now >= t_trace:
            on_trace(True)
            tracing = True
            w.trace_t = (now, math.inf)
        with span("chipbench.submit"):
            if open_loop:
                while i < len(reqs) and t0 + reqs[i].due <= now:
                    w.due_abs[reqs[i].rid] = t0 + reqs[i].due
                    w.lateness.append(now - (t0 + reqs[i].due))
                    submit(reqs[i], now)
                    i += 1
            else:
                while i < len(reqs) and len(eng.sched.waiting) < backlog:
                    w.due_abs[reqs[i].rid] = now
                    submit(reqs[i], now)
                    i += 1
        if eng.sched.has_work():
            st = StepRec(t0=time.perf_counter())
            if recorder is not None:
                recorder.cur = st
            with span("chipbench.step"):
                executed = eng.step()
            st.t1 = t = time.perf_counter()
            w.steps.append(st)
            for rid in list(live):
                out = eng.outputs[rid]
                new = len(out) - live[rid]
                if new <= 0:
                    continue
                if live[rid] == 0:
                    w.first[rid] = t
                w.times[rid].extend([t] * new)
                st.emitted += new
                live[rid] = len(out)
                if live[rid] >= w.reqs[rid].output_len:
                    del live[rid]
                    w.finished.append(w.reqs[rid])
            w.queue.append((t, len(eng.sched.waiting)))
            if executed:
                continue
        nxt = t0 + reqs[i].due if open_loop and i < len(reqs) else t_close
        with span("chipbench.wait"):
            time.sleep(max(0.0, min(nxt, t_close) - time.perf_counter()))
    w.t_end = time.perf_counter()
    if tracing:
        w.trace_t = (w.trace_t[0], w.t_end)
        on_trace(False)
    w.tokens = sum(len([t for t in ts if t <= w.t_end])
                   for ts in w.times.values())
    return w


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #

def device_check(cell: Cell, peaks: dict):
    """The TPU JAX finds, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r} devices")
    if dev.device_kind not in peaks:
        raise SystemExit(f"device kind {dev.device_kind!r} is not in the "
                         f"peaks table ({sorted(peaks)})")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devs)}")
    return dev, devs


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, cpu_kind: Optional[str] = None,
             hook: Optional[Callable] = None, rate: Optional[float] = None,
             control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``cpu_kind`` is for rehearsals only: it skips the look for a TPU and
    prices the engine's cost model and the rooflines as that device kind.
    ``hook(engine)`` is called once the engine is warm (tests use it to
    break the timed path).  ``rate`` offers that many requests per second
    in place of the mix's (the knee sweep).  ``control`` puts the control
    in the program's place for the check: the gap of the token the
    reference one precision step down ranks first is held to the same
    limit, and the program's own gap is only printed."""
    import jax
    if rate is not None:
        cell.mix["rate_rps"] = rate
    setup_jax_cache(cell.root)
    clock = CompileClock()
    peaks = load_peaks(cell.root)
    if cpu_kind is None:
        dev, devs = device_check(cell, peaks)
        kind = dev.device_kind
    else:
        devs = jax.devices()
        dev, kind = devs[0], cpu_kind
    log(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}; cell {cell.name}, seed {seed}, {seconds} s, "
        f"trace {int(trace)}, {cell.mix.get('rate_rps')} requests/s"
        f"{', control' if control else ''}")
    cfg = model_config(cell)
    es = cell.engine
    ref = load_reference(cell.config["reference"], cell.root)
    arch = ref.Arch.from_model(cell.config["model"])
    params = jax.block_until_ready(ref.make_params(arch, jax_key(seed)))
    reqs = cell_requests(cell, seed, seconds, cfg.vocab_size)
    eng = build_engine(cfg, params, es, kind)
    mem = dev.memory_stats() or {}
    log(f"weights {sum(x.nbytes for x in jax.tree.leaves(params))} bytes; "
        f"with the pools {mem.get('bytes_in_use')} of "
        f"{mem.get('bytes_limit')} bytes in use")
    warm(eng, es, cell.mix, cfg.vocab_size)
    if cell.mix.get("prime_templates"):
        prime(eng, cell.mix, seed, cfg.vocab_size)
    if hook is not None:
        hook(eng)
    recorder = Recorder(eng) if trace else None
    trace_dir = str(cell.root / OUT.name / "trace" / cell.name)
    win_span = []

    def on_trace(start: bool) -> None:
        if start:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            win_span.append(jax.profiler.TraceAnnotation("chipbench.window"))
            win_span[0].__enter__()
        else:
            win_span[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    base = window_counters(eng)
    compiles0 = clock.count
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s!r} s; {clock}")
    jax.config.update("jax_log_compiles", True)    # names any window compile
    w = drive(eng, reqs, cell.mix, es, seconds, recorder=recorder,
              on_trace=on_trace if trace else None)
    in_window = clock.count - compiles0
    jax.config.update("jax_log_compiles", False)
    counters = {k: window_counters(eng)[k] - v for k, v in base.items()}
    counters["finished"] = len(w.finished)
    counters["prompt_tokens_admitted"] = sum(
        r.input_len for r in w.reqs.values() if r.m > 0 or r.finished)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    lat = w.lateness or [0.0]
    log(f"window {w.t_end - w.t0!r} s: {len(w.due_abs)} requests due, "
        f"{len(w.finished)} finished, {w.tokens} tokens, {len(w.steps)} "
        f"steps; generator late by {float(np.mean(lat))!r} s mean, "
        f"{max(lat)!r} s max; {in_window} XLA compiles inside the window")
    log(f"window counters {counters}")
    itl = itl_samples(w.times, w.t_end)
    window = {"rate_rps": cell.mix.get("rate_rps"),
              "due": len(w.due_abs), "finished": len(w.finished),
              "queue_trend": queue_trend(w),
              "queue_end": w.queue[-1][1] if w.queue else 0,
              "late_max_s": max(lat), "compiles": in_window,
              "preemptions": counters["preemptions"],
              "itl_gaps": len(itl),
              "itl_ms": {f"p{q}": 1e3 * percentile(itl, q)
                         for q in (50, 90, 95, 99)} if itl else {}}
    log(f"window queue and gaps {window}")

    # the check: free the program's state, then run the reference
    sample = pick_sample(w, seed)
    window["preempted_in_sample"] = sum(
        1 for r in sample if r.preemptions or r.partial_preemptions)
    items = [(r.prompt, list(eng.outputs[r.rid])) for r in sample]
    bad = sum(1 for r in w.finished
              if len(eng.outputs[r.rid]) != r.output_len
              or not all(0 <= t < cfg.vocab_size for t in eng.outputs[r.rid]))
    eng.k_pools = eng.v_pools = None
    del eng
    gc.collect()
    record = RunRecord(model=cell.config["model"], peak=peaks[kind],
                       steps=w.steps, counters=counters,
                       traced_steps=[s for s in w.steps
                                     if s.t0 >= w.trace_t[0]
                                     and s.t1 <= w.trace_t[1]])
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        from chipbench import trace as tr
        record.trace = tr.reduce(*tr.load(tr.newest_xplane(trace_dir)))
        result_device["busy_s"] = record.trace.busy_s
        result_device["window_s"] = record.trace.window_s
    checks = run_check(cell, ref, arch, params, items, bad, control)
    correct = all(c["ok"] for c in checks.values())
    metrics = (per_layer_metrics(cell, record) if trace
               else end_to_end_metrics(cell, w, setup_s))
    out = {"correct": correct, "attempted": len(w.due_abs), "failed": 0,
           "metrics": metrics, "device": result_device}
    if trace:
        t = record.trace
        out["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in t.op_s.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(([k, v] for k, v in t.idle_by_label.items()),
                                key=lambda kv: -kv[1])[:10]}
    out["window"] = window
    out["checks"] = {k: {kk: vv for kk, vv in c.items() if kk != "ok"}
                     for k, c in checks.items()}
    for k, c in checks.items():
        lim = ", ".join(f"{kk} {vv!r}" for kk, vv in c.items()
                        if kk not in ("ok", "value"))
        log(f"check {k}: {c['value']!r} ({lim}) {'ok' if c['ok'] else 'FAIL'}")
    return out


def cell_requests(cell: Cell, seed: int, seconds: float, vocab: int):
    from chipbench import traffic
    n = traffic.count_for(cell.mix, seconds, cell.engine["nslots"])
    return traffic.generate(cell.mix, seed, n, vocab)


def queue_trend(w: Window) -> float:
    """How the queue of waiting requests grew: its mean length over the
    last third of the window less that over the first third.  Near 0
    below the knee; it grows through the window above it."""
    span = w.t_end - w.t0
    q = [(t - w.t0, n) for t, n in w.queue]
    first = [n for t, n in q if t < span / 3]
    last = [n for t, n in q if t >= 2 * span / 3]
    if not first or not last:
        return 0.0
    return float(np.mean(last) - np.mean(first))


def window_counters(eng) -> Dict[str, float]:
    from repro.core import stat_keys as SK
    return {"prefix_shared_tokens":
            eng.allocator.stats[SK.PREFIX_SHARED_TOKENS],
            "preemptions": eng.sched.num_preemptions,
            "partial_preemptions": eng.sched.num_partial_preempts,
            "swap_outs": eng.swap_stats[SK.SWAP_OUTS],
            "demotions": eng.swap_stats[SK.DEMOTIONS],
            "promotions": eng.swap_stats[SK.PROMOTIONS]}


def pick_sample(w: Window, seed: int):
    from chipbench import correct
    return correct.pick(w.finished, seed, CHECK_TOKENS, CHECK_MAX_REQUESTS)


def run_check(cell: Cell, ref, arch, params, items, bad: int,
              control: bool = False) -> dict:
    """The numbers that decide ``correct``, each beside its limit.  With
    ``control`` the control's gap stands in the program's place."""
    from chipbench import correct
    limit = cell.config["correct"]["logit_gap_max"]
    k_read = max([len(s) for _, s in items] + [1])
    k_read = max(64, 1 << (k_read - 1).bit_length())
    gaps = correct.compare(ref, arch, params, items, k_read, control)
    gap = gaps["logit_gap"]
    if control:
        log(f"program's own logit_gap {gap!r}; the control's stands in")
        gap = gaps["control_logit_gap"]
    served = sum(len(s) for _, s in items)
    return {
        "logit_gap": {"value": gap, "max": limit, "ok": gap <= limit},
        "served_tokens_compared": {"value": served, "min": CHECK_MIN_TOKENS,
                                   "ok": served >= CHECK_MIN_TOKENS},
        "bad_outputs": {"value": bad, "max": 0, "ok": bad == 0}}


def end_to_end_metrics(cell: Cell, w: Window, setup_s: float) -> dict:
    span = w.t_end - w.t0
    values = {
        "ttft_p90_ms": lambda: 1e3 * percentile(
            ttft_samples(w.due_abs, w.first, w.t_end), 90),
        "itl_p95_ms": lambda: 1e3 * percentile(
            itl_samples(w.times, w.t_end) or [span], 95),
        "tokens_per_s": lambda: w.tokens / span,
        "setup_s": lambda: setup_s,
    }
    return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer_metrics(cell: Cell, record: RunRecord) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_reader(m["name"], cell.root)(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
