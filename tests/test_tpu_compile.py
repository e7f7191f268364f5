"""Both paged Pallas kernels compile for a described TPU v5e.

The TPU compiler is installed beside the CPU backend, so a kernel can be
compiled ahead of time for a chip that is described and not attached.
That catches what interpret mode cannot: block shapes the Mosaic tiling
refuses and kernels that overflow VMEM.  Shapes are those the engine's
paged plane serves with ``chip_smoke.py``: page 16, prefill chunk 256,
8 rows over a 2048-token cache, bfloat16 pools; the decode kernel, and
both step programs of the paged plane, also at the shape of the
``qwen3-4b.analytics-prefix`` benchmark cell.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.paged_attention import (paged_decode_bhd,
                                                           paged_prefill_bhd)
from repro.models import model as M
from repro.serving.engine import _make_paged_step_fns

PAGE, CHUNK, ROWS, CACHE_LEN, POOL_PAGES = 16, 256, 8, 2048, 512
CELL_ROWS, CELL_POOL_PAGES = 16, 1100     # 128 pages of 16 a row
WIDTHS = {                       # name: (Hkv, G, D)
    "qwen3-4b": (8, 4, 128),
    "tinyllama-1.1b": (4, 8, 64),
    "smollm-360m": (5, 3, 64),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def spec(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _pool_and_tables(spec, Hkv, D):
    pool = spec((1, POOL_PAGES, Hkv, PAGE, D))       # a stack of one layer
    return pool, spec((ROWS, CACHE_LEN // PAGE), jnp.int32)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_compiles_for_v5e(spec, no_compile_cache, width):
    Hkv, G, D = WIDTHS[width]
    pool, tables = _pool_and_tables(spec, Hkv, D)
    fn = jax.jit(functools.partial(paged_decode_bhd, interpret=False))
    compiled = fn.lower(spec((ROWS, Hkv, G, D)), pool, pool, tables,
                        spec((ROWS,), jnp.int32),
                        spec((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_compiles_at_the_cell_shape(spec, no_compile_cache,
                                                 width):
    """The jitted entry at the cell's shape; its custom call keeps the
    name the trace reader of ``paged_decode_roofline`` matches."""
    Hkv, G, D = WIDTHS[width]
    pool = spec((1, CELL_POOL_PAGES, Hkv, PAGE, D))
    fn = jax.jit(functools.partial(paged_decode_attention, interpret=False))
    text = fn.lower(spec((CELL_ROWS, Hkv * G, D)), pool, pool,
                    spec((CELL_ROWS, CACHE_LEN // PAGE), jnp.int32),
                    spec((CELL_ROWS,), jnp.int32)).compile().as_text()
    calls = [line.split("=")[0].strip().lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert all(c.startswith("paged_decode_attention") for c in calls), calls


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_prefill_compiles_for_v5e(spec, no_compile_cache, width):
    Hkv, G, D = WIDTHS[width]
    pool, tables = _pool_and_tables(spec, Hkv, D)
    rows = spec((ROWS,), jnp.int32)
    chunk_kv = spec((ROWS, Hkv, CHUNK, D))
    fn = jax.jit(functools.partial(paged_prefill_bhd, interpret=False))
    compiled = fn.lower(spec((ROWS, Hkv, CHUNK * G, D)), chunk_kv, chunk_kv,
                        pool, pool, tables, rows, rows,
                        spec((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


INSTRUCTION = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


@pytest.mark.parametrize("arch", ["qwen3-4b", "smollm-360m"])
def test_paged_step_programs_carry_the_pools(spec, no_compile_cache,
                                             monkeypatch, arch):
    """Both step programs at the cell's shapes, with the Pallas kernels:
    the layer loop carries the stacked pools and the kernels index the
    layer, so no instruction but a kernel's produces one layer's pool,
    and each stacked pool is copied once, on entry (the program does not
    own its arguments).  Decode writes the new rows by in-place updates
    first; Qwen3-4B's decode kernel then copies its own pages, and
    SmolLM-360M's (D 64) pipelines them.  At D 64 the
    device keeps a (..., 16, 64) array with its page axis minor, so the
    program also lays each pool back out once on exit.

    Each model keeps its own depth: a stack of a few layers fits the
    v5e's VMEM, and the compiler then stages the entry copy through it a
    layer at a time."""
    monkeypatch.setattr(pa_ops, "_on_cpu", lambda: False)   # the kernels
    cfg = get_config(arch)
    L, Hkv, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    pool = spec((L, CELL_POOL_PAGES, Hkv, PAGE, D))
    tables = spec((CELL_ROWS, CACHE_LEN // PAGE), jnp.int32)
    rows = spec((CELL_ROWS,), jnp.int32)
    prefill, decode = (jax.jit(f) for f in _make_paged_step_fns(cfg, "dense"))
    programs = {
        "prefill": prefill.lower(params, pool, pool,
                                 spec((CELL_ROWS, CHUNK + 2), jnp.int32),
                                 tables),
        "decode": decode.lower(params, pool, pool, rows, rows, tables,
                               spec((CELL_ROWS,), jnp.bool_)),
    }
    one_layer = (f"bf16[{CELL_POOL_PAGES},{Hkv},{PAGE},{D}]",
                 f"bf16[1,{CELL_POOL_PAGES},{Hkv},{PAGE},{D}]")
    stack = f"bf16[{L},{CELL_POOL_PAGES},{Hkv},{PAGE},{D}]"
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        per_layer, copies, relayout = [], [], False
        for line in text.splitlines():
            m = INSTRUCTION.match(line)
            if m is None:
                continue
            op, shape, opcode = m.groups()
            if any(s in shape for s in one_layer) and \
                    'custom_call_target="tpu_custom_call"' not in line:
                per_layer.append(op)
            if opcode in ("copy", "copy-start") and stack in shape:
                copies.append(op)
            if opcode == "parameter" and 'op_name="k_pools"' in line:
                relayout = "{4,3,2,1,0:" not in shape
        assert not per_layer, (name, per_layer)
        assert len(copies) <= 2 * (1 + relayout), (name, copies)
        kernel = f"paged_{name}_attention"
        assert re.search(rf"%{kernel}[.\d]* = ", text), (name, kernel)
