"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib and compiles for a described
topology (``v5e:2x2``; one of its devices stands for one chip).  Nothing
runs: these tests catch what interpret mode on CPU cannot — block shapes
Mosaic refuses, VMEM overuse, a step that does not fit HBM — for the
served kernels and the served step at published widths.

The topology is described only inside the module fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model

# v5e: 16 GiB of HBM per chip
HBM_BYTES = 16 * 2 ** 30

# chip_smoke.py's engine: max_batch 8, max_seq 2048, the engine's default
# page size (64) and pool
B, MAX_SEQ, PAGE = 8, 2048, 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent compile
    cache off (a TPU executable written there cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: _spec(sharding, s.shape, s.dtype), tree)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("T", [1, 2, 4, 8])
def test_paged_decode_chunk_compiles_at_qwen3_width(one_chip, T):
    """The served kernel at qwen3-0.6b widths (16/8 heads, head_dim 128,
    page 64) for every chunk width the engine uses (pow2 up to the prefill
    chunk of 8; T=4 is also the verify width at spec_k=3), over the stacked
    28-layer pool with a traced layer, as the layer scan calls it."""
    from repro.kernels.paged_attention.paged_attention import \
        paged_decode_chunk
    from repro.serving.engine import default_pool_pages
    cfg = get_config("qwen3_0_6b")
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    n_pages = default_pool_pages(B, MAX_SEQ, PAGE)
    max_pages = MAX_SEQ // PAGE
    f = jax.jit(lambda q, kp, vp, pt, pos, layer: paged_decode_chunk(
        q, kp, vp, pt, pos, layer=layer, scale=D ** -0.5, n_rep=H // Hkv,
        interpret=False))
    pages = _spec(one_chip, (cfg.n_layers, n_pages, PAGE, Hkv, D),
                  jnp.bfloat16)
    compiled = f.lower(_spec(one_chip, (B, T, H, D), jnp.bfloat16),
                       pages, pages,
                       _spec(one_chip, (B, max_pages), jnp.int32),
                       _spec(one_chip, (B,), jnp.int32),
                       _spec(one_chip, (), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [None, 4096])
def test_decode_ring_compiles_at_qwen3_width(one_chip, window):
    """The dense ring kernel (``attention_impl="pallas"``) at the same
    widths, with and without a sliding window."""
    from repro.kernels.paged_attention.paged_attention import decode_ring
    cfg = get_config("qwen3_0_6b")
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    f = jax.jit(lambda q, ck, cv, pos: decode_ring(
        q, ck, cv, pos, scale=D ** -0.5, n_rep=H // Hkv, window=window,
        interpret=False))
    cache = _spec(one_chip, (B, MAX_SEQ, Hkv, D), jnp.bfloat16)
    compiled = f.lower(_spec(one_chip, (B, 1, H, D), jnp.bfloat16),
                       cache, cache,
                       _spec(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -------------------------------------------------------- the served steps
@pytest.mark.parametrize("T", [1, 2, 4, 8])
def test_qwen3_paged_step_with_kernel_fits_one_chip(one_chip, monkeypatch,
                                                    T):
    """The engine's whole paged step (28 layers, vocab 151936) with the
    Pallas kernel, at chip_smoke.py's sizes and every chunk width: it
    compiles for v5e, holds the kernel, and its arguments plus temporaries
    fit the chip's HBM."""
    import repro.kernels.paged_attention.ops as ops
    from repro.serving.engine import default_pool_pages, paged_step_fn
    # the kernel picks interpret mode off-TPU; this compile is for the TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("qwen3_0_6b")
    model = build_model(cfg)
    n_pages = default_pool_pages(B, MAX_SEQ, PAGE)
    pages = _spec(one_chip, (cfg.n_layers, n_pages, PAGE, cfg.n_kv_heads,
                             cfg.head_dim_), cfg.jnp_dtype)
    step = jax.jit(paged_step_fn(model, MAX_SEQ, kernel=True),
                   donate_argnums=(4, 5))
    compiled = step.lower(
        _on(one_chip, model.param_shapes()),
        _spec(one_chip, (B, T), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        {"pos": _spec(one_chip, (B,), jnp.int32)}, pages, pages,
        _spec(one_chip, (B, MAX_SEQ // PAGE), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, (
        f"paged step needs {need / 2 ** 30:.2f} GiB of {HBM_BYTES / 2 ** 30}")


# the benchmark's qwen3_0_6b deployment: max_batch 16, max_seq 4096 and a
# pool of 896 pages (bench/configs/qwen3_0_6b.json)
SERVED_B, SERVED_SEQ, SERVED_PAGES = 16, 4096, 896
# an HLO instruction: "%name = dtype[dims]{layout} opcode("
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = (\w+\[[\d,]*\])"
                     r"(?:\{[^}]*\})?\s+([\w-]+)\(")
_POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


@pytest.mark.parametrize("T", [1, 8])
def test_qwen3_paged_step_updates_donated_pools_in_place(one_chip,
                                                         monkeypatch, T):
    """The served step at the benchmark's pool size with donated pools:
    the layer scan carries the pools, so XLA neither copies a whole pool
    nor slices a layer out and writes it back.  Its temporaries stay below
    one pool, and no copy, dynamic-slice or dynamic-update-slice (or a
    fusion named for one) yields a pool or one layer of it — the scatter
    of the new tokens is the only op that writes the pool."""
    import repro.kernels.paged_attention.ops as ops
    from repro.serving.engine import paged_step_fn
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("qwen3_0_6b")
    model = build_model(cfg)
    pool = (cfg.n_layers, SERVED_PAGES, PAGE, cfg.n_kv_heads, cfg.head_dim_)
    pages = _spec(one_chip, pool, cfg.jnp_dtype)
    step = jax.jit(paged_step_fn(model, SERVED_SEQ, kernel=True),
                   donate_argnums=(4, 5))
    compiled = step.lower(
        _on(one_chip, model.param_shapes()),
        _spec(one_chip, (SERVED_B, T), jnp.int32),
        _spec(one_chip, (SERVED_B,), jnp.int32),
        {"pos": _spec(one_chip, (SERVED_B,), jnp.int32)}, pages, pages,
        _spec(one_chip, (SERVED_B, SERVED_SEQ // PAGE), jnp.int32)).compile()
    pool_bytes = jnp.dtype(cfg.jnp_dtype).itemsize
    for n in pool:
        pool_bytes *= n
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (
        f"step temporaries {temp} B reach one pool's {pool_bytes} B")
    dtype = jnp.dtype(cfg.jnp_dtype).name
    shapes = {f"{dtype}[{','.join(map(str, pool))}]",
              f"{dtype}[{','.join(map(str, pool[1:]))}]"}
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    moved = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None or m.group(2) not in shapes:
            continue
        name, shape, op = m.groups()
        if op in _POOL_MOVES or (op == "fusion" and any(
                w in name for w in _POOL_MOVES)):
            moved.append(f"%{name} = {shape} {op}")
    assert not moved, f"the step moves whole pools or layers: {moved}"


def test_mamba2_decode_chunk_compiles_at_published_width(one_chip):
    """mamba2-130m's fused chunk step (24 layers, d_model 768, state 128)
    at B=8, T=8."""
    cfg = get_config("mamba2_130m")
    model = build_model(cfg)
    T = 8
    compiled = jax.jit(model.decode_chunk).lower(
        _on(one_chip, model.param_shapes()),
        _spec(one_chip, (B, T), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        _on(one_chip, model.cache_shapes(B, MAX_SEQ))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
