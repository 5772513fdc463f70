"""The serving entry point at published and smoke widths, and where the
launchers keep JAX's persistent compilation cache.

Published widths are checked by shapes only: the model's ``init`` is
swapped for its ``jax.eval_shape``, so no weights are drawn and nothing
runs at full width on CPU.
"""

from pathlib import Path

import jax
import pytest

import repro.models as models
from repro.configs import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.workloads.router import build_pool_runtime


@pytest.fixture
def shapes_only(monkeypatch):
    real = models.build_model

    def build(cfg, **kw):
        model = real(cfg, **kw)
        init = model.init
        model.init = lambda key: jax.eval_shape(init, key)
        return model

    monkeypatch.setattr(models, "build_model", build)


@pytest.mark.parametrize("kw,published", [({}, False),
                                          ({"smoke": True}, False),
                                          ({"smoke": False}, True)])
def test_build_pool_runtime_width(shapes_only, kw, published):
    """``smoke=False`` builds the replicas from ``get_config``; the default
    stays the smoke config.  Either way the paged engine allocates no dense
    per-slot K/V next to its pool."""
    rt = build_pool_runtime(replicas=1, max_batch=1, max_seq=64, **kw)
    try:
        (bridge,) = rt.engine_backends["llm"].bridges.values()
        engine = bridge.engine
        cfg = (get_config if published else get_smoke_config)("qwen3_0_6b")
        assert engine.cfg == cfg
        embed = engine.params["embed"]["tok"]
        assert embed.shape == (cfg.vocab_size, cfg.d_model)
        assert engine.pool.k.shape == (cfg.n_layers, engine.pool.n_pages,
                                       engine.pool.page_size, cfg.n_kv_heads,
                                       cfg.head_dim_)
        assert set(engine.cache) == {"pos"}
    finally:
        rt.shutdown()


@pytest.mark.parametrize("env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, env):
    """``JAX_COMPILATION_CACHE_DIR`` decides when set, and the helper then
    sets nothing; otherwise the cache goes to ``<repo>/.jax_cache``."""
    was = jax.config.jax_compilation_cache_dir
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env:
        assert got == str(tmp_path) and now == was
    else:
        repo = Path(__file__).resolve().parents[1]
        assert got == now == str(repo / ".jax_cache")
