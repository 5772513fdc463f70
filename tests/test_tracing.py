"""The engine's own measurement: per-request stamps, the count of prefill
steps, and the ``nalar.`` host spans on the profiler's clock.

* Every admission path (chunked cold start, prefix adoption, resume,
  monolithic prefill) stamps ``admitted_at`` between submission and the
  first token.
* ``prefill_steps`` counts the steps that fed prompt tokens:
  ``ceil(fed / prefill_chunk)`` for a chunked prompt, the suffix only when a
  prefix was adopted.
* The stamps reach the ``GenerationResult`` a future resolves to.
* Under a profiler session the spans carry exactly the documented names,
  each step that ran the device step holds one ``nalar.engine.device_wait``,
  and those steps number as many as ``EngineMetrics.decode_steps`` counted.
"""

import glob
import math
import time

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core import NalarRuntime, deployment
from repro.core.runtime import current_runtime
from repro.models import build_model
from repro.serving import (GenerationResult, InferenceEngine, SamplingParams,
                           register_engine_agent)

CHUNK = 8
SPANS = {"nalar.engine.step", "nalar.engine.admit", "nalar.engine.prepare",
         "nalar.kv.append", "nalar.engine.dispatch",
         "nalar.engine.device_wait", "nalar.kv.commit", "nalar.engine.sample",
         "nalar.engine.emit", "nalar.bridge.deliver", "nalar.bridge.submit",
         "nalar.runtime.control_round"}


@pytest.fixture(scope="module")
def model_setup():
    cfg = get_smoke_config("qwen3_0_6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, chunk=CHUNK):
    return InferenceEngine(model, params, max_batch=4, max_seq=128,
                           page_size=16, prefill_chunk=chunk)


def _tokens(start, n):
    return [(start + 7 * i) % 500 + 1 for i in range(n)]


def _ordered(r):
    return 0 <= r.submitted_wall <= r.admitted_at <= r.first_token_at \
        <= r.finished_at


def _gen(engine, prompt, sid, new=3):
    return engine.generate(prompt, session_id=sid,
                           sampling=SamplingParams(max_new_tokens=new))


@pytest.mark.parametrize("n", [1, 8, 29, 64])
def test_cold_chunked_prompt(model_setup, n):
    _cfg, model, params = model_setup
    req = _gen(_engine(model, params), _tokens(3, n), "cold")
    assert req.prefix_reused_tokens == 0
    assert req.prefill_steps == math.ceil(n / CHUNK)
    assert _ordered(req)


def test_adopted_prefix_feeds_the_suffix_only(model_setup):
    _cfg, model, params = model_setup
    engine = _engine(model, params)
    shared = _tokens(11, 48)
    _gen(engine, shared + _tokens(200, 5), "first")
    prompt = shared + _tokens(300, 21)
    req = _gen(engine, prompt, "second")
    assert engine.metrics.shared_prefix_hits == 1
    assert req.prefix_reused_tokens > 0
    fed = len(prompt) - req.prefix_reused_tokens
    assert req.prefill_steps == math.ceil(fed / CHUNK)
    assert _ordered(req)


def test_resumed_session_feeds_the_new_tokens(model_setup):
    _cfg, model, params = model_setup
    engine = _engine(model, params)
    _gen(engine, _tokens(5, 20), "sess")
    req = _gen(engine, _tokens(90, 13), "sess")
    assert engine.metrics.prefix_hits >= 1 and req.prefix_reused_tokens > 0
    assert req.prefill_steps == math.ceil(13 / CHUNK)
    assert _ordered(req)


def test_monolithic_prefill_is_one_step(model_setup):
    _cfg, model, params = model_setup
    req = _gen(_engine(model, params, chunk=0), _tokens(9, 29), "mono")
    assert req.prefill_steps == 1
    assert _ordered(req)


def _runtime(model, params):
    rt = NalarRuntime(simulate=False)
    engine = _engine(model, params)
    register_engine_agent(rt, "llm", engine,
                          sampling=SamplingParams(max_new_tokens=4),
                          encode=lambda toks: [int(t) for t in toks])
    return rt, engine


def test_stamps_reach_the_generation_result(model_setup):
    _cfg, model, params = model_setup
    rt, _engine_ = _runtime(model, params)
    prompt = _tokens(17, 21)

    def driver():
        return current_runtime().stub("llm").generate(prompt).value(
            timeout=300)

    out = deployment.main(driver, runtime=rt)
    rt.shutdown()
    assert isinstance(out, GenerationResult)
    assert _ordered(out)
    assert out.prefill_steps == math.ceil(len(prompt) / CHUNK)


def _spans(log_dir):
    """nalar.* host events of the profile: (line key, name, start, end)."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for p, plane in enumerate(jax.profiler.ProfileData.from_file(path).planes):
        for n, line in enumerate(plane.lines):
            out += [((p, n), e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("nalar.")]
    return out


def test_spans_under_a_profiler_session(model_setup, tmp_path):
    _cfg, model, params = model_setup
    rt, engine = _runtime(model, params)

    def calls(n):
        llm = current_runtime().stub("llm")
        futs = [llm.generate(_tokens(40 * i, 12 + 5 * i)) for i in range(n)]
        return [f.value(timeout=300) for f in futs]

    deployment.main(lambda: calls(1), runtime=rt)     # compile outside
    before = engine.metrics.decode_steps
    with jax.profiler.trace(str(tmp_path)):
        deployment.main(lambda: calls(3), runtime=rt)
        rt.global_controller.run_once()
        time.sleep(0.3)              # the pump's last deliver span closes
        steps = engine.metrics.decode_steps - before
    rt.shutdown()
    spans = _spans(tmp_path)
    assert {name for _l, name, _s, _e in spans} == SPANS

    def inside(step, name):
        line, _n, s, e = step
        return [x for x in spans if x[0] == line and x[1] == name
                and s <= x[2] and x[3] <= e]

    ran = 0
    for step in (x for x in spans if x[1] == "nalar.engine.step"):
        waits = inside(step, "nalar.engine.device_wait")
        dispatched = inside(step, "nalar.engine.dispatch")
        assert len(waits) == len(dispatched) <= 1
        ran += len(dispatched)
    assert ran == steps > 0
