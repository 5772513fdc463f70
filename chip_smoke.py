#!/usr/bin/env python3
"""Bring-up smoke run: the served path on one TPU chip, end to end.

    python3 chip_smoke.py [--seed 0]

Serves qwen3-0.6b at its published width (28 layers, d_model 1024, 16/8
heads, head_dim 128, vocab 151936) with random weights drawn from
``--seed``, through the entry points a deployment uses:
``build_pool_runtime`` -> ``EnginePool`` -> ``InferenceEngine`` on the paged
plane with the Pallas paged-attention kernel, behind ``launch/serve.py``'s
OpenAI-compatible ``/v1/chat/completions`` on an ephemeral localhost port.
One process, no children: the chip belongs to one process at a time.

Eight requests go over TCP, five of them at once: prompts of about 1000
words, four of them sharing a 512-word prefix (prefix index + chunked
prefill at real length); one streamed and compared byte for byte with the
same prompt answered whole; one sampled at temperature 0.7.  The run fails
(non-zero exit, no result line) on any non-200 answer, a completion short
of ``max_tokens``, a request that left the paged plane, a step without the
compiled kernel, or kernel logits that stray from the gathered path's
beyond the bound derived in :func:`compare_kernel`.

The lines before the last are bring-up facts, not benchmark metrics.  The
last line is one JSON object naming the device.  Without a TPU the script
exits non-zero before it builds anything.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3_0_6b"
MAX_BATCH, MAX_SEQ = 8, 2048
PROMPT_WORDS, PREFIX_WORDS, MAX_TOKENS = 1000, 512, 64
SHARED, SOLO = 4, 3           # prompts with / without the shared prefix


def fact(name: str, value) -> None:
    print(f"[bring-up fact, not a benchmark metric] {name}: {value}",
          flush=True)


def require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    return devices


def post(port: int, payload: dict):
    """One chat completion over TCP.  Whole answers -> the parsed body;
    ``"stream": true`` -> the SSE event payloads in arrival order."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status} for {payload.get('user')}")
        if not payload.get("stream"):
            return json.loads(resp.read())
        events = []
        for raw in resp:
            line = raw.decode().strip()
            if line == "data: [DONE]":
                break
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
        return events


def completion_text(body: dict) -> str:
    n = body["usage"]["completion_tokens"]
    if n != MAX_TOKENS:
        raise RuntimeError(f"{n} completion tokens, asked {MAX_TOKENS}")
    return body["choices"][0]["message"]["content"]


def streamed_text(events: list) -> str:
    errors = [e for e in events if e["object"] != "chat.completion.chunk"]
    if errors or events[-1]["choices"][0]["finish_reason"] != "stop":
        raise RuntimeError(f"stream did not finish cleanly: {events[-1]}")
    text = "".join(e["choices"][0]["delta"].get("content") or ""
                   for e in events)
    if len(text.split()) != MAX_TOKENS:
        raise RuntimeError(f"{len(text.split())} streamed tokens, asked "
                           f"{MAX_TOKENS}")
    return text


def compare_kernel(engine, sessions, seed: int) -> None:
    """Logits of one paged step with the Pallas kernel and with the
    gathered-dense attention, on the same pages.

    The pages are the finished sessions' own, copied in reverse order so
    the page tables are no identity map; one extra blank page per row takes
    the chunk's writes.  Rows alternate T=8 and T=1 valid tokens.

    Tolerance: both paths attend the same bf16 K/V in float32 and differ
    only in summation order and in the gathered path's matmul precision
    (XLA's default on TPU rounds f32 operands to bf16): relative error
    u = 2^-8 per layer in the attention output, which the model rounds to
    bf16 anyway.  Carried by the bf16 residual stream through n_layers
    layers and the shared final norm and unembedding, that moves the
    logits by at most n_layers * u * max|logit|; the bf16 logits' own
    rounding adds one ulp, 2u * max|logit|.  Bound:
    (n_layers + 2) * 2^-8 * max|logit| (30/256 ~ 0.12 of the largest logit
    for qwen3-0.6b).  A wrong mask, head or page moves them by
    O(max|logit|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import paged_step_fn

    pool, B, T = engine.pool, engine.max_batch, 8
    rows = [pool.session(s) for s in sessions][:B]
    pages = sorted({p for sp in rows for p in sp.pages}, reverse=True)
    where = {p: i for i, p in enumerate(pages)}
    idx = jnp.asarray(pages)
    blank = jnp.zeros(pool.k.shape[:1] + (len(rows),) + pool.k.shape[2:],
                      pool.k.dtype)
    kp = jnp.concatenate([pool.k[:, idx], blank], axis=1)
    vp = jnp.concatenate([pool.v[:, idx], blank], axis=1)
    pt = np.full((B, engine._max_pages), -1, np.int32)
    pos = np.zeros(B, np.int32)
    valid = np.zeros(B, np.int32)
    for b, sp in enumerate(rows):
        if sp.tokens + T > engine.max_seq:
            raise RuntimeError(f"session {sp.session_id} has no room")
        table = [where[p] for p in sp.pages] + [len(pages) + b]
        pt[b, :len(table)] = table[:engine._max_pages]
        pos[b] = sp.tokens
        valid[b] = T if b % 2 == 0 else 1
    toks = np.random.default_rng(seed).integers(
        0, engine.cfg.vocab_size, (B, T), dtype=np.int32)
    logits = {}
    for kernel in (True, False):
        step = jax.jit(paged_step_fn(engine.model, engine.max_seq, kernel,
                                     all_rows=True))
        out = step(engine.params, jnp.asarray(toks), jnp.asarray(valid),
                   {"pos": jnp.asarray(pos)}, kp, vp, jnp.asarray(pt))[0]
        logits[kernel] = np.asarray(out, np.float32)
    live = np.arange(T)[None, :] < valid[:, None]          # [B,T]
    got, ref = logits[True][live], logits[False][live]
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise RuntimeError("non-finite logits")
    diff = float(np.max(np.abs(got - ref)))
    top = float(np.max(np.abs(ref)))
    bound = (engine.cfg.n_layers + 2) * 2.0 ** -8 * top
    fact("kernel vs gathered logits, max |diff| over "
         f"{int(live.sum())} positions", diff)
    fact("kernel vs gathered logits, bound (n_layers+2)*2^-8*max|logit|",
         bound)
    if not diff <= bound:
        raise RuntimeError(f"kernel logits off by {diff} > {bound}")


def check_compiled_kernel(engine) -> None:
    """The engine's served step lowers with the Pallas kernel compiled for
    the chip, not interpreted."""
    import jax.numpy as jnp
    B = engine.max_batch
    hlo = engine._paged_step.lower(
        engine.params, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), engine.cache, engine.pool.k,
        engine.pool.v, jnp.zeros((B, engine._max_pages), jnp.int32)).as_text()
    if "tpu_custom_call" not in hlo:
        raise RuntimeError("the served step holds no compiled kernel")
    fact("served step holds the compiled Pallas kernel (tpu_custom_call)",
         True)


def warm_up(engine, record) -> float:
    """Compile every step shape the requests use before they arrive:
    chunk widths 8, 4, 2 and 1, and the stochastic sampler."""
    from repro.serving.sampler import SamplingParams
    t0 = time.monotonic()
    for first, n, temperature in ((1, 12, 0.0), (100, 2, 0.0),
                                  (200, 2, 0.7)):
        record.append(engine.generate(
            list(range(first, first + n)),
            sampling=SamplingParams(max_new_tokens=2,
                                    temperature=temperature)))
    return time.monotonic() - t0


def serve_and_check(rt, seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import OpenAIFrontend

    (bridge,) = rt.engine_backends["llm"].bridges.values()
    engine = bridge.engine
    cfg = engine.cfg
    if cfg != get_config(ARCH):
        raise RuntimeError(f"engine serves {cfg.arch_id}, not {ARCH}")
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    fact("model", f"{cfg.arch_id}: {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
         f"{cfg.head_dim_}, vocab {cfg.vocab_size}, {n_params} parameters "
         f"(random, seed {seed})")
    fact("engine", f"max_batch {engine.max_batch}, max_seq "
         f"{engine.max_seq}, page {engine.pool.page_size}, pool "
         f"{engine.pool.n_pages} pages, prefill chunk "
         f"{engine.prefill_chunk}")
    if not (engine._paged and engine._paged_kernel):
        raise RuntimeError("engine is not on the paged plane with the kernel")

    record = []                      # every Request the engine is handed
    submit = engine.submit_async

    def recording_submit(req, *a, **kw):
        record.append(req)
        return submit(req, *a, **kw)

    engine.submit_async = recording_submit

    compile_s = [0.0]

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    fact("warm-up wall seconds (compiles included)",
         warm_up(engine, record))
    fact("backend compile seconds in warm-up", compile_s[0])

    rt.start()
    fe = OpenAIFrontend(rt, default_max_tokens=MAX_TOKENS,
                        request_timeout=900.0)
    server = fe.serve(port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        rng = random.Random(seed)

        def words(n: int) -> str:
            return " ".join(f"w{rng.randrange(1 << 30)}" for _ in range(n))

        prefix = words(PREFIX_WORDS)
        shared = [f"{prefix} {words(PROMPT_WORDS - PREFIX_WORDS)}"
                  for _ in range(SHARED)]
        solo = [words(PROMPT_WORDS) for _ in range(SOLO)]

        def ask(content: str, **kw):
            return post(port, {"model": "nalar-llm", "max_tokens": MAX_TOKENS,
                               "messages": [{"role": "user",
                                             "content": content}], **kw})

        t0 = time.monotonic()
        whole = completion_text(ask(solo[0]))
        if streamed_text(ask(solo[0], stream=True)) != whole:
            raise RuntimeError("streamed text differs from the whole answer")
        fact("streamed == whole answer, byte for byte", True)
        # the first shared-prefix session indexes the prefix pages ...
        completion_text(ask(shared[0], user="agent-0"))
        # ... which the next three adopt, alongside unshared traffic
        wave = [dict(content=shared[i], user=f"agent-{i}")
                for i in range(1, SHARED)]
        wave += [dict(content=solo[1], user="solo-1"),
                 dict(content=solo[2], user="solo-2", temperature=0.7)]
        results, errors = [None] * len(wave), []

        def run(i: int) -> None:
            try:
                results[i] = completion_text(ask(**wave[i]))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(wave))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        wall = time.monotonic() - t0
    finally:
        server.shutdown()
    n_requests = 3 + len(wave)
    fact("requests answered in full over /v1/chat/completions",
         f"{n_requests} x {MAX_TOKENS} tokens")
    fact("wall seconds for the requests", wall)
    fact("tokens generated for the requests", n_requests * MAX_TOKENS)
    m = engine.metrics
    fact("tokens generated by the engine (warm-up included)",
         m.tokens_generated)
    fact("engine steps (warm-up included)", m.decode_steps)
    per_hit = (PREFIX_WORDS + 1) // engine.pool.page_size * \
        engine.pool.page_size                 # "user:" + the prefix words
    fact("prefix-hit tokens", f"{m.shared_prefix_tokens} over "
         f"{m.shared_prefix_hits} hits")
    if m.shared_prefix_tokens < (SHARED - 1) * per_hit:
        raise RuntimeError("shared-prefix sessions missed the prefix index")

    paths = sorted({r.decode_path for r in record})
    fact("decode paths of the finished requests", paths)
    if not all(r.finished for r in record) or paths != ["paged"]:
        raise RuntimeError(f"requests left the paged plane: {paths}")
    check_compiled_kernel(engine)
    compare_kernel(engine, [f"agent-{i}" for i in range(SHARED)]
                   + ["solo-1", "solo-2"], seed)

    if len(record) != 3 + n_requests:
        raise RuntimeError(f"engine saw {len(record)} requests")
    stats = jax.devices()[0].memory_stats() or {}
    fact("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    leaf = jax.tree_util.tree_leaves(engine.params)[0]
    fact("device of params", sorted(str(d) for d in leaf.devices()))
    fact("device of pool pages",
         sorted(str(d) for d in engine.pool.k.devices()))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the random weights and the prompts")
    args = p.parse_args()
    devices = require_tpu()
    dev = devices[0]
    from repro.launch.compile_cache import use_compile_cache
    from repro.workloads.router import build_pool_runtime

    fact("device_kind", f"{dev.device_kind} x {len(devices)}")
    fact("compile cache", use_compile_cache())
    rt = build_pool_runtime(replicas=1, arch=ARCH, max_batch=MAX_BATCH,
                            max_seq=MAX_SEQ, max_new_tokens=MAX_TOKENS,
                            seed=args.seed, smoke=False)
    try:
        serve_and_check(rt, args.seed)
    finally:
        rt.shutdown()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
