"""JAX inference engine: slot-based continuous batching over any zoo model.

The engine is the "LLM serving backend" of the reproduction (the vLLM role
in the paper's stack).  One engine instance = one NALAR agent instance; the
engine exports queue/latency telemetry and consumes KVRegistry hints via its
cache pool, which is precisely the LMCache-hook integration of §4.3.2.

Execution model:
  * ``max_batch`` slots share a stacked per-slot cache (model.init_cache);
  * admission pulls from a bounded, heap-ordered priority wait queue; a new
    request either resumes its session's cache from the pool (prefix reuse —
    the paper's motivating win for session stickiness/migration) or starts a
    **chunked prefill**: the prompt is admitted into a blank cache row and
    consumed ``prefill_chunk`` tokens per step, piggybacked onto the same
    batched decode the active slots run — a long prompt therefore never
    head-of-line-blocks the batch the way the legacy monolithic (left-padded
    bucket) prefill does, and no pad token ever enters the KV cache;
  * each ``step()`` runs one batched step: every decoding slot advances one
    token while prefilling slots consume up to a chunk of prompt (masked
    sub-steps over the shared jitted decode fn);
  * a bounded wait queue (``max_queue``) rejects overflow with
    ``EngineOverloaded`` — backpressure the bridge turns into a retryable
    failure instead of unbounded queue growth — and exports a saturation
    watermark so routers/policies shed load before collapse;
  * finished sessions write their cache back to the pool so follow-up
    requests in the same session skip recomputation;
  * **paged-native decode** (default where supported): the pool pages ARE
    the decode cache — each step feeds per-slot page tables into the model
    and scatters new K/V straight into pool pages (COW-privatized first if
    shared), so admission, resume, eviction and finish move zero cache
    bytes and the dense per-slot K/V arrays are never allocated.
    ``paged_decode=False`` restores the dense gather/write-back path.

Host spans: ``step`` and its phases run inside ``jax.profiler`` trace
annotations with fixed names (``nalar.engine.step`` holding
``nalar.engine.admit``, ``.prepare``, ``nalar.kv.append``,
``nalar.engine.dispatch``, ``.device_wait``, ``nalar.kv.commit``,
``nalar.engine.sample`` and ``.emit``), on the profiler's clock, so a
device trace says which host work each idle gap sat in.  They record only
while a profiler session is active.  ``device_wait`` holds nothing but the
step's first host read of its outputs: outside it, the step's host time.
"""

from __future__ import annotations

import threading
import time
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from ..configs.base import ModelConfig
from ..models.model import Model
from .batching import (EngineOverloaded, Request, RequestExpired, WaitQueue,
                       bucket_len)
from .kv_cache import PagedKVPool, StateCachePool
from .sampler import SamplingParams, sample, speculative_verify

# model families whose decode step, run token-by-token from a blank cache
# row, is exactly prefill (causal attention / recurrent state).  Encoder-
# decoder ("audio") models chunk too (``_chunked_for`` special-cases them:
# one ``encode_cross`` pass supplies the cross-attention memory first), but
# stay OUT of this tuple — it also gates prefix sharing, and audio decoder
# K/V depends on the frames, so token-identity never implies K/V-identity.
_CHUNKABLE_FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm")


@dataclass
class EngineMetrics:
    queued: int = 0
    active: int = 0
    completed: int = 0
    decode_steps: int = 0
    prefills: int = 0
    prefix_hits: int = 0
    # cross-session sharing: cold sessions admitted onto another session's
    # indexed prefix pages (suffix-only prefill)
    shared_prefix_hits: int = 0
    shared_prefix_tokens: int = 0
    tokens_generated: int = 0
    prefill_tokens: int = 0
    admission_rejects: int = 0
    # resumes refused because the restored cache would not fit the slot
    # (previously a silent None -> cold rebuild)
    resume_overflows: int = 0
    # resumes refused because the family cannot restore from the pool
    # (encoder-decoder: cross-attention memory is not poolable; previously
    # the dense path silently resumed with zeroed xk/xv)
    resume_unsupported: int = 0
    # paged-native admissions/steps aborted because the pool could not
    # provide pages (all residents protected or pinned)
    paged_append_failures: int = 0
    # speculative decoding: rounds that ran a draft, draft tokens proposed,
    # and tokens the verifier accepted
    spec_rounds: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    # requests dropped because their deadline passed: rejected at admission
    # (push/pop) or preempted mid-decode with slot + KV pages reclaimed
    expired: int = 0

    @property
    def spec_acceptance(self) -> float:
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    @property
    def decode_tokens_per_step(self) -> float:
        """Acceptance-weighted decode throughput: > 1 means speculation is
        paying (every accepted draft token rides a step for free)."""
        return (self.tokens_generated / self.decode_steps
                if self.decode_steps else 0.0)


def _cache_slot_axis(key: str) -> int:
    return 0 if key == "pos" else 1


def set_slot(cache: dict, slot: int, row: dict) -> dict:
    """Insert a single sequence's cache (batch dim 1) into batch slot.

    Row caches produced by bucketed prefill can be shorter in the seq dim
    than the slot cache; they are zero-padded at the end (consistent with
    the ring layout: prefill caches are unrolled when S <= window).
    """
    out = {}
    for k, v in cache.items():
        ax = _cache_slot_axis(k)
        r = row[k]
        r = jnp.squeeze(r, axis=ax) if r.ndim == v.ndim else r
        target = tuple(s for i, s in enumerate(v.shape) if i != ax)
        if tuple(r.shape) != target:
            pads = [(0, t - s) for s, t in zip(r.shape, target)]
            if any(p[1] < 0 for p in pads):
                raise ValueError(f"row cache leaf {k}: {r.shape} exceeds "
                                 f"slot shape {target}")
            r = jnp.pad(r, pads)
        idx = [slice(None)] * v.ndim
        idx[ax] = slot
        out[k] = v.at[tuple(idx)].set(r)
    return out


def get_slot(cache: dict, slot: int) -> dict:
    out = {}
    for k, v in cache.items():
        ax = _cache_slot_axis(k)
        out[k] = jnp.expand_dims(jnp.take(v, slot, axis=ax), axis=ax)
    return out


def default_pool_pages(max_batch: int, max_seq: int, page_size: int) -> int:
    """KV pool size when the caller gives none: two full contexts' worth of
    pages per batch slot (room for resident sessions between turns)."""
    return max_batch * (max_seq // page_size + 1) * 2


def paged_step_fn(model: Model, max_seq: int, kernel: bool,
                  all_rows: bool = False) -> Callable:
    """The paged plane's fused step, unjitted: chunked prefill + decode +
    per-slot sampling prep in one function over (params, tokens [B,T],
    valid [B], slim cache, k pages, v pages, page table [B,max_pages]).

    Returns (logits, greedy, slim cache, k pages, v pages).  By default
    ``logits`` is each slot's next-token row [B,V] (taken at its last valid
    position) and ``greedy`` its argmax [B]: the [B,T,V] logits never
    leave the device.  ``all_rows=True`` (the speculative verifier, which
    needs every position) returns the full [B,T,V] logits and the
    per-position argmax [B,T]."""
    paged_fn = model.decode_chunk_paged

    def step(params, toks, valid, cache, kp, vp, pt):
        logits, cache, kp, vp = paged_fn(params, toks, valid, cache, kp, vp,
                                         pt, max_seq=max_seq, kernel=kernel)
        if all_rows:
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, greedy, cache, kp, vp
        rows = jnp.take_along_axis(
            logits, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)[:, 0]
        return rows, jnp.argmax(rows, axis=-1), cache, kp, vp

    return step


class InferenceEngine:
    def __init__(self, model: Model, params: dict, *, max_batch: int = 8,
                 max_seq: int = 512, instance_id: str = "engine:0",
                 kv_registry=None, pool_pages: int = 0,
                 page_size: int = 64, rng_seed: int = 0,
                 prefill_chunk: int = 8, max_queue: int = 0,
                 queue_watermark: float = 0.75,
                 finished_cap: int = 8192,
                 prefix_sharing: bool = True,
                 paged_decode: bool = True,
                 paged_kernel: Optional[bool] = None,
                 draft_model: Optional[Model] = None,
                 draft_params: Optional[dict] = None,
                 spec_k: int = 3,
                 spec_min_accept: float = 0.25,
                 spec_warmup: int = 24,
                 tier: str = "") -> None:
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.instance_id = instance_id
        self.kv_registry = kv_registry
        self.metrics = EngineMetrics()
        # optional fault injector (repro.serving.chaos.ChaosConfig-driven);
        # when set, step() calls chaos.before_step(engine) outside the lock
        self.chaos: Optional[Any] = None
        # prompt tokens consumed per slot per step while prefilling;
        # 0 = legacy monolithic bucket prefill at admission
        self.prefill_chunk = int(prefill_chunk)
        self.max_queue = int(max_queue)
        # saturation fraction above which routers should shed new sessions
        # to a sibling replica (surfaced via telemetry(); advisory only)
        self.queue_watermark = queue_watermark
        self.finished_cap = int(finished_cap)
        self.queue = WaitQueue(maxsize=self.max_queue)
        self._rng = jax.random.PRNGKey(rng_seed)     # base of request streams
        self._lock = threading.RLock()
        # completion plumbing has its own lock: submissions and drains must
        # never serialize behind a long step (a monolithic prefill used to
        # block submit_async for its whole duration)
        self._done_lock = threading.Lock()

        # per-slot state
        self.slots: List[Optional[Request]] = [None] * max_batch
        self._active_mask = np.zeros(max_batch, bool)
        # slot -> prompt tokens not yet consumed (resumed suffixes and
        # chunked prefills); always cleared when the slot is vacated
        self._pending_prompt: Dict[int, List[int]] = {}
        # request_id -> private PRNG stream (stochastic sampling only)
        self._req_rng: Dict[str, jax.Array] = {}
        self._blank_row_cache: Optional[dict] = None

        # session cache pool (paged KV for attention families, O(1) state
        # for ssm/hybrid) + NALAR hint hook
        if self.cfg.family == "ssm":
            self.pool: Any = StateCachePool(self.cfg)
        elif self.cfg.family == "hybrid":
            self.pool = StateCachePool(self.cfg)
        else:
            n_pages = pool_pages or default_pool_pages(max_batch, max_seq,
                                                       page_size)
            self.pool = PagedKVPool(self.cfg, n_pages=n_pages,
                                    page_size=page_size)
        if kv_registry is not None:
            kv_registry.register_hook(instance_id, self.pool.on_hint)

        # cross-session prefix sharing: admission/warm_session consult the
        # pool's radix index before prefilling.  Valid only when a cache
        # position maps 1:1 to a token prefix position — paged pools,
        # causal-chunkable families, no sliding-window ring wraparound.
        self.prefix_sharing = bool(prefix_sharing)
        W = self.cfg.sliding_window
        self._prefix_share_ok = (
            self.prefix_sharing
            and isinstance(self.pool, PagedKVPool)
            and self.cfg.family in _CHUNKABLE_FAMILIES
            and (not W or self.max_seq <= W))
        # paged-native decode (the tentpole): the KV pool IS the decode
        # cache.  The per-slot dense k/v arrays are dropped entirely; each
        # step consumes per-slot page tables and scatters new K/V straight
        # into pool pages, so admission/eviction/finish move no cache bytes
        # (``gather_contiguous`` leaves the hot path).  Windowed configs
        # qualify only when the ring never wraps (max_seq <= window, the
        # same condition as prefix sharing) — slot == position then, so the
        # linear page layout matches the ring layout bitwise.
        self.paged_decode = bool(paged_decode)
        self._paged = (self.paged_decode
                       and isinstance(self.pool, PagedKVPool)
                       and model.decode_chunk_paged is not None
                       and (not W or self.max_seq <= W))
        # Pallas paged-attention kernel instead of the bitwise-identical
        # gathered-dense attention inside the paged step (auto-on on TPU;
        # near-identical numerics, not bitwise)
        self._paged_kernel = (bool(paged_kernel) if paged_kernel is not None
                              else jax.default_backend() == "tpu")
        # dense per-slot cache length (what self.cache["k"].shape[2] was)
        self._slot_C = min(self.max_seq, W) if W else self.max_seq
        # slot -> pool session key the slot decodes into (paged mode only;
        # anonymous requests get a synthetic key released at vacate)
        self._slot_sid: Dict[int, str] = {}
        if self._paged:
            # slim cache: every leaf but the dense k/v, which are never
            # allocated (at published widths they would cost as much HBM as
            # the pool itself)
            shapes = model.cache_shapes(max_batch, max_seq)
            self.cache = {key: jnp.zeros(s.shape, s.dtype)
                          for key, s in shapes.items()
                          if key not in ("k", "v")}
            self._max_pages = self.pool.pages_needed(self.max_seq)
        else:
            self.cache = model.init_cache(max_batch, max_seq)
        # slot -> token ids whose K/V occupy the slot's cache positions so
        # far (None = unknown provenance, the finish write stays opaque)
        self._slot_tokens: Dict[int, Optional[List[int]]] = {}
        # lazily jitted batch-1 fns for suffix-only warm extension
        self._extend_chunk: Optional[Callable] = None
        self._extend_step: Optional[Callable] = None

        def _masked_decode(params, tokens, cache, mask):
            # one batched decode where only masked-in slots advance: the
            # cache (and pos) of a masked-out slot is untouched, so prompt
            # chunks and single decode tokens share one compiled step
            logits, new = model.decode_step(params, tokens, cache)
            out = {}
            for k in new:
                ax = _cache_slot_axis(k)
                shp = [1] * new[k].ndim
                shp[ax] = new[k].shape[ax]
                out[k] = jnp.where(mask.reshape(shp), new[k], cache[k])
            return logits, out

        self._masked_decode = jax.jit(_masked_decode)
        # fused chunk step (transformer families): a whole prompt chunk is
        # one forward instead of prefill_chunk sequential decodes.  Two
        # compiled shapes only: T=1 (decode-only steps) and T=prefill_chunk.
        self._decode_chunk = (jax.jit(model.decode_chunk)
                              if model.decode_chunk is not None else None)
        # paged-native fused step: chunked prefill + decode + per-slot
        # sampling prep in ONE jit over (slim cache, pool pages, page
        # tables).  Only the [B,V] next-token rows and the greedy argmax
        # cross the host boundary — the [B,T,V] logits never leave device.
        self._paged_step: Optional[Callable] = None
        # donate the pool arrays on TPU so the step updates them in place
        # (CPU donation is a no-op and only warns)
        donate = (4, 5) if jax.default_backend() == "tpu" else ()
        if self._paged:
            self._paged_step = jax.jit(
                paged_step_fn(model, self.max_seq, self._paged_kernel),
                donate_argnums=donate)

        # speculative decoding (paged plane only): a small-tier draft
        # proposes spec_k tokens per decode round; the same paged chunk
        # step verifies all k+1 positions at once.  The spec variant of the
        # step jit returns the full [B,T,V] logits plus per-position argmax
        # — the verifier needs every row, not just the last valid one.
        self.tier = str(tier)
        self.spec_k = int(spec_k)
        self.spec_min_accept = float(spec_min_accept)
        self.spec_warmup = int(spec_warmup)
        self._spec = None
        self._paged_step_all: Optional[Callable] = None
        # pool-session -> [proposed, accepted]: the acceptance ledger the
        # adaptive controller reads to disable speculation per session
        self._spec_ledger: Dict[str, List[int]] = {}
        self._spec_off: set = set()
        # slots whose draft stream mirrors the target's consumed tokens
        # (unknown provenance = no speculation for that slot)
        self._spec_ok: set = set()
        if draft_model is not None and self._paged and self.spec_k > 0:
            if draft_model.cfg.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    "draft/target vocab mismatch: "
                    f"{draft_model.cfg.vocab_size} vs {self.cfg.vocab_size}")
            from .speculative import DraftEngine
            self._spec = DraftEngine(draft_model, draft_params,
                                     max_batch=max_batch, max_seq=max_seq)
            self._paged_step_all = jax.jit(
                paged_step_fn(model, self.max_seq, self._paged_kernel,
                              all_rows=True),
                donate_argnums=donate)
        # lazily jitted encoder pass for chunked encoder-decoder admission
        self._encode_cross: Optional[Callable] = None
        self._prefill_cache: Dict[int, Callable] = {}

        # async completion plumbing (NALAR bridge): request_id -> callback,
        # plus a list of finished requests awaiting drain.  Callbacks fire
        # outside the step lock so they may re-enter submit().
        self._callbacks: Dict[str, Callable[[Request], None]] = {}
        self._finished: List[Request] = []
        # token-streaming plumbing: per-request chunk callbacks plus the
        # pending (request, chunk) pairs each step emitted.  Chunks are
        # buffered under _done_lock at the end of step()/_finish_slot and
        # delivered by drain_completions — off the step lock, before the
        # completion callback of the same request.
        self._stream_cbs: Dict[str, Callable[[Request, List[int]], None]] = {}
        self._stream_pending: List[tuple] = []

    # ----------------------------------------------------------- submission
    def submit(self, req: Request) -> str:
        """Queue ``req``.  Raises :class:`EngineOverloaded` when the bounded
        wait queue is at capacity (backpressure — callers retry or shed) and
        :class:`RequestExpired` when the request's deadline already passed."""
        if req.submitted_wall < 0:
            req.submitted_wall = time.monotonic()
        try:
            self.queue.push(req)
        except EngineOverloaded:
            self.metrics.admission_rejects += 1
            raise
        except RequestExpired:
            self.metrics.expired += 1
            raise
        return req.request_id

    def submit_async(self, req: Request,
                     on_done: Optional[Callable[[Request], None]] = None,
                     on_chunk: Optional[
                         Callable[[Request, List[int]], None]] = None) -> str:
        """Queue ``req``; ``on_done(req)`` fires from ``drain_completions``
        after the request finishes (the NALAR future-resolution hook).
        ``on_chunk(req, tokens)`` fires from the same drain for every batch
        of tokens the request's slot emitted since the previous drain —
        in order, and always before the request's ``on_done``."""
        with self._done_lock:
            if on_done is not None:
                self._callbacks[req.request_id] = on_done
            if on_chunk is not None:
                self._stream_cbs[req.request_id] = on_chunk
        try:
            return self.submit(req)
        except BaseException:
            with self._done_lock:       # rejected: no completion will fire
                self._callbacks.pop(req.request_id, None)
                self._stream_cbs.pop(req.request_id, None)
            raise

    def poll_finished(self) -> List[Request]:
        """Requests finished since the last poll/drain (no callbacks fired)."""
        with self._done_lock:
            out, self._finished = self._finished, []
        return out

    def drain_completions(self) -> int:
        """Fire stream-chunk then completion callbacks for work the step
        loop emitted.  Called by the bridge pump thread after each step(),
        outside the engine lock — chunk callbacks for a request always fire
        before (and never after) its completion callback."""
        with self._done_lock:
            chunks, self._stream_pending = self._stream_pending, []
            ccbs = [(r, c, self._stream_cbs.get(r.request_id))
                    for r, c in chunks]
            done, self._finished = self._finished, []
            cbs = [(r, self._callbacks.pop(r.request_id, None)) for r in done]
            for r in done:
                self._stream_cbs.pop(r.request_id, None)
        for req, chunk, ccb in ccbs:
            if ccb is not None:
                ccb(req, chunk)
        for req, cb in cbs:
            if cb is not None:
                cb(req)
        return len(cbs)

    def bind_registry(self, kv_registry, instance_id: str) -> None:
        """(Re)bind this engine to a NALAR runtime identity: the engine's
        telemetry and cache-pool hints are tagged with the agent-instance id
        so the runtime's Router and KVRegistry see one coherent name."""
        self.instance_id = instance_id
        self.kv_registry = kv_registry
        if kv_registry is not None:
            kv_registry.register_hook(instance_id, self.pool.on_hint)

    def generate(self, prompt, session_id: str = "",
                 sampling: Optional[SamplingParams] = None,
                 **extras) -> Request:
        """Synchronous helper: submit + run until this request finishes."""
        req = Request.make(prompt, session_id=session_id, sampling=sampling,
                           now=time.monotonic(), **extras)
        self.submit(req)
        while not req.finished:
            self.step()
        return req

    # ------------------------------------------------------------ admission
    def saturation(self) -> float:
        """Wait-queue depth as a fraction of capacity (0.0 if unbounded)."""
        return self.queue.saturation()

    def overloaded(self) -> bool:
        """Above the shed watermark: routers should prefer a sibling."""
        return bool(self.max_queue) and self.saturation() >= self.queue_watermark

    def _prefill(self, req: Request, align: str = "left"):
        """Monolithic bucketed prefill (legacy path + migration replay).

        ``align="right"`` places the prompt at the start of the bucket:
        under causal attention the trailing pads never contaminate the
        first ``len(prompt)`` cache positions, so callers that only need
        the cache (``warm_session``) get an exact-token prefix.  The
        left-aligned default keeps the final position's logits real, at
        the cost of pad positions entering the cache (the legacy
        exposure chunked prefill removes).
        """
        S = len(req.prompt)
        bucket = min(bucket_len(S), self.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        if align == "right":
            toks[0, :S] = req.prompt
        else:
            toks[0, -S:] = req.prompt      # left-pad so last position is real
        batch = {"tokens": jnp.asarray(toks)}
        for k, v in req.extras.items():
            batch[k] = jnp.asarray(v[None] if v.ndim == 2 else v)
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = jax.jit(self.model.prefill)
        logits, row_cache = self._prefill_cache[bucket](self.params, batch)
        self.metrics.prefills += 1
        self.metrics.prefill_tokens += S
        return logits, row_cache

    def _try_resume(self, req: Request):
        """Prefix reuse: restore this session's cache from the pool.

        Refusals are explicit and counted (``resume_overflows`` /
        ``resume_unsupported``) — a ``None`` always means the caller
        rebuilds the context cold.  In paged mode a successful resume moves
        no bytes at all: the slot simply adopts the session's pages and the
        sentinel ``("paged", tokens)`` is returned instead of a dense row.
        """
        if isinstance(self.pool, StateCachePool):
            payload = self.pool.load(req.session_id)
            if payload is None:
                return None
            state, tokens = payload
            return state, tokens
        if self.cfg.family == "audio":
            # decoder self-attention K/V is poolable, but the cross-
            # attention memory (xk/xv) is not: a resumed slot would cross-
            # attend zeros.  The dense path used to do exactly that
            # silently; refuse and count instead.
            sp = self.pool.session(req.session_id)
            if sp is not None and sp.pages:
                self.metrics.resume_unsupported += 1
            return None
        if self._paged:
            sp = self.pool.session(req.session_id)
            if sp is None or not sp.pages or sp.tokens <= 0:
                return None
            if sp.tokens > self.max_seq:
                self.metrics.resume_overflows += 1
                return None
            return "paged", sp.tokens
        got = self.pool.gather_contiguous(req.session_id, self.max_seq)
        if got is None:
            return None
        k, v, tokens = got
        C = self._slot_C
        pad = C - k.shape[1]
        if pad < 0:
            self.metrics.resume_overflows += 1
            return None
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None]
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None]
        row = {key: None for key in self.cache}
        row["k"], row["v"] = k, v
        row["pos"] = jnp.asarray([tokens], jnp.int32)
        for key in self.cache:
            if row.get(key) is None:   # xk/xv etc.: zeros
                ax = _cache_slot_axis(key)
                shp = list(self.cache[key].shape)
                shp[ax] = 1
                row[key] = jnp.zeros(shp, self.cache[key].dtype)
        return row, tokens

    def _blank_row(self) -> dict:
        """Zeroed single-slot cache row for chunked-prefill admission
        (recurrent families accumulate state unconditionally, so a recycled
        slot must never start from its previous occupant's row)."""
        if self._blank_row_cache is None:
            row = {}
            for k, v in self.cache.items():
                ax = _cache_slot_axis(k)
                shp = tuple(s for i, s in enumerate(v.shape) if i != ax)
                row[k] = jnp.zeros(shp, v.dtype)
            self._blank_row_cache = row
        return self._blank_row_cache

    def _paged_row(self, tokens: int) -> dict:
        """Slim cache row for a paged-native admission: position only — the
        K/V lives in the session's pool pages."""
        row = dict(self._blank_row())
        row["pos"] = jnp.asarray(tokens, jnp.int32)
        return row

    def _resumed_slot_tokens(self, req: Request,
                             tokens: int) -> Optional[List[int]]:
        """Token provenance of a resumed slot: the pool session's ids, when
        they exactly describe the restored cache positions."""
        if not self._prefix_share_ok:
            return None
        sp = self.pool.session(req.session_id)
        if (sp is not None and sp.tokens == tokens
                and len(sp.token_ids) == sp.tokens):
            return list(sp.token_ids)
        return None

    def _chunked_for(self, req: Request) -> bool:
        if self.prefill_chunk <= 0:
            return False
        if self.cfg.family == "audio":
            # encoder-decoder: one encoder pass computes the cross-attn
            # memory (exactly the bytes prefill would), then the decoder
            # prompt chunks like any causal family
            return (set(req.extras) == {"frames"}
                    and self.model.encode_cross is not None
                    and self.model.decode_chunk is not None)
        if req.extras:
            return False
        return self.cfg.family in _CHUNKABLE_FAMILIES

    def _request_key(self, req: Request) -> jax.Array:
        sp = req.sampling
        salt = (sp.seed if sp.seed is not None
                else zlib.crc32(req.request_id.encode()))
        return jax.random.fold_in(self._rng, int(salt) & 0x7FFFFFFF)

    def _sample_slot(self, req: Request, logits, row: int,
                     greedy: np.ndarray) -> int:
        """Sample one token for ``row`` with the request's *own* params,
        exactly once.  Greedy requests take the batch argmax and burn no
        RNG; stochastic requests draw from their private per-request
        stream, so batch composition never perturbs a request's samples."""
        sp = req.sampling
        if sp.temperature <= 0.0:
            return int(greedy[row])
        key = self._req_rng.get(req.request_id)
        if key is None:
            key = self._request_key(req)
        key, sub = jax.random.split(key)
        self._req_rng[req.request_id] = key
        return int(np.asarray(sample(logits[row:row + 1], sp, sub))[0])

    @partial(annotate_function, name="nalar.engine.admit")
    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self._active_mask[slot]:
                continue
            req = self.queue.pop_next()
            if req is None:
                return
            now = time.monotonic()
            while 0 <= req.deadline_wall <= now:
                # expired while waiting: never occupy a slot.  Finished
                # with expired=True so the bridge callback still fires and
                # can resolve the future DeadlineExceeded.
                req.expired = True
                req.finished = True
                req.finished_at = now
                self.metrics.expired += 1
                with self._done_lock:
                    self._finished.append(req)
                req = self.queue.pop_next()
                if req is None:
                    return
            if (self._paged and req.session_id
                    and req.session_id in self._slot_sid.values()):
                # the session's pages are already the live in-place write
                # target of an active slot; a second concurrent appender
                # would corrupt them.  Defer until that slot finishes.
                self.queue.push(req)
                return
            req.admitted_at = now
            W = self.cfg.sliding_window
            if self._paged:
                req.decode_path = "paged"
            elif W and self.max_seq > W and self._decode_chunk is not None:
                # windowed config whose ring wraps (max_seq > window): the
                # paged plane cannot serve it (linear page layout != ring
                # layout), so it stays on the dense ring fallback plane
                req.decode_path = "dense-ring"
            elif self._decode_chunk is not None:
                req.decode_path = "fused"
            else:
                req.decode_path = "masked"
            resumed = None
            if req.session_id:
                resumed = self._try_resume(req)
            if resumed is not None and not isinstance(self.pool, PagedKVPool):
                # SSM/hybrid: resumed state + run prompt incrementally is
                # equivalent to prefill; simplest correct path: prefill anyway
                resumed = None
            if resumed is not None:
                _row, cached = resumed
                if cached + len(req.prompt) > self.max_seq - 1:
                    # the resumed suffix would run past the slot's cache
                    # capacity mid-prompt; rebuild the (bounded) full
                    # context cold instead of overflowing the ring
                    self.metrics.resume_overflows += 1
                    resumed = None
            if resumed is None and req.fallback_prompt is not None:
                # The caller sent only a continuation suffix expecting a warm
                # session cache, but the cache is cold (evicted or migrated):
                # rebuild the full context in one prefill instead.
                req.prompt = req.fallback_prompt
            if len(req.prompt) > self.max_seq - 1:
                req.prompt = req.prompt[-(self.max_seq - 1):]
            if (resumed is None and req.session_id and not req.extras
                    and self._prefix_share_ok and len(req.prompt) > 1):
                # cold session: another session may have indexed this
                # prompt's prefix.  Adopt the shared pages and feed only
                # the novel suffix (keep >= 1 token so the final position's
                # logits are computed by a real forward).
                ids = [int(t) for t in req.prompt]
                matched = self.pool.acquire_prefix(req.session_id, ids[:-1],
                                                   now=now)
                if matched > 0:
                    resumed = self._try_resume(req)
                    if resumed is None:    # defensive: capacity race
                        self.pool.release(req.session_id)
                    else:
                        self.metrics.shared_prefix_hits += 1
                        self.metrics.shared_prefix_tokens += matched
                        req.prompt = req.prompt[matched:]
            if resumed is not None:
                row_cache, tokens = resumed
                req.prefix_reused_tokens = tokens
                self.metrics.prefix_hits += 1
                # feed the prompt as additional decode steps (short suffix)
                if self._paged and row_cache == "paged":
                    # zero-copy resume: the slot decodes straight into the
                    # session's resident pages (shared prefix tails are
                    # privatized lazily by begin_append's COW)
                    self.pool.protect(req.session_id)
                    self._slot_sid[slot] = req.session_id
                    self.cache = set_slot(self.cache, slot,
                                          self._paged_row(tokens))
                else:
                    self.cache = set_slot(self.cache, slot, row_cache)
                self._pending_prompt[slot] = [int(t) for t in req.prompt]
                self._slot_tokens[slot] = self._resumed_slot_tokens(req, tokens)
                if self._spec is not None:
                    # the draft can only shadow this slot if the resumed
                    # positions have exact token provenance to replay
                    self._spec.reset(slot)
                    ids = self._slot_tokens[slot]
                    if ids is not None and len(ids) == tokens:
                        self._spec.observe(slot, ids)
                        self._spec_ok.add(slot)
            elif self._chunked_for(req):
                # chunked prefill: blank row now, prompt consumed by step()
                # in prefill_chunk-sized pieces piggybacked on decode
                row = self._blank_row()
                if self.cfg.family == "audio":
                    frames = req.extras["frames"]
                    frames = jnp.asarray(frames[None] if frames.ndim == 2
                                         else frames)
                    if self._encode_cross is None:
                        self._encode_cross = jax.jit(self.model.encode_cross)
                    xk, xv = self._encode_cross(self.params, frames)
                    row = dict(row)
                    row["xk"], row["xv"] = xk[:, 0], xv[:, 0]
                if self._paged:
                    sid = req.session_id or f"__anon:{req.request_id}"
                    if req.session_id:
                        # stale pages from a refused resume would misplace
                        # the first in-place append: start cold
                        self.pool.release(sid)
                    self.pool.protect(sid)
                    self._slot_sid[slot] = sid
                self.cache = set_slot(self.cache, slot, row)
                self._pending_prompt[slot] = [int(t) for t in req.prompt]
                self._slot_tokens[slot] = [] if self._prefix_share_ok else None
                if self._spec is not None:
                    # chunked prefill feeds the whole prompt through the
                    # step loop, which mirrors each chunk into the draft
                    self._spec.reset(slot)
                    self._spec_ok.add(slot)
                self.metrics.prefills += 1
                self.metrics.prefill_tokens += len(req.prompt)
            else:
                logits, row_cache = self._prefill(req)
                req.prefill_steps = 1
                greedy = np.asarray(jnp.argmax(logits, axis=-1))
                tok = self._sample_slot(req, logits, 0, greedy)
                req.generated.append(tok)
                # TTFT: the first token exists *now*, after the prefill
                # compute — not at admission time
                req.first_token_at = time.monotonic()
                S = len(req.prompt)
                bucket = min(bucket_len(S), self.max_seq)
                share = self._prefix_share_ok and not req.extras
                # left-aligned bucket prefill: pad token 0's K/V enters
                # the leading positions and is part of the provenance
                ids = ([0] * (bucket - S) + [int(t) for t in req.prompt]
                       if share else None)
                if self._paged:
                    sid = req.session_id or f"__anon:{req.request_id}"
                    tokens = int(np.asarray(row_cache["pos"]).reshape(-1)[0])
                    if req.session_id:
                        self.pool.release(sid)
                    if tokens > self.max_seq or not self.pool.write_session(
                            sid, row_cache["k"][:, 0, :tokens],
                            row_cache["v"][:, 0, :tokens], tokens, now,
                            token_ids=ids):
                        # pool exhausted (residents all protected/pinned):
                        # deliver what we have instead of wedging the slot
                        self.metrics.paged_append_failures += 1
                        self.metrics.tokens_generated += 1
                        req.finished = True
                        req.finished_at = time.monotonic()
                        self.metrics.completed += 1
                        self._emit_stream(req)
                        with self._done_lock:
                            self._finished.append(req)
                        continue
                    self.pool.protect(sid)
                    self._slot_sid[slot] = sid
                    row = {key: v for key, v in row_cache.items()
                           if key not in ("k", "v")}
                    self.cache = set_slot(self.cache, slot, row)
                else:
                    self.cache = set_slot(self.cache, slot, row_cache)
                self._slot_tokens[slot] = list(ids) if ids is not None else None
                if self._spec is not None and self._paged:
                    # bucketed prefill: the cache holds the left-padded
                    # bucket, reconstructible whether or not it was indexed
                    full = ([0] * (bucket - S) + [int(t) for t in req.prompt])
                    self._spec.reset(slot)
                    if tokens == len(full):
                        self._spec.observe(slot, full)
                        self._spec_ok.add(slot)
                self.metrics.tokens_generated += 1
                if (len(req.generated) >= req.sampling.max_new_tokens
                        or tok == req.sampling.eos_token):
                    # stop conditions apply to the admission-sampled token
                    # too: a max_new_tokens=1 (or instant-eos) request must
                    # not decode a second token
                    self.slots[slot] = req
                    self._active_mask[slot] = True
                    self._finish_slot(slot, time.monotonic())
                    if self.kv_registry is not None:
                        self.kv_registry.touch(req.session_id,
                                               self.instance_id,
                                               len(req.prompt), now)
                    continue
            self.slots[slot] = req
            self._active_mask[slot] = True
            if self.kv_registry is not None:
                self.kv_registry.touch(req.session_id, self.instance_id,
                                       len(req.prompt), now)

    # ------------------------------------------------------------ migration
    def warm_session(self, session_id: str, prompt_tokens: List[int]) -> int:
        """Prefill ``prompt_tokens`` straight into the session cache pool.

        This is the migration-in half of transcript replay (§4.3.1 applied
        to K,V state): the pool replays a session's transcript onto this
        replica so the *next* call in the session is a warm continuation —
        no batch slot is occupied and nothing is generated.  Returns the
        number of tokens now cached for the session (0 if nothing to do).

        The prefill cost is real and shows up in ``metrics.prefill_tokens``
        — that is the honest price of a migration, paid once, instead of on
        every follow-up call (which is what cold re-routing would cost).
        """
        if not session_id or not prompt_tokens:
            return 0
        vocab = self.cfg.vocab_size
        toks = [int(t) % vocab for t in prompt_tokens]
        toks = toks[-(self.max_seq - 1):]       # respect the context budget
        req = Request.make(toks, session_id=session_id)
        now = time.monotonic()
        W = self.cfg.sliding_window
        bucket = min(bucket_len(len(toks)), self.max_seq)
        with self._lock:
            if self._prefix_share_ok:
                # resident-prefix fast path: pages covering a prefix of the
                # transcript (this session's own, or another session's via
                # the index) make the replay partial or entirely redundant
                warmed = self._warm_from_resident(session_id, toks, now)
                if warmed:
                    return warmed
            if isinstance(self.pool, PagedKVPool) and (not W or bucket <= W):
                # right-aligned prefill: under causal attention the trailing
                # pads never touch the first len(toks) positions, so the
                # stored prefix is exact — no pad K/V enters the session
                # cache (the legacy left-pad exposure)
                _logits, row_cache = self._prefill(req, align="right")
                tokens = len(toks)
                ids = toks if self._prefix_share_ok else None
            else:
                _logits, row_cache = self._prefill(req)
                tokens = int(np.asarray(row_cache["pos"]).reshape(-1)[0])
                ids = None
            if isinstance(self.pool, PagedKVPool):
                if tokens > self.max_seq:
                    return 0
                k = row_cache["k"][:, 0, :tokens]
                v = row_cache["v"][:, 0, :tokens]
                if not self.pool.write_session(session_id, k, v, tokens, now,
                                               token_ids=ids):
                    return 0
            else:
                self.pool.store(session_id, row_cache, tokens)
            if self.kv_registry is not None:
                self.kv_registry.touch(session_id, self.instance_id,
                                       tokens, now)
        return tokens

    def _warm_from_resident(self, session_id: str, toks: List[int],
                            now: float) -> int:
        """Warm a session from pages already resident in the pool.

        Full coverage (the session's own pages after a page-ship import, or
        a shared prefix acquired from the index) costs *zero* prefill
        steps; partial coverage prefills only the missing suffix through
        batch-1 decode (``_extend_session``).  Returns tokens cached, or 0
        to make the caller fall back to the full transcript replay."""
        pool = self.pool
        sp = pool.session(session_id)
        resident = 0
        if sp is not None and sp.pages:
            if len(sp.token_ids) != sp.tokens:
                return 0    # opaque contents: cannot trust the prefix
            n = min(sp.tokens, len(toks))
            if sp.token_ids[:n] != toks[:n]:
                return 0    # diverged: full replay reconciles via COW
            if sp.tokens >= len(toks):
                if self.kv_registry is not None:
                    self.kv_registry.touch(session_id, self.instance_id,
                                           sp.tokens, now)
                return sp.tokens
            resident = sp.tokens
        else:
            resident = pool.acquire_prefix(session_id, toks, now=now)
            if resident >= len(toks):
                if self.kv_registry is not None:
                    self.kv_registry.touch(session_id, self.instance_id,
                                           resident, now)
                return resident
        if resident <= 0:
            return 0
        tokens = self._extend_session(session_id, toks, resident, now)
        if tokens and self.kv_registry is not None:
            self.kv_registry.touch(session_id, self.instance_id, tokens, now)
        return tokens

    def _extend_session(self, session_id: str, toks: List[int],
                        resident: int, now: float) -> int:
        """Suffix-only warm: feed ``toks[resident:]`` through batch-1
        decode on top of the session's resident cache and write the
        extended cache back.  The honest migration/warm cost becomes the
        novel suffix, not the whole transcript."""
        suffix = toks[resident:]
        C = self._slot_C
        if resident + len(suffix) > min(C, self.max_seq):
            return 0
        got = self.pool.gather_contiguous(session_id, self.max_seq)
        if got is None:
            return 0
        k, v, cached = got
        if cached != resident:
            return 0
        pad = C - k.shape[1]
        if pad < 0:
            return 0
        row: Dict[str, Any] = {
            "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None],
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, None],
            "pos": jnp.asarray([resident], jnp.int32),
        }
        for key in self.cache:
            if key not in row:
                ax = _cache_slot_axis(key)
                shp = list(self.cache[key].shape)
                shp[ax] = 1
                row[key] = jnp.zeros(shp, self.cache[key].dtype)
        if self.model.decode_chunk is not None:
            if self._extend_chunk is None:
                self._extend_chunk = jax.jit(self.model.decode_chunk)
            T = max(1, self.prefill_chunk or 8)
            i = 0
            while i < len(suffix):
                n = min(T, len(suffix) - i)
                tk = np.zeros((1, T), np.int32)
                tk[0, :n] = suffix[i:i + n]
                _logits, row = self._extend_chunk(
                    self.params, jnp.asarray(tk),
                    jnp.asarray([n], jnp.int32), row)
                i += n
        else:
            if self._extend_step is None:
                self._extend_step = jax.jit(self.model.decode_step)
            for t in suffix:
                _logits, row = self._extend_step(
                    self.params, jnp.asarray([t], jnp.int32), row)
        tokens = resident + len(suffix)
        kk = row["k"][:, 0, :tokens]
        vv = row["v"][:, 0, :tokens]
        if not self.pool.write_session(session_id, kk, vv, tokens, now,
                                       token_ids=toks):
            return 0
        self.metrics.prefills += 1
        self.metrics.prefill_tokens += len(suffix)
        return tokens

    # ----------------------------------------------------------------- step
    @partial(annotate_function, name="nalar.engine.step")
    def step(self) -> int:
        """Admit + one piggybacked batched step.

        Every decoding slot advances one token; prefilling slots consume up
        to ``prefill_chunk`` prompt tokens via masked sub-steps against the
        same compiled decode fn.  Returns #active sequences.
        """
        if self.chaos is not None:
            self.chaos.before_step(self)
        with self._lock:
            self._admit()
            now = time.monotonic()
            expired = [i for i in range(self.max_batch)
                       if self._active_mask[i] and self.slots[i] is not None
                       and 0 <= self.slots[i].deadline_wall <= now]
            for i in expired:
                # mid-decode preemption: the deadline passed, so further
                # tokens are worthless.  _finish_slot vacates through the
                # normal COW-safe path (unprotect + page release), the slot
                # is free for the next admission this very step.
                self.slots[i].expired = True
                self._finish_slot(i, now)
            if expired:
                self._admit()
            active = [i for i in range(self.max_batch) if self._active_mask[i]]
            if not active:
                self.metrics.queued = len(self.queue)
                self.metrics.active = 0
                return 0
            pending = self._pending_prompt
            prefilling = [i for i in active if pending.get(i)]
            for i in prefilling:
                # every plane feeds each such slot at least one prompt token
                self.slots[i].prefill_steps += 1
            budget = max(1, self.prefill_chunk) if prefilling else 1
            if self._paged:
                sampled = self._step_paged(active, budget)
            elif self._decode_chunk is not None:
                sampled = self._step_fused(active, budget)
            else:
                sampled = self._step_masked(active, budget)
            with TraceAnnotation("nalar.engine.emit"):
                pos_arr = np.asarray(self.cache["pos"])
                now = time.monotonic()
                for i in active:
                    req = self.slots[i]
                    if req is None:
                        continue
                    done = False
                    if i in sampled:
                        tok = req.generated[-1]
                        done = (len(req.generated)
                                >= req.sampling.max_new_tokens
                                or tok == req.sampling.eos_token)
                    if pos_arr[i] >= self.max_seq - 1:
                        done = True
                    if done:
                        self._finish_slot(i, now)
                    else:
                        self._emit_stream(req)
            self.metrics.queued = len(self.queue)
            self.metrics.active = int(self._active_mask.sum())
            return len(active)

    def _emit_stream(self, req: Request) -> None:
        """Buffer the tokens this request's slot appended since the last
        emission as one stream chunk (caller holds the step lock; delivery
        happens off it, in ``drain_completions``).  Only requests with a
        registered chunk callback buffer anything, so sync callers and
        abandoned requests cost nothing."""
        n = len(req.generated)
        if n <= req.streamed:
            return
        chunk = [int(t) for t in req.generated[req.streamed:]]
        req.streamed = n
        with self._done_lock:
            if req.request_id in self._stream_cbs:
                self._stream_pending.append((req, chunk))

    def _step_paged(self, active: List[int], budget: int) -> set:
        """One paged-native fused step.

        Identical batching policy to ``_step_fused`` (chunk width sized to
        need, rounded to a power of two), but the K/V never touches a
        per-slot dense cache: ``begin_append`` reserves (and COW-privatizes)
        each advancing session's pages, the jitted step scatters new K/V
        into them by page table and returns only the next-token rows, and
        ``commit_append`` publishes the new tokens (re-keying the prefix
        index).  A slot whose reservation fails is aborted explicitly —
        counted, finished with what it has — never silently wedged."""
        pending = self._pending_prompt
        with TraceAnnotation("nalar.engine.prepare"):
            pos_before = np.asarray(self.cache["pos"])
            spec_plan = self._plan_speculation(active, pos_before)
            toks, valid = self._feed(active, budget, spec_plan)
        now = time.monotonic()
        aborted: List[int] = []
        with TraceAnnotation("nalar.kv.append"):
            for i in active:
                if not valid[i]:
                    continue
                if not self.pool.begin_append(self._slot_sid[i],
                                              int(valid[i]), now):
                    self.metrics.paged_append_failures += 1
                    valid[i] = 0
                    aborted.append(i)
            if self._prefix_share_ok:
                for i in active:
                    ids = self._slot_tokens.get(i)
                    if ids is not None and valid[i] and i not in spec_plan:
                        ids.extend(int(t) for t in toks[i, :valid[i]])
            pt = np.full((self.max_batch, self._max_pages), -1, np.int32)
            for i in active:
                if valid[i]:
                    pt[i] = self.pool.page_table(self._slot_sid[i],
                                                 self._max_pages)
        step = self._paged_step_all or self._paged_step
        with TraceAnnotation("nalar.engine.dispatch"):
            args = (self.params, jnp.asarray(toks), jnp.asarray(valid),
                    self.cache)

            def run(kp, vp):
                out, greedy, cache, kp, vp = step(*args, kp, vp,
                                                  jnp.asarray(pt))
                return (out, greedy, cache), kp, vp

            out, greedy, self.cache = self.pool.update_pages(run)
        self.metrics.decode_steps += 1
        with TraceAnnotation("nalar.kv.commit"):
            # host bookkeeping while the device runs the step
            for i in active:
                if valid[i] and i not in spec_plan:
                    n = int(valid[i])
                    ids = (toks[i, :n].tolist()
                           if self._slot_tokens.get(i) is not None else None)
                    self.pool.commit_append(self._slot_sid[i], n,
                                            token_ids=ids, now=now)
            for i in aborted:
                self._finish_slot(i, now)
        with TraceAnnotation("nalar.engine.device_wait"):
            greedy_host = np.asarray(greedy)
        if self._paged_step_all is not None:
            logits, greedy_np_all = out, greedy_host                # [B,T]
            greedy_np = greedy_host[np.arange(self.max_batch),
                                    np.maximum(valid - 1, 0)]       # [B]
            rows = None                                             # lazy
        else:
            rows, logits, greedy_np_all = out, None, None
            greedy_np = greedy_host
        ready = [i for i in active if valid[i] and i not in pending]
        sampled: set = set()
        with TraceAnnotation("nalar.engine.sample"):
            for i in ready:
                req = self.slots[i]
                if i in spec_plan:
                    self._verify_slot(req, i, spec_plan[i], int(toks[i, 0]),
                                      int(pos_before[i]), logits,
                                      greedy_np_all, now)
                    sampled.add(i)
                    continue
                if rows is None and logits is not None:
                    rows = jnp.take_along_axis(
                        logits, jnp.asarray(np.maximum(valid - 1, 0))
                        [:, None, None], axis=1)[:, 0]              # [B,V]
                tok = self._sample_slot(req, rows, i, greedy_np)
                req.generated.append(tok)
                if req.first_token_at < 0:
                    req.first_token_at = time.monotonic()
                self.metrics.tokens_generated += 1
                sampled.add(i)
        return sampled

    def _plan_speculation(self, active: List[int],
                          pos_before: np.ndarray) -> Dict[int, List[int]]:
        """Decode-only slots with a shadowing draft stream propose
        ``spec_k`` tokens each (batched across slots in the draft)."""
        if self._spec is None:
            return {}
        want: Dict[int, int] = {}
        for i in active:
            if self._pending_prompt.get(i) or i not in self._spec_ok:
                continue
            req = self.slots[i]
            if req is None or not req.generated:
                continue
            k_i = self._spec_budget(i, req, int(pos_before[i]))
            if k_i > 0:
                self._spec.observe(i, [int(req.generated[-1])])
                want[i] = k_i
        return self._spec.propose(want) if want else {}

    def _feed(self, active: List[int], budget: int,
              spec_plan: Dict[int, List[int]]):
        """The step's tokens [B,T] and valid counts [B]: a prompt chunk for
        each prefilling slot (consumed from its pending prompt), the last
        token plus any drafts for each decoding slot.  The width is sized
        to need, rounded to a power of two."""
        pending = self._pending_prompt
        need = 1
        for i in active:
            q = pending.get(i)
            if q:
                need = max(need, min(len(q), budget))
            elif i in spec_plan:
                need = max(need, 1 + len(spec_plan[i]))
        cap = budget
        if spec_plan:
            cap = max(cap, max(1 + len(d) for d in spec_plan.values()))
        T = min(1 << (need - 1).bit_length(), cap)
        toks = np.zeros((self.max_batch, T), np.int32)
        valid = np.zeros((self.max_batch,), np.int32)
        for i in active:
            q = pending.get(i)
            if q:
                n = min(len(q), T)
                toks[i, :n] = q[:n]
                del q[:n]
                valid[i] = n
                if not q:
                    pending.pop(i, None)
                if self._spec is not None and i in self._spec_ok:
                    # mirror the consumed chunk into the draft stream
                    self._spec.observe(i, toks[i, :n].tolist())
            else:
                req = self.slots[i]
                seq = [int(req.generated[-1]) if req.generated else 0]
                seq += spec_plan.get(i, [])
                toks[i, :len(seq)] = seq
                valid[i] = len(seq)
        return toks, valid

    def _spec_budget(self, slot: int, req: Request, pos: int) -> int:
        """Draft tokens worth proposing for this slot this round (0 = run a
        plain decode step): bounded by the configured ``spec_k``, by the
        request's remaining new-token budget (a round emits at most k+1),
        by the slot's remaining positions, and by the adaptive per-session
        off-switch."""
        sid = self._slot_sid.get(slot)
        if sid is None or sid in self._spec_off:
            return 0
        remaining_new = req.sampling.max_new_tokens - len(req.generated)
        n_max = self.max_seq - 1 - pos       # emission budget to the cap
        return max(0, min(self.spec_k, remaining_new - 1, n_max - 1))

    def _verify_slot(self, req: Request, slot: int, drafts: List[int],
                     t_prev: int, pos0: int, logits, greedy_all: np.ndarray,
                     now: float) -> None:
        """Rejection-sample one verified draft chunk for ``slot``.

        The jitted step already scattered K/V for all ``k+1`` fed positions
        into the slot's reserved pages; this decides how many survive.
        Greedy accepts the longest prefix where the in-jit argmax equals
        the draft (bitwise the non-speculative sequence, because chunked ==
        sequential is pinned); stochastic runs the accept/resample rule on
        the per-position logits with the request's seeded stream.  Commits
        exactly the consumed positions, rolls the rejected tail's reserved
        pages back, rewinds the slot position, and truncates the draft's
        stream to the surviving prefix."""
        k = len(drafts)
        sp = req.sampling
        if sp.temperature <= 0.0:
            g = greedy_all[slot]
            m = 0
            while m < k and int(g[m]) == drafts[m]:
                m += 1
            candidates = drafts[:m] + [int(g[m])]
        else:
            key = self._req_rng.get(req.request_id)
            if key is None:
                key = self._request_key(req)
            key, sub = jax.random.split(key)
            self._req_rng[req.request_id] = key
            rows_np = np.asarray(logits[slot, :k + 1], dtype=np.float32)
            candidates, m = speculative_verify(rows_np, drafts, sp, sub)
        # trim emissions at the request's stop conditions (a mid-chunk eos
        # or budget hit ends the round early, exactly like the one-token
        # path would have)
        emitted: List[int] = []
        for t in candidates:
            emitted.append(int(t))
            if (len(req.generated) + len(emitted) >= sp.max_new_tokens
                    or t == sp.eos_token
                    or pos0 + len(emitted) >= self.max_seq - 1):
                break
        r = len(emitted)
        sid = self._slot_sid[slot]
        consumed_ids = [t_prev] + [int(t) for t in emitted[:r - 1]]
        ids = self._slot_tokens.get(slot)
        if ids is not None:
            ids.extend(consumed_ids)
        self.pool.commit_append(
            sid, r, token_ids=(consumed_ids if ids is not None else None),
            now=now)
        self.pool.truncate_reserved(sid)
        # the jit advanced pos by the full k+1 feed; only r positions exist
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos0 + r)
        self._spec.rollback(slot, pos0 + r)
        for t in emitted:
            req.generated.append(int(t))
            self.metrics.tokens_generated += 1
        if req.first_token_at < 0:
            req.first_token_at = time.monotonic()
        self.metrics.spec_rounds += 1
        self.metrics.spec_proposed += k
        self.metrics.spec_accepted += m
        led = self._spec_ledger.setdefault(sid, [0, 0])
        led[0] += k
        led[1] += m
        if (led[0] >= self.spec_warmup
                and led[1] < self.spec_min_accept * led[0]):
            # observed acceptance makes speculation a loss for this
            # session: every future round decodes plain
            self._spec_off.add(sid)
        if len(self._spec_ledger) > 8192:
            self._spec_ledger.clear()
            self._spec_off.clear()

    def _step_fused(self, active: List[int], budget: int) -> set:
        """One fused chunk forward: prefilling slots consume up to
        ``budget`` prompt tokens, decoding slots advance one, idle slots
        none.  The chunk width is sized to the actual need and rounded up
        to a power of two, so a short prompt never pays a full-width chunk
        step and the compiled-shape set stays logarithmic.  Returns the
        slots that produced a token."""
        pending = self._pending_prompt
        need = 1
        for i in active:
            q = pending.get(i)
            if q:
                need = max(need, min(len(q), budget))
        # next power of two, clipped to the chunk budget (need <= budget,
        # so T >= need always holds and the chunk is consumed in full)
        T = min(1 << (need - 1).bit_length(), budget)
        toks = np.zeros((self.max_batch, T), np.int32)
        valid = np.zeros((self.max_batch,), np.int32)
        for i in active:
            q = pending.get(i)
            if q:
                n = min(len(q), T)
                toks[i, :n] = q[:n]
                del q[:n]
                valid[i] = n
                if not q:
                    pending.pop(i, None)
            else:
                req = self.slots[i]
                toks[i, 0] = req.generated[-1] if req.generated else 0
                valid[i] = 1
        if self._prefix_share_ok:
            for i in active:
                ids = self._slot_tokens.get(i)
                if ids is not None and valid[i]:
                    ids.extend(int(t) for t in toks[i, :valid[i]])
        logits, self.cache = self._decode_chunk(
            self.params, jnp.asarray(toks), jnp.asarray(valid), self.cache)
        self.metrics.decode_steps += 1
        ready = [i for i in active if valid[i] and i not in pending]
        if not ready:
            return set()
        # next-token distribution sits at each slot's last valid row
        rows = jnp.take_along_axis(
            logits, jnp.asarray(np.maximum(valid - 1, 0))[:, None, None],
            axis=1)[:, 0]                                        # [B,V]
        greedy = np.asarray(jnp.argmax(rows, axis=-1))
        sampled: set = set()
        for i in ready:
            req = self.slots[i]
            tok = self._sample_slot(req, rows, i, greedy)
            req.generated.append(tok)
            if req.first_token_at < 0:
                # stamp after the sampled token exists (consistent between
                # prefill and prefix-reuse paths)
                req.first_token_at = time.monotonic()
            self.metrics.tokens_generated += 1
            sampled.add(i)
        return sampled

    def _step_masked(self, active: List[int], budget: int) -> set:
        """Per-token fallback for families without a fused chunk step:
        up to ``budget`` masked sub-steps over the shared decode fn, where
        only prompt-consuming slots advance after the first."""
        pending = self._pending_prompt
        sampled: set = set()
        for j in range(budget):
            toks = np.zeros((self.max_batch,), np.int32)
            mask = np.zeros((self.max_batch,), bool)
            for i in active:
                q = pending.get(i)
                if q:
                    toks[i] = q.pop(0)
                    mask[i] = True
                    if not q:
                        pending.pop(i, None)
                elif j == 0 and i not in sampled:
                    req = self.slots[i]
                    toks[i] = req.generated[-1] if req.generated else 0
                    mask[i] = True
            if not mask.any():
                break
            if self._prefix_share_ok:
                for i in active:
                    if mask[i]:
                        ids = self._slot_tokens.get(i)
                        if ids is not None:
                            ids.append(int(toks[i]))
            logits, self.cache = self._masked_decode(
                self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(mask))
            self.metrics.decode_steps += 1
            ready = [i for i in active
                     if mask[i] and i not in pending and i not in sampled]
            if not ready:
                continue
            greedy = np.asarray(jnp.argmax(logits, axis=-1))
            for i in ready:
                req = self.slots[i]
                tok = self._sample_slot(req, logits, i, greedy)
                req.generated.append(tok)
                if req.first_token_at < 0:
                    req.first_token_at = time.monotonic()
                self.metrics.tokens_generated += 1
                sampled.add(i)
        return sampled

    def _vacate_slot(self, slot: int) -> None:
        """Free a batch slot and every per-slot residue (pending prompt,
        request PRNG stream) so a recycled slot can never inherit a previous
        request's unconsumed prompt tokens."""
        req = self.slots[slot]
        self.slots[slot] = None
        self._active_mask[slot] = False
        self._pending_prompt.pop(slot, None)
        self._slot_tokens.pop(slot, None)
        if self._spec is not None:
            self._spec.reset(slot)
            self._spec_ok.discard(slot)
        sid = self._slot_sid.pop(slot, None)
        if sid is not None:
            self.pool.unprotect(sid)
            if req is None or not req.session_id:
                # anonymous paged session: no follow-up can resume it
                self.pool.release(sid)
        if req is not None:
            self._req_rng.pop(req.request_id, None)

    def _finish_slot(self, slot: int, now: float) -> None:
        req = self.slots[slot]
        req.finished = True
        req.finished_at = now
        if not req.expired:
            # flush the final tokens as a last chunk BEFORE delivery, so a
            # consumer's chunk stream concatenates to exactly the completion
            # value.  Expired requests emit nothing further: their partial
            # generation is worthless past the deadline.
            self._emit_stream(req)
        if req.expired:
            # deadline preemption: the partial generation is worthless and
            # its tokens never reach the transcript, so don't leave a warm
            # session cache behind (a later session-affine resume would
            # continue from divergent history) — reclaim slot and pages
            self.metrics.expired += 1
            self._vacate_slot(slot)
            if req.session_id:
                self.pool.release(req.session_id)
            with self._done_lock:
                self._finished.append(req)
                if len(self._finished) > self.finished_cap:
                    self._trim_finished()
            return
        self.metrics.completed += 1
        # persist session cache for prefix reuse on follow-ups
        if self._paged:
            # nothing to persist: the pool pages ARE the session cache,
            # already current through commit_append.  Vacate unprotects
            # (and releases anonymous sessions).
            if req.session_id:
                sp = self.pool.session(req.session_id)
                tokens = (sp.tokens if sp is not None
                          else int(np.asarray(self.cache["pos"])[slot]))
                if self.kv_registry is not None:
                    self.kv_registry.touch(req.session_id, self.instance_id,
                                           tokens, now)
        elif req.session_id:
            row = get_slot(self.cache, slot)
            tokens = int(np.asarray(row["pos"])[0])
            if isinstance(self.pool, PagedKVPool):
                k = row["k"][:, 0, :tokens]
                v = row["v"][:, 0, :tokens]
                ids = self._slot_tokens.get(slot)
                if ids is not None and len(ids) != tokens:
                    ids = None      # provenance lost: keep the write opaque
                if tokens <= self.max_seq:
                    self.pool.write_session(req.session_id, k, v, tokens, now,
                                            token_ids=ids)
            else:
                self.pool.store(req.session_id,
                                jax.tree_util.tree_map(lambda x: x, row),
                                tokens)
            if self.kv_registry is not None:
                self.kv_registry.touch(req.session_id, self.instance_id,
                                       tokens, now)
        self._vacate_slot(slot)
        with self._done_lock:
            self._finished.append(req)
            if len(self._finished) > self.finished_cap:
                self._trim_finished()

    def _trim_finished(self) -> None:
        """Bound the finished list without losing async completions.

        Sync callers never drain, so the list must stay bounded — but a
        request with a registered callback still owes its caller a
        completion: evicting it would strand a NALAR future forever.
        Fire-or-keep: evict oldest callback-less requests first; callback-
        bearing requests survive until ``drain_completions``.  Only under a
        pathological flood (callbacks registered but never drained) does
        the hard cap evict them too, dropping the orphaned callback entry
        with the request so the callback table cannot leak.

        Caller holds ``_done_lock``.
        """
        cut = len(self._finished) - self.finished_cap // 2
        kept: List[Request] = []
        for idx, r in enumerate(self._finished):
            if idx < cut and r.request_id not in self._callbacks:
                continue
            kept.append(r)
        overflow = len(kept) - 2 * self.finished_cap
        if overflow > 0:
            for r in kept[:overflow]:
                self._callbacks.pop(r.request_id, None)
            kept = kept[overflow:]
        self._finished = kept

    def cancel_request(self, request_id: str) -> bool:
        """Abandon one request (hedge loser / caller gone): remove it from
        the wait queue, or vacate its batch slot mid-decode — the slot and
        its protected KV pages are reclaimed through the normal vacate
        path.  The request is NOT delivered to ``_finished`` and its
        completion callback is dropped: the caller already resolved the
        future elsewhere.  Returns True if the request was found."""
        with self._lock:
            with self._done_lock:
                self._callbacks.pop(request_id, None)
                self._stream_cbs.pop(request_id, None)
                self._stream_pending = [
                    (r, c) for r, c in self._stream_pending
                    if r.request_id != request_id]
            req = self.queue.remove(request_id)
            if req is not None:
                self.metrics.queued = len(self.queue)
                return True
            for slot in range(self.max_batch):
                r = self.slots[slot]
                if r is not None and r.request_id == request_id:
                    self._vacate_slot(slot)
                    if r.session_id:
                        # the abandoned decode already extended this
                        # session's cache with tokens that will never reach
                        # the transcript (the winner's did) — a later
                        # session-affine resume here would continue from
                        # divergent history, so drop the cache outright
                        self.pool.release(r.session_id)
                    self.metrics.active = int(self._active_mask.sum())
                    return True
        return False

    def abort_all(self) -> int:
        """Clear the wait queue and vacate every slot (replica death /
        bridge ``fail_inflight``): results will never be delivered, and a
        recycled slot must not inherit a dead request's pending prompt.
        Returns the number of requests dropped."""
        with self._lock:
            n = self.queue.clear()
            for slot in range(self.max_batch):
                if self.slots[slot] is not None:
                    n += 1
                    self._vacate_slot(slot)
            self._pending_prompt.clear()
            self._slot_tokens.clear()
            with self._done_lock:
                self._callbacks.clear()
                self._stream_cbs.clear()
                self._stream_pending.clear()
            self.metrics.queued = 0
            self.metrics.active = 0
            return n

    # ------------------------------------------------------------ telemetry
    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and len(self.queue) == 0:
                return

    def slot_sessions(self) -> Dict[int, str]:
        """Session tag of every occupied batch slot (cache-slot ownership)."""
        with self._lock:
            return {i: r.session_id for i, r in enumerate(self.slots)
                    if r is not None}

    def telemetry(self) -> Dict[str, Any]:
        m = self.metrics
        return {"queued": len(self.queue), "active": m.active,
                "completed": m.completed, "decode_steps": m.decode_steps,
                "prefills": m.prefills, "prefill_tokens": m.prefill_tokens,
                "prefix_hits": m.prefix_hits,
                "shared_prefix_hits": m.shared_prefix_hits,
                "shared_prefix_tokens": m.shared_prefix_tokens,
                "prefix_sharing": (dict(self.pool.stats)
                                   if isinstance(self.pool, PagedKVPool)
                                   else {}),
                "tokens_generated": m.tokens_generated,
                "queue_limit": self.max_queue,
                "queue_saturation": self.saturation(),
                "admission_rejects": self.queue.rejected,
                "expired": m.expired,
                "expired_rejects": self.queue.expired_rejects,
                "prefill_chunk": self.prefill_chunk,
                "paged_decode": self._paged,
                "paged_kernel": self._paged and self._paged_kernel,
                "tier": self.tier,
                "speculative": self._spec is not None,
                "spec_rounds": m.spec_rounds,
                "spec_proposed": m.spec_proposed,
                "spec_accepted": m.spec_accepted,
                "spec_acceptance": m.spec_acceptance,
                "decode_tokens_per_step": m.decode_tokens_per_step,
                "resume_overflows": m.resume_overflows,
                "resume_unsupported": m.resume_unsupported,
                "paged_append_failures": m.paged_append_failures,
                "slot_sessions": self.slot_sessions()}
