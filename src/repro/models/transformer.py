"""Dense decoder-only transformer (qwen3 / stablelm-2 / starcoder2 families).

Layer-stacked parameters + ``jax.lax.scan`` keep the HLO size independent of
depth (94-layer configs compile in seconds).  Also the backbone for the VLM
config (phi-3-vision consumes precomputed patch embeddings).
"""

from __future__ import annotations

import functools

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import moe as M


def init_layer(rng, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "mlp_norm": L.init_norm(cfg),
        "mlp": (M.init_moe_layer(k2, cfg) if cfg.n_experts > 0
                else L.init_mlp(k2, cfg)),
    }


def _ffn(x: jnp.ndarray, layer_p: dict, cfg: ModelConfig,
         moe_impl: str = "einsum"):
    """FFN sub-block: dense MLP or MoE.  Returns (y, aux_loss)."""
    if cfg.n_experts > 0:
        y, aux, _counts = M.moe_block(x, layer_p["mlp"], cfg, impl=moe_impl)
        return y, aux
    return L.mlp_block(x, layer_p["mlp"], cfg), jnp.zeros((), jnp.float32)


def init_params(rng, cfg: ModelConfig) -> dict:
    ke, kl = jax.random.split(rng)
    layer_rngs = jax.random.split(kl, cfg.n_layers)
    return {
        "embed": L.init_embedding(ke, cfg),
        "layers": jax.vmap(lambda r: init_layer(r, cfg))(layer_rngs),
        "final_norm": L.init_norm(cfg),
    }


def _block(x: jnp.ndarray, p: dict, *, cfg: ModelConfig,
           positions: jnp.ndarray, attention_impl: str,
           moe_impl: str = "einsum"):
    h = L.apply_norm(x, p["attn_norm"], cfg)
    x = x + L.attention_block(h, p["attn"], cfg, positions,
                              window=cfg.sliding_window,
                              attention_impl=attention_impl)
    h = L.apply_norm(x, p["mlp_norm"], cfg)
    y, aux = _ffn(h, p, cfg, moe_impl)
    return x + y, aux


def forward(params: dict, cfg: ModelConfig, tokens: Optional[jnp.ndarray],
            inputs_embeds: Optional[jnp.ndarray] = None,
            attention_impl: str = "xla", moe_impl: str = "einsum",
            return_aux: bool = False, remat: bool = False,
            unembed: bool = True):
    """Training/prefill forward.  Returns logits [B, S, V] (+ MoE aux loss);
    ``unembed=False`` returns the final hidden states instead (the chunked
    cross-entropy path never materializes full logits).

    ``remat=True`` checkpoints each layer (recompute-in-backward): live
    activations drop from O(L x per-layer internals) to O(L x boundaries) +
    one layer's internals — required for the production train shapes to fit
    HBM (EXPERIMENTS.md §Perf iteration 5)."""
    x = inputs_embeds if inputs_embeds is not None else L.embed(tokens, params["embed"])
    x = x.astype(cfg.jnp_dtype)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]

    blk = functools.partial(_block, cfg=cfg, positions=positions,
                            attention_impl=attention_impl, moe_impl=moe_impl)
    if remat:
        blk = jax.checkpoint(blk)

    def step(carry, layer_p):
        x, aux = carry
        x, a = blk(x, layer_p)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = L.apply_norm(x, params["final_norm"], cfg)
    out = x if not unembed else L.unembed(x, params["embed"], cfg)
    if return_aux:
        return out, aux / max(cfg.n_layers, 1)
    return out


# --------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    C = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    shape = (cfg.n_layers, batch, C, cfg.n_kv_heads, cfg.head_dim_)
    return {
        "k": jnp.zeros(shape, cfg.jnp_dtype),
        "v": jnp.zeros(shape, cfg.jnp_dtype),
        "pos": jnp.zeros((batch,), jnp.int32),   # per-seq next position
    }


def prefill(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            inputs_embeds: Optional[jnp.ndarray] = None,
            attention_impl: str = "xla", moe_impl: str = "einsum",
            pad_cache_to: Optional[int] = None) -> Tuple[jnp.ndarray, dict]:
    """Process the full prompt; returns (last-token logits [B,V], cache).

    ``pad_cache_to`` adds decode headroom to the returned KV cache (the
    prefill cache is otherwise exactly prompt-sized and the first decoded
    token would overwrite the last prompt slot)."""
    x = inputs_embeds if inputs_embeds is not None else L.embed(tokens, params["embed"])
    x = x.astype(cfg.jnp_dtype)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)[None, :]
    W = cfg.sliding_window
    C = min(S, W) if W else S

    def step(carry, layer_p):
        x = carry
        h = L.apply_norm(x, layer_p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, layer_p["attn"], cfg, positions)
        n_rep = cfg.n_heads // cfg.n_kv_heads
        scale = cfg.head_dim_ ** -0.5
        o = L.full_attention(q, L.repeat_kv(k, n_rep), L.repeat_kv(v, n_rep),
                             causal=True, window=W, scale=scale,
                             impl=attention_impl)
        x = x + jnp.einsum("bshk,hkd->bsd", o, layer_p["attn"]["wo"])
        h = L.apply_norm(x, layer_p["mlp_norm"], cfg)
        y, _aux = _ffn(h, layer_p, cfg, moe_impl)
        x = x + y
        # keep the last C positions, ring-aligned so that slot = pos % C
        kc, vc = k[:, -C:], v[:, -C:]
        if W:
            shift = S % C
            kc = jnp.roll(kc, shift, axis=1)
            vc = jnp.roll(vc, shift, axis=1)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(step, x, params["layers"])
    x = L.apply_norm(x[:, -1:], params["final_norm"], cfg)
    logits = L.unembed(x[:, 0], params["embed"], cfg)
    ks, vs = L.pad_cache_seq(ks, vs, C, W, pad_cache_to)
    cache = {"k": ks, "v": vs, "pos": jnp.full((B,), S, jnp.int32)}
    return logits, cache


def decode_step(params: dict, cfg: ModelConfig, token: jnp.ndarray,
                cache: dict, attention_impl: str = "xla",
                moe_impl: str = "einsum") -> Tuple[jnp.ndarray, dict]:
    """One token ([B] int32) against the KV cache.  Returns (logits, cache)."""
    B = token.shape[0]
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(token[:, None], params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None]
    W = cfg.sliding_window

    def step(carry, xs):
        x = carry
        layer_p, ck, cv = xs
        h = L.apply_norm(x, layer_p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, layer_p["attn"], cfg, positions)
        ck, cv = L.kv_cache_update(ck, cv, k, v, pos, W)
        o = L.decode_attention(q, ck, cv, pos, cfg, window=W,
                               impl=attention_impl)
        x = x + jnp.einsum("bshk,hkd->bsd", o, layer_p["attn"]["wo"])
        h = L.apply_norm(x, layer_p["mlp_norm"], cfg)
        y, _aux = _ffn(h, layer_p, cfg, moe_impl)
        x = x + y
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(step, x, (params["layers"], cache["k"], cache["v"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = L.unembed(x[:, 0], params["embed"], cfg)
    return logits, {"k": ks, "v": vs, "pos": pos + 1}


def decode_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                 valid_len: jnp.ndarray, cache: dict,
                 attention_impl: str = "xla",
                 moe_impl: str = "einsum") -> Tuple[jnp.ndarray, dict]:
    """T tokens ([B,T] int32) against the KV cache in one forward.

    The chunked-prefill primitive: each sequence advances by
    ``valid_len[b] <= T`` positions — a prefilling slot consumes a prompt
    chunk while a decoding slot piggybacked in the same batch advances one
    token (valid_len 1) and an idle slot none (valid_len 0; its cache row
    and position are untouched).  Causal within the chunk, full attention
    over the cached prefix.  Returns (logits [B,T,V], cache); callers read
    row ``valid_len[b]-1`` for the next-token distribution.
    """
    B, T = tokens.shape
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None] + jnp.arange(T)[None, :]          # [B,T]
    valid = jnp.arange(T)[None, :] < valid_len[:, None]        # [B,T]
    W = cfg.sliding_window

    def step(carry, xs):
        x = carry
        layer_p, ck, cv = xs
        h = L.apply_norm(x, layer_p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, layer_p["attn"], cfg, positions)
        if W:
            # ring caches: attend the pre-write cache + the chunk itself
            # (a chunk write can clobber ring slots earlier in-chunk
            # queries still need), then write
            o = L.chunk_decode_attention_windowed(
                q, ck, cv, k, v, pos, valid_len, positions, cfg, window=W)
            ck, cv = L.kv_cache_update_chunk(ck, cv, k, v, pos, valid, W)
        else:
            ck, cv = L.kv_cache_update_chunk(ck, cv, k, v, pos, valid, W)
            o = L.chunk_decode_attention(q, ck, cv, positions, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", o, layer_p["attn"]["wo"])
        h = L.apply_norm(x, layer_p["mlp_norm"], cfg)
        y, _aux = _ffn(h, layer_p, cfg, moe_impl)
        x = x + y
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(step, x, (params["layers"], cache["k"],
                                         cache["v"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = L.unembed(x, params["embed"], cfg)                # [B,T,V]
    return logits, {"k": ks, "v": vs, "pos": pos + valid_len}


def decode_chunk_paged(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                       valid_len: jnp.ndarray, cache: dict,
                       k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                       page_table: jnp.ndarray, *, max_seq: int,
                       attention_impl: str = "xla", moe_impl: str = "einsum",
                       kernel: bool = False):
    """Paged-native :func:`decode_chunk`: the KV pool IS the decode cache.

    New K/V is scattered straight into the pool pages named by
    ``page_table`` [B, max_pages] (-1 padding) — no dense per-slot cache,
    no ``gather_contiguous`` on admission, no write-back on eviction.  The
    caller (``serving.engine``) must guarantee every page about to receive
    a write has refcount 1 (``PagedKVPool.begin_append`` privatizes shared
    pages first) and that distinct batch rows never map a written position
    to the same page, so the scatter is collision-free; rows with
    ``valid_len == 0`` and -1 table entries write nowhere (``mode='drop'``).

    Default path gathers the tables to a dense [B, C, Hkv, Dh] view (C =
    the dense slot-cache length) and reuses the exact
    ``chunk_decode_attention`` / ``_windowed`` math, so greedy outputs and
    cache bytes are bitwise identical to :func:`decode_chunk`.
    ``kernel=True`` instead runs the Pallas paged kernel over the tables
    (no dense materialization; near-identical, not bitwise).

    The stacked pools [L, n_pages, P, Hkv, Dh] ride the layer scan's carry:
    each layer scatters its new tokens' slots into them and attends its
    own slab in place, so with donated pools no whole pool is copied and
    no layer is sliced out or written back.  Windowed
    configs are only supported when ``max_seq <= sliding_window`` (ring
    slot == position, so the linear page layout matches the ring layout);
    the engine falls back to the dense path otherwise.

    Returns (logits [B,T,V], slim cache {"pos"}, k_pages, v_pages).
    """
    B, T = tokens.shape
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None] + jnp.arange(T)[None, :]          # [B,T]
    valid = jnp.arange(T)[None, :] < valid_len[:, None]        # [B,T]
    W = cfg.sliding_window
    C = min(max_seq, W) if W else max_seq

    n_layers, n_pages, P, Hkv, Dh = k_pages.shape
    maxp = page_table.shape[1]
    # position -> (page, offset) routing for the chunk's scatter writes
    pslot = jnp.minimum(positions // P, maxp - 1)              # [B,T]
    page_of = jnp.take_along_axis(page_table, pslot, axis=1)   # [B,T]
    off = positions % P
    oob = (~valid) | (page_of < 0) | (positions >= C)
    widx = jnp.where(oob, n_pages, page_of)                    # drop sentinel
    pt_c = jnp.maximum(page_table, 0)                          # [B,maxp]

    def gather(pages, layer):
        # dense [B, C, Hkv, Dh] view of one layer's tables, read straight
        # from the stacked pool — same length as the dense slot cache, so
        # the attention HLO (and its reduction order) is identical
        return pages[layer, pt_c].reshape(B, maxp * P, Hkv, Dh)[:, :C]

    def write(kp, vp, layer, k, v):
        with jax.named_scope("kv_write"):
            return (kp.at[layer, widx, off].set(k.astype(kp.dtype),
                                                mode="drop"),
                    vp.at[layer, widx, off].set(v.astype(vp.dtype),
                                                mode="drop"))

    # named scopes (attention, attention/kv_write, mlp, unembed) label the
    # step's ops in a device profile
    def step(carry, xs):
        x, kp, vp = carry
        layer_p, layer = xs
        with jax.named_scope("attention"):
            h = L.apply_norm(x, layer_p["attn_norm"], cfg)
            q, k, v = L.attention_qkv(h, layer_p["attn"], cfg, positions)
            if kernel:
                kp, vp = write(kp, vp, layer, k, v)
                o = L.paged_chunk_attention(q, kp, vp, page_table, pos, cfg,
                                            layer=layer)
            elif W:
                # mirror decode_chunk's order exactly: attend the pre-write
                # view + the chunk itself, then write
                o = L.chunk_decode_attention_windowed(
                    q, gather(kp, layer), gather(vp, layer), k, v, pos,
                    valid_len, positions, cfg, window=W)
                kp, vp = write(kp, vp, layer, k, v)
            else:
                kp, vp = write(kp, vp, layer, k, v)
                o = L.chunk_decode_attention(q, gather(kp, layer),
                                             gather(vp, layer), positions,
                                             cfg)
            x = x + jnp.einsum("bshk,hkd->bsd", o, layer_p["attn"]["wo"])
        with jax.named_scope("mlp"):
            h = L.apply_norm(x, layer_p["mlp_norm"], cfg)
            y, _aux = _ffn(h, layer_p, cfg, moe_impl)
            x = x + y
        return (x, kp, vp), None

    (x, ks, vs), _ = jax.lax.scan(
        step, (x, k_pages, v_pages),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    with jax.named_scope("unembed"):
        x = L.apply_norm(x, params["final_norm"], cfg)
        logits = L.unembed(x, params["embed"], cfg)            # [B,T,V]
    return logits, {"pos": pos + valid_len}, ks, vs
