"""Whisper-style encoder-decoder backbone — arXiv:2212.04356.

Per the assignment carve-out, the modality frontend (mel-spectrogram +
2-conv feature extractor) is a STUB: ``input_specs`` supplies precomputed
frame embeddings [B, T_enc, D].  This module implements everything after it:
bidirectional encoder, causal decoder with cross-attention, learned absolute
positions (Whisper uses sinusoidal enc / learned dec; both are stand-ins
here), LayerNorm + GELU.

Decode caches: decoder self-attention KV (grows with generated length) plus
the cross-attention K/V computed once from the encoder output.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L


def _init_xattn(rng, cfg: ModelConfig) -> dict:
    # cross-attention has full heads on both sides (Whisper is MHA)
    return L.init_attention(rng, cfg)


def _init_enc_layer(rng, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "mlp_norm": L.init_norm(cfg),
        "mlp": L.init_mlp(k2, cfg),
    }


def _init_dec_layer(rng, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "attn_norm": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "xattn_norm": L.init_norm(cfg),
        "xattn": _init_xattn(k2, cfg),
        "mlp_norm": L.init_norm(cfg),
        "mlp": L.init_mlp(k3, cfg),
    }


def init_params(rng, cfg: ModelConfig) -> dict:
    ke, kenc, kdec, kp = jax.random.split(rng, 4)
    enc_rngs = jax.random.split(kenc, cfg.n_encoder_layers)
    dec_rngs = jax.random.split(kdec, cfg.n_layers)
    return {
        "embed": L.init_embedding(ke, cfg),
        "enc_pos": (jax.random.normal(kp, (cfg.encoder_seq, cfg.d_model))
                    * 0.02).astype(cfg.jnp_dtype),
        "enc_layers": jax.vmap(lambda r: _init_enc_layer(r, cfg))(enc_rngs),
        "enc_norm": L.init_norm(cfg),
        "dec_layers": jax.vmap(lambda r: _init_dec_layer(r, cfg))(dec_rngs),
        "final_norm": L.init_norm(cfg),
    }


def encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: precomputed conv-frontend embeddings [B, T_enc, D]."""
    x = frames.astype(cfg.jnp_dtype) + params["enc_pos"][None, :frames.shape[1]]
    T = x.shape[1]
    positions = jnp.arange(T)[None, :]

    def step(carry, p):
        h = L.apply_norm(carry, p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
        n_rep = cfg.n_heads // cfg.n_kv_heads
        o = L.sdpa(q, L.repeat_kv(k, n_rep), L.repeat_kv(v, n_rep), None,
                   cfg.head_dim_ ** -0.5)   # bidirectional: no mask
        x = carry + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = L.apply_norm(x, p["mlp_norm"], cfg)
        return x + L.mlp_block(h, p["mlp"], cfg), None

    x, _ = jax.lax.scan(step, x, params["enc_layers"])
    return L.apply_norm(x, params["enc_norm"], cfg)


def _cross_kv(memory: jnp.ndarray, p: dict, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    return k, v


def _cross_attend(x: jnp.ndarray, xk: jnp.ndarray, xv: jnp.ndarray,
                  p: dict, cfg: ModelConfig) -> jnp.ndarray:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    n_rep = cfg.n_heads // cfg.n_kv_heads
    o = L.sdpa(q, L.repeat_kv(xk, n_rep), L.repeat_kv(xv, n_rep), None,
               cfg.head_dim_ ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _dec_block(x, p, cfg, positions, memory_kv, self_mask, impl="xla"):
    xk, xv = memory_kv
    h = L.apply_norm(x, p["attn_norm"], cfg)
    q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    o = L.full_attention(q, L.repeat_kv(k, n_rep), L.repeat_kv(v, n_rep),
                         causal=True, window=None,
                         scale=cfg.head_dim_ ** -0.5, impl=impl)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
    h = L.apply_norm(x, p["xattn_norm"], cfg)
    x = x + _cross_attend(h, xk, xv, p["xattn"], cfg)
    h = L.apply_norm(x, p["mlp_norm"], cfg)
    return x + L.mlp_block(h, p["mlp"], cfg), (k, v)


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            frames: jnp.ndarray, attention_impl: str = "xla",
            remat: bool = False, unembed: bool = True) -> jnp.ndarray:
    """Teacher-forced training forward.  Returns decoder logits [B,S,V]."""
    memory = encode(params, cfg, frames)
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    mask = L.causal_mask(S, S, 0)

    def blk(carry, p):
        kv = _cross_kv(memory, p["xattn"], cfg)
        out, _ = _dec_block(carry, p, cfg, positions, kv, mask,
                            impl=attention_impl)
        return out

    if remat:
        blk = jax.checkpoint(blk)

    def step(carry, p):
        return blk(carry, p), None

    x, _ = jax.lax.scan(step, x, params["dec_layers"])
    x = L.apply_norm(x, params["final_norm"], cfg)
    return L.unembed(x, params["embed"], cfg) if unembed else x


# ------------------------------------------------------------------ serving
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim_
    H = cfg.n_heads
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_seq, Hkv, Dh), cfg.jnp_dtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_seq, Hkv, Dh), cfg.jnp_dtype),
        # cross-attn memory K/V, computed at prefill
        "xk": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, Hkv, Dh),
                        cfg.jnp_dtype),
        "xv": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, Hkv, Dh),
                        cfg.jnp_dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
            frames: jnp.ndarray, attention_impl: str = "xla",
            pad_cache_to=None) -> Tuple[jnp.ndarray, dict]:
    memory = encode(params, cfg, frames)
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)[None, :]
    mask = L.causal_mask(S, S, 0)

    def step(carry, p):
        kv = _cross_kv(memory, p["xattn"], cfg)
        out, (k, v) = _dec_block(carry, p, cfg, positions, kv, mask,
                                 impl=attention_impl)
        return out, (k, v, kv[0], kv[1])

    x, (ks, vs, xks, xvs) = jax.lax.scan(step, x, params["dec_layers"])
    x = L.apply_norm(x[:, -1:], params["final_norm"], cfg)
    logits = L.unembed(x[:, 0], params["embed"], cfg)
    ks, vs = L.pad_cache_seq(ks, vs, S, None, pad_cache_to)
    return logits, {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                    "pos": jnp.full((B,), S, jnp.int32)}


def decode_step(params: dict, cfg: ModelConfig, token: jnp.ndarray,
                cache: dict) -> Tuple[jnp.ndarray, dict]:
    B = token.shape[0]
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(token[:, None], params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None]

    def step(carry, xs):
        p, ck, cv, xk, xv = xs
        x = carry
        h = L.apply_norm(x, p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
        ck, cv = L.kv_cache_update(ck, cv, k, v, pos, None)
        o = L.decode_attention(q, ck, cv, pos, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = L.apply_norm(x, p["xattn_norm"], cfg)
        x = x + _cross_attend(h, xk, xv, p["xattn"], cfg)
        h = L.apply_norm(x, p["mlp_norm"], cfg)
        x = x + L.mlp_block(h, p["mlp"], cfg)
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        step, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["xk"], cache["xv"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = L.unembed(x[:, 0], params["embed"], cfg)
    return logits, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"],
                    "pos": pos + 1}


def encode_cross(params: dict, cfg: ModelConfig,
                 frames: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the encoder and project per-decoder-layer cross K/V.

    Returns (xk, xv) [L, B, T_enc, Hkv, Dh] — bitwise identical to the
    ``xk``/``xv`` leaves :func:`prefill` produces (same ``encode`` + same
    per-layer ``_cross_kv`` einsums), so the chunked admission path can
    populate the slim cache without running a monolithic prefill."""
    memory = encode(params, cfg, frames)

    def step(carry, p):
        k, v = _cross_kv(memory, p["xattn"], cfg)
        return carry, (k, v)

    _, (xks, xvs) = jax.lax.scan(step, 0, params["dec_layers"])
    return xks, xvs


def decode_chunk(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                 valid_len: jnp.ndarray, cache: dict) -> Tuple[jnp.ndarray, dict]:
    """T decoder tokens ([B,T]) against the KV cache in one forward.

    Chunked-prefill for the encoder-decoder: causal self-attention within
    the chunk + full attention over the cached prefix, cross-attending the
    precomputed ``xk``/``xv`` memory every layer.  Mirrors
    ``transformer.decode_chunk`` (non-windowed branch)."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None] + jnp.arange(T)[None, :]          # [B,T]
    valid = jnp.arange(T)[None, :] < valid_len[:, None]        # [B,T]

    def step(carry, xs):
        p, ck, cv, xk, xv = xs
        x = carry
        h = L.apply_norm(x, p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
        ck, cv = L.kv_cache_update_chunk(ck, cv, k, v, pos, valid, None)
        o = L.chunk_decode_attention(q, ck, cv, positions, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = L.apply_norm(x, p["xattn_norm"], cfg)
        x = x + _cross_attend(h, xk, xv, p["xattn"], cfg)
        h = L.apply_norm(x, p["mlp_norm"], cfg)
        x = x + L.mlp_block(h, p["mlp"], cfg)
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        step, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["xk"], cache["xv"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = L.unembed(x, params["embed"], cfg)                # [B,T,V]
    return logits, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"],
                    "pos": pos + valid_len}


def decode_chunk_paged(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                       valid_len: jnp.ndarray, cache: dict,
                       k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                       page_table: jnp.ndarray, *, max_seq: int,
                       kernel: bool = False):
    """Paged-native :func:`decode_chunk`: decoder self-attention KV lives in
    the pool pages; the slim cache carries only {"xk", "xv", "pos"}.  Same
    scatter-routing and bitwise-parity strategy as
    ``transformer.decode_chunk_paged`` (non-windowed).  Returns
    (logits [B,T,V], slim cache, k_pages, v_pages)."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(cache["pos"], (B,))
    x = L.embed(tokens, params["embed"]).astype(cfg.jnp_dtype)
    positions = pos[:, None] + jnp.arange(T)[None, :]          # [B,T]
    valid = jnp.arange(T)[None, :] < valid_len[:, None]        # [B,T]
    C = max_seq

    _nl, n_pages, P, Hkv, Dh = k_pages.shape
    maxp = page_table.shape[1]
    pslot = jnp.minimum(positions // P, maxp - 1)              # [B,T]
    page_of = jnp.take_along_axis(page_table, pslot, axis=1)   # [B,T]
    off = positions % P
    oob = (~valid) | (page_of < 0) | (positions >= C)
    widx = jnp.where(oob, n_pages, page_of)                    # drop sentinel
    pt_c = jnp.maximum(page_table, 0)

    def gather(pages):
        return pages[pt_c].reshape(B, maxp * P, Hkv, Dh)[:, :C]

    def step(carry, xs):
        p, kp, vp, xk, xv = xs
        x = carry
        h = L.apply_norm(x, p["attn_norm"], cfg)
        q, k, v = L.attention_qkv(h, p["attn"], cfg, positions)
        kp = kp.at[widx, off].set(k.astype(kp.dtype), mode="drop")
        vp = vp.at[widx, off].set(v.astype(vp.dtype), mode="drop")
        if kernel:
            o = L.paged_chunk_attention(q, kp[None], vp[None], page_table,
                                        pos, cfg, layer=0)
        else:
            o = L.chunk_decode_attention(q, gather(kp), gather(vp),
                                         positions, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = L.apply_norm(x, p["xattn_norm"], cfg)
        x = x + _cross_attend(h, xk, xv, p["xattn"], cfg)
        h = L.apply_norm(x, p["mlp_norm"], cfg)
        x = x + L.mlp_block(h, p["mlp"], cfg)
        return x, (kp, vp)

    x, (ks, vs) = jax.lax.scan(
        step, x, (params["dec_layers"], k_pages, v_pages,
                  cache["xk"], cache["xv"]))
    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = L.unembed(x, params["embed"], cfg)                # [B,T,V]
    return (logits, {"xk": cache["xk"], "xv": cache["xv"],
                     "pos": pos + valid_len}, ks, vs)
