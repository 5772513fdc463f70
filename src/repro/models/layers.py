"""Shared model building blocks (pure JAX, pytree params).

Conventions:
  * params are nested dicts of jnp arrays; layer-stacked arrays have a
    leading L dimension and run under ``jax.lax.scan``;
  * activations flow in ``cfg.jnp_dtype`` (bf16 by default); norms/softmax
    accumulate in f32;
  * attention math matches the published architectures: GQA with optional
    per-head qk RMSNorm (Qwen3), partial RoPE (StableLM-2), sliding windows
    (RecurrentGemma local layers, long-context dense carve-out).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig


# ------------------------------------------------------------------- norms
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.norm_type == "layer":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, width: Optional[int] = None) -> dict:
    d = width or cfg.d_model
    p = {"w": jnp.ones((d,), cfg.jnp_dtype)}
    if cfg.norm_type == "layer":
        p["b"] = jnp.zeros((d,), cfg.jnp_dtype)
    return p


# -------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, rope_pct: float, theta: float):
    rot = int(head_dim * rope_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable)."""
    inv, rot = rope_frequencies(cfg.head_dim_, cfg.rope_pct, cfg.rope_theta)
    if rot == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # [..., S, rot/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]    # [..., S, 1, rot/2]
    cos = cos[..., :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------- attention
def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B,S,Hkv,Dh] -> [B,S,Hkv*n_rep,Dh] (GQA broadcast)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def causal_mask(q_len: int, kv_len: int, q_offset,
                window: Optional[int] = None) -> jnp.ndarray:
    """Boolean [q_len, kv_len]; True = attendable.  q position i (global
    q_offset+i) may attend kv position j iff j <= i and (window is None or
    i - j < window)."""
    qpos = q_offset + jnp.arange(q_len)[:, None]
    kpos = jnp.arange(kv_len)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """Softmax attention.  q:[B,Sq,H,Dh] k,v:[B,Skv,H,Dh] mask:[Sq,Skv]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def chunked_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                 causal: bool, window: Optional[int], scale: float,
                 q_chunk: int = 512) -> jnp.ndarray:
    """Memory-efficient attention: scan over query chunks so only a
    [B, H, q_chunk, Skv] score block is ever live (the XLA-level analogue
    of the Pallas flash kernel — used at production shapes where the full
    [B, H, S, S] matrix does not fit HBM; EXPERIMENTS.md §Perf iter 5)."""
    B, S, H, D = q.shape
    Skv = k.shape[1]
    c = min(q_chunk, S)
    pad = (-S) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (S + pad) // c
    qb = q.reshape(B, nq, c, H, D).transpose(1, 0, 2, 3, 4)  # [nq,B,c,H,D]
    kpos = jnp.arange(Skv)

    def block(carry, inp):
        i, qi = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                            preferred_element_type=jnp.float32) * scale
        qpos = i * c + jnp.arange(c)
        m = jnp.ones((c, Skv), bool)
        if causal:
            m = m & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            m = m & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(m[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return carry, jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    _, out = jax.lax.scan(block, None, (jnp.arange(nq), qb))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, H, D)
    return out[:, :S]


def full_attention(q, k, v, *, causal: bool, window, scale: float,
                   impl: str = "xla"):
    """Dispatch full-sequence attention (k/v already GQA-expanded)."""
    if impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention
        B, S, H, D = q.shape
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale)
    if impl == "xla_chunked":
        return chunked_sdpa(q, k, v, causal=causal, window=window,
                            scale=scale)
    mask = causal_mask(q.shape[1], k.shape[1], 0, window) if causal or window \
        else None
    return sdpa(q, k, v, mask, scale)


def init_attention(rng, cfg: ModelConfig) -> dict:
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k = jax.random.split(rng, 4)
    s = lambda *shape: (2.0 / (shape[0] + shape[-1])) ** 0.5
    p = {
        "wq": (jax.random.normal(k[0], (D, H, Dh)) * s(D, Dh)).astype(cfg.jnp_dtype),
        "wk": (jax.random.normal(k[1], (D, Hkv, Dh)) * s(D, Dh)).astype(cfg.jnp_dtype),
        "wv": (jax.random.normal(k[2], (D, Hkv, Dh)) * s(D, Dh)).astype(cfg.jnp_dtype),
        "wo": (jax.random.normal(k[3], (H, Dh, D)) * s(Dh, D)).astype(cfg.jnp_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), cfg.jnp_dtype)
        p["k_norm"] = jnp.ones((Dh,), cfg.jnp_dtype)
    return p


def attention_qkv(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                  positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Project + qk-norm + rope.  Returns q:[B,S,H,Dh], k/v:[B,S,Hkv,Dh]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    return q, k, v


def attention_block(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                    positions: jnp.ndarray,
                    window: Optional[int] = None,
                    attention_impl: str = "xla") -> jnp.ndarray:
    """Full (training / prefill) self-attention over x:[B,S,D]."""
    B, S, _ = x.shape
    q, k, v = attention_qkv(x, p, cfg, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim_ ** -0.5
    if attention_impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                              causal=True, window=window, scale=scale)
    elif attention_impl == "xla_chunked":
        out = chunked_sdpa(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                           causal=True, window=window, scale=scale)
    else:
        mask = causal_mask(S, S, 0, window)
        out = sdpa(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), mask, scale)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# --------------------------------------------------------------------- MLP
def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k = jax.random.split(rng, 3)
    s_in = (2.0 / (D + F)) ** 0.5
    p = {
        "w_up": (jax.random.normal(k[0], (D, F)) * s_in).astype(cfg.jnp_dtype),
        "w_down": (jax.random.normal(k[1], (F, D)) * s_in).astype(cfg.jnp_dtype),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(k[2], (D, F)) * s_in).astype(cfg.jnp_dtype)
    return p


def mlp_block(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# --------------------------------------------------------------- embedding
def init_embedding(rng, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))
                 * cfg.d_model ** -0.5).astype(cfg.jnp_dtype)}
    if not cfg.tie_embeddings:
        p["out"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                    * cfg.d_model ** -0.5).astype(cfg.jnp_dtype)
    return p


def embed(tokens: jnp.ndarray, p: dict) -> jnp.ndarray:
    return p["tok"][tokens]


@jax.custom_vjp
def _tied_unembed(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,vd->...v", x, w)


def _tied_unembed_fwd(x, w):
    from ..distributed.context import constrain
    return constrain(_tied_unembed(x, w), "logits"), (x, w)


def _tied_unembed_bwd(res, g):
    """Backward with the cotangent explicitly constrained to the logits
    sharding.  Without this, GSPMD materializes replicated d(logits) for the
    tied-weight gradient — the residual ~40 GB all-gather of EXPERIMENTS.md
    §Perf iteration 1.  dw is a local v-shard product + a small all-reduce
    over the batch axis; dx is a sharded-v contraction (partial-sum).
    """
    from ..distributed.context import constrain
    x, w = res
    g = constrain(g, "logits")
    dx = jnp.einsum("...v,vd->...d", g, w).astype(x.dtype)
    dw = jnp.einsum("...v,...d->vd", g, x).astype(w.dtype)
    return dx, dw


_tied_unembed.defvjp(_tied_unembed_fwd, _tied_unembed_bwd)


def unembed(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    from ..distributed.context import constrain
    if cfg.tie_embeddings:
        return _tied_unembed(x, p["tok"])
    return constrain(x @ p["out"], "logits")


# ------------------------------------------------------------ decode utils
def pad_cache_seq(ks: jnp.ndarray, vs: jnp.ndarray, C: int,
                  window: Optional[int], pad_cache_to: Optional[int]):
    """Grow a prefill cache's seq dim (axis 2 of [L,B,C,H,D]) for decode
    headroom.  Windowed caches never grow past the window (the ring already
    holds the last `window` entries; C == window when S > window)."""
    if pad_cache_to is None:
        return ks, vs
    target = min(pad_cache_to, window) if window else pad_cache_to
    if target <= C:
        return ks, vs
    pads = [(0, 0), (0, 0), (0, target - C), (0, 0), (0, 0)]
    return jnp.pad(ks, pads), jnp.pad(vs, pads)

def kv_cache_update(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray,
                    pos: jnp.ndarray, window: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one step's k/v ([B,1,Hkv,Dh]) at per-sequence ``pos`` [B]
    (ring-rolled if windowed).  cache_[kv]: [B, C, Hkv, Dh]."""
    B, C = cache_k.shape[0], cache_k.shape[1]
    pos = jnp.broadcast_to(pos, (B,))
    slot = pos % C if window is not None else jnp.minimum(pos, C - 1)
    b = jnp.arange(B)
    ck = cache_k.at[b, slot].set(k[:, 0].astype(cache_k.dtype))
    cv = cache_v.at[b, slot].set(v[:, 0].astype(cache_v.dtype))
    return ck, cv


def kv_cache_update_chunk(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                          k: jnp.ndarray, v: jnp.ndarray,
                          pos: jnp.ndarray, valid: jnp.ndarray,
                          window: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write up to T tokens' k/v ([B,T,Hkv,Dh]) at per-sequence positions
    ``pos .. pos+T-1`` (ring-rolled if windowed).  ``valid`` [B,T] masks the
    tail: an invalid position re-writes the cache's existing value, so a
    sequence advancing fewer than T tokens (a decode slot piggybacked on a
    prefill chunk) leaves the rest of its row untouched."""
    B, C = cache_k.shape[0], cache_k.shape[1]
    T = k.shape[1]
    pos = jnp.broadcast_to(pos, (B,))
    positions = pos[:, None] + jnp.arange(T)[None, :]          # [B,T]
    slot = positions % C if window is not None else jnp.minimum(positions,
                                                                C - 1)
    b = jnp.arange(B)[:, None]
    m = valid[..., None, None]
    ck = cache_k.at[b, slot].set(
        jnp.where(m, k.astype(cache_k.dtype), cache_k[b, slot]))
    cv = cache_v.at[b, slot].set(
        jnp.where(m, v.astype(cache_v.dtype), cache_v[b, slot]))
    return ck, cv


def chunk_decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                           cache_v: jnp.ndarray, positions: jnp.ndarray,
                           cfg: ModelConfig) -> jnp.ndarray:
    """T-token attention: q:[B,T,H,Dh] over a *non-ring* cache
    [B,C,Hkv,Dh] whose chunk k/v has already been written.

    ``positions`` [B,T] is the logical position of each query token; query
    t attends cache entries at positions <= positions[:, t] (slot index ==
    logical position without a sliding window), which gives causal
    attention within the chunk and full attention over the cached prefix —
    the chunked-prefill generalization of :func:`decode_attention` (T=1
    reduces to it).  Windowed (ring) caches must use
    :func:`chunk_decode_attention_windowed` instead: a chunk write can
    overwrite ring slots that earlier in-chunk queries still need.
    """
    B, C = cache_k.shape[0], cache_k.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim_ ** -0.5
    k = repeat_kv(cache_k, n_rep)
    v = repeat_kv(cache_v, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(C)
    p = positions[..., None]                                   # [B,T,1]
    valid = slots[None, None, :] <= p
    logits = jnp.where(valid[:, None], logits, -1e30)          # [B,H,T,C]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def chunk_decode_attention_windowed(q: jnp.ndarray, cache_k: jnp.ndarray,
                                    cache_v: jnp.ndarray, k_new: jnp.ndarray,
                                    v_new: jnp.ndarray, pos: jnp.ndarray,
                                    valid_len: jnp.ndarray,
                                    positions: jnp.ndarray, cfg: ModelConfig,
                                    window: int) -> jnp.ndarray:
    """Chunked attention for ring (sliding-window) caches, computed
    against the **pre-write** cache plus the chunk's own k/v.

    Writing a whole chunk into a ring of size C before attending is wrong
    for the earlier in-chunk queries: a later chunk token can land on the
    ring slot of a position still inside an earlier query's window.  So
    each query t (logical position ``positions[:, t]``) attends

    * the pre-write cache, whose slot ``s`` holds the largest logical
      position < pos congruent to ``s`` (mod C), masked to the query's
      window, plus
    * the chunk itself, causally (``t' <= t``) and window-masked, limited
      to each sequence's ``valid`` length.

    The ring write (:func:`kv_cache_update_chunk`) happens *after* this.
    """
    B, C = cache_k.shape[0], cache_k.shape[1]
    T = k_new.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim_ ** -0.5
    pos = jnp.broadcast_to(pos, (B,))
    p = positions[..., None]                                   # [B,T,1]
    win = min(window, C)
    # pre-write holder of ring slot s: largest position < pos with
    # position % C == s (negative -> the slot was never written)
    slots = jnp.arange(C)[None, :]
    h_old = pos[:, None] - 1 - ((pos[:, None] - 1 - slots) % C)  # [B,C]
    valid_old = (h_old[:, None, :] >= 0) & (h_old[:, None, :] > p - win)
    k_c = repeat_kv(cache_k, n_rep)
    v_c = repeat_kv(cache_v, n_rep)
    log_c = jnp.einsum("bqhd,bkhd->bhqk", q, k_c,
                       preferred_element_type=jnp.float32) * scale
    log_c = jnp.where(valid_old[:, None], log_c, -1e30)
    # in-chunk: causal, window-masked, clipped to the sequence's valid len
    t_new = jnp.arange(T)
    p_new = pos[:, None] + t_new[None, :]                      # [B,T]
    valid_new = ((p_new[:, None, :] <= p) & (p_new[:, None, :] > p - win)
                 & (t_new[None, None, :] < valid_len[:, None, None]))
    k_n = repeat_kv(k_new, n_rep)
    v_n = repeat_kv(v_new, n_rep)
    log_n = jnp.einsum("bqhd,bkhd->bhqk", q, k_n,
                       preferred_element_type=jnp.float32) * scale
    log_n = jnp.where(valid_new[:, None], log_n, -1e30)
    logits = jnp.concatenate([log_c, log_n], axis=-1)          # [B,H,T,C+T]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals = jnp.concatenate([v_c, v_n], axis=1)                 # [B,C+T,...]
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vals.dtype), vals)


def paged_chunk_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                          v_pages: jnp.ndarray, page_table: jnp.ndarray,
                          pos: jnp.ndarray, cfg: ModelConfig, *,
                          layer: int | jnp.ndarray) -> jnp.ndarray:
    """Pallas paged attention for a T-token chunk straight over layer
    ``layer`` of the stacked KV pool (q [B,T,H,Dh]; pages
    [L,n_pages,P,Hkv,Dh]; the chunk's K/V must already be scattered into
    the pages).

    The serving fast path behind ``decode_chunk_paged(kernel=True)``: no
    dense gather is materialized and no layer is sliced out of the pool —
    pages stage HBM->VMEM by table lookup.
    Numerics match :func:`chunk_decode_attention` to float tolerance but
    not bitwise (different softmax accumulation order), so the engine
    gates it behind its ``paged_kernel`` knob (auto-on on TPU only)."""
    from ..kernels.paged_attention.ops import paged_decode_chunk_attention
    return paged_decode_chunk_attention(
        q, k_pages, v_pages, page_table, pos, layer=layer,
        scale=cfg.head_dim_ ** -0.5,
        n_rep=cfg.n_heads // cfg.n_kv_heads)


def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     pos: jnp.ndarray, cfg: ModelConfig,
                     window: Optional[int] = None,
                     impl: str = "xla") -> jnp.ndarray:
    """One-token attention: q:[B,1,H,Dh] over cache [B,C,Hkv,Dh].

    ``pos`` [B] is the (0-based) position of each sequence's new token;
    cache entries at logical positions <= pos are valid.  With a window the
    cache is a ring buffer and entries older than ``window`` are masked.
    """
    B, C = cache_k.shape[0], cache_k.shape[1]
    pos = jnp.broadcast_to(pos, (B,))
    if impl == "pallas":
        from ..kernels.paged_attention.ops import decode_attention_kernel
        return decode_attention_kernel(q, cache_k, cache_v, pos,
                                       window=window,
                                       scale=cfg.head_dim_ ** -0.5,
                                       n_rep=cfg.n_heads // cfg.n_kv_heads)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim_ ** -0.5
    k = repeat_kv(cache_k, n_rep)
    v = repeat_kv(cache_v, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(C)
    if window is not None:
        # ring buffer: slot s holds logical position p with p % C == s and
        # p in (pos-window, pos]; newest write sits at pos % C.
        age = (pos[:, None] % C - slots[None, :]) % C        # [B,C], 0=newest
        valid = age < jnp.minimum(window, pos[:, None] + 1)
    else:
        valid = slots[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
