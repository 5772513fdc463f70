"""Decode attention Pallas TPU kernels (the serving hot-spot).

Two kernels:

1. ``decode_ring`` — single-token attention over the model's dense
   per-slot ring-buffer cache [B, C, Hkv, Dh] with per-sequence positions
   and optional sliding window.  This is the kernel behind
   ``layers.decode_attention(impl="pallas")``.

2. ``paged_decode_chunk`` — attention for a T-token chunk over the
   engine's paged pool ([n_pages, page, Hkv, Dh]) indexed through
   per-sequence page tables, using PrefetchScalarGridSpec so the page table
   is available to the BlockSpec index_map (the TPU-native equivalent of
   vLLM's block tables: pages stage HBM->VMEM by table lookup, no gather
   materialization).  ``paged_decode`` is its T=1 case.

TPU layout: a K/V block is a whole page (or ring block) across ALL kv
heads, ``(1, page, Hkv, Dh)``.  Its last two dimensions are the array's
own (Hkv, Dh), which is the block shape Mosaic tiles for any Hkv; a
per-head block ``(1, page, 1, Dh)`` is refused whenever Hkv > 1.  The grid
is therefore (B, pages) and the kernel loops over kv heads inside, each
head's GQA group (n_rep query heads, times T chunk tokens) processed as
one [R, Dh] x [page, Dh]^T matmul per page — the MXU eats the whole query
group at once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, valid,
                  scale: float):
    """Online-softmax update with one K/V block, every kv head.

    q_ref [1, Hkv, R, D]; k_ref/v_ref [1, BK, Hkv, D]; scratch m/l
    [Hkv, R, 1] and acc [Hkv, R, D]; ``valid`` [R, BK] masks the block's
    positions for every head alike."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)             # [R, D]
        k = k_ref[0, :, h, :].astype(jnp.float32)       # [BK, D]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new


def _finish(o_ref, l_scr, acc_scr):
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                ).astype(o_ref.dtype)


def _scratch(Hkv: int, R: int, D: int):
    return [pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, D), jnp.float32)]


# ---------------------------------------------------------------- ring cache
def _ring_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, scale: float, block_k: int, cache_len: int,
                 window: Optional[int]):
    """Grid: (B, C/BK).  q_ref: [1, Hkv, n_rep, D]; k/v: [1, BK, Hkv, D]."""
    b = pl.program_id(0)
    ki = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    shape = (q_ref.shape[2], block_k)
    slots = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is not None:
        age = (pos % cache_len - slots) % cache_len
        valid = age < jnp.minimum(window, pos + 1)
    else:
        valid = slots <= pos
    _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, valid, scale)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        _finish(o_ref, l_scr, acc_scr)


def decode_ring(q: jnp.ndarray, cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                pos: jnp.ndarray, *, scale: float, n_rep: int,
                window: Optional[int] = None, block_k: int = 128,
                interpret: bool = True) -> jnp.ndarray:
    """q: [B, 1, H, D]; cache: [B, C, Hkv, D]; pos: [B] -> [B, 1, H, D]."""
    B, C, Hkv, D = cache_k.shape
    H = Hkv * n_rep
    qg = q[:, 0].reshape(B, Hkv, n_rep, D)
    bk = min(block_k, C)
    pad = (-C) % bk
    if pad:
        cache_k = jnp.pad(cache_k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache_v = jnp.pad(cache_v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Cp = C + pad
    # padded slots never hold valid entries: slot >= C > pos (no window) and
    # age >= window (window case) because the ring arithmetic uses cache_len=C
    kernel = functools.partial(_ring_kernel, scale=scale, block_k=bk,
                               cache_len=C, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Cp // bk),
            in_specs=[
                pl.BlockSpec((1, Hkv, n_rep, D), lambda b, j, pos: (b, 0, 0, 0)),
                pl.BlockSpec((1, bk, Hkv, D), lambda b, j, pos: (b, j, 0, 0)),
                pl.BlockSpec((1, bk, Hkv, D), lambda b, j, pos: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hkv, n_rep, D),
                                   lambda b, j, pos: (b, 0, 0, 0)),
            scratch_shapes=_scratch(Hkv, n_rep, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, n_rep, D), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, cache_k, cache_v)
    return out.reshape(B, 1, H, D)


# -------------------------------------------------------- paged chunk decode
def _paged_chunk_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale: float, page: int,
                        T: int, n_rep: int):
    """Grid: (B, max_pages).  q_ref: [1, Hkv, R, D] with R = T*n_rep query
    rows; row r belongs to chunk token ``r // n_rep`` at logical position
    ``pos[b] + r // n_rep``.  The chunk's own K/V is already scattered into
    the pages (write-then-attend), so per-row causal masking
    ``tok <= pos + t`` is the only mask needed.  Pages past the chunk's
    last position hold nothing any row attends: they are skipped."""
    b = pl.program_id(0)
    ji = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(ji == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    @pl.when((pt_ref[b, ji] >= 0) & (ji * page <= pos + T - 1))
    def _compute():
        shape = (T * n_rep, page)
        tok = ji * page + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // n_rep
        _attend_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                      tok <= qpos, scale)

    @pl.when(ji == pl.num_programs(1) - 1)
    def _fin():
        _finish(o_ref, l_scr, acc_scr)


def paged_decode_chunk(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, page_table: jnp.ndarray,
                       pos: jnp.ndarray, *, scale: float, n_rep: int,
                       interpret: bool = True) -> jnp.ndarray:
    """Chunk-extended paged decode: q [B, T, H, D] over pool pages.

    ``pos`` [B] is each sequence's first chunk position; chunk token t
    queries positions <= pos+t.  The caller must have scattered the chunk's
    K/V into the pages already.  Rows past a sequence's valid length attend
    unwritten positions and return garbage — callers discard them (the
    engine reads row ``valid_len[b]-1`` only).  -> [B, T, H, D].
    """
    B, T, H, D = q.shape
    n_pages, page, Hkv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    R = T * n_rep
    # head h = kv*n_rep + rep (repeat_kv layout); row r = t*n_rep + rep
    qg = (q.reshape(B, T, Hkv, n_rep, D)
          .transpose(0, 2, 1, 3, 4).reshape(B, Hkv, R, D))

    def kv_index(b, j, pt, pos_):
        # table lookup inside the index_map: the DMA fetches exactly the
        # page this grid cell needs.  Cells past the chunk's last page
        # repeat that page's index, so the pipeline skips their copy.
        last = jnp.maximum((pos_[b] + T - 1) // page, 0)
        return (jnp.maximum(pt[b, jnp.minimum(j, last)], 0), 0, 0, 0)

    kernel = functools.partial(_paged_chunk_kernel, scale=scale, page=page,
                               T=T, n_rep=n_rep)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, max_pages),
            in_specs=[
                pl.BlockSpec((1, Hkv, R, D),
                             lambda b, j, pt, pos_: (b, 0, 0, 0)),
                pl.BlockSpec((1, page, Hkv, D), kv_index),
                pl.BlockSpec((1, page, Hkv, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, Hkv, R, D),
                                   lambda b, j, pt, pos_: (b, 0, 0, 0)),
            scratch_shapes=_scratch(Hkv, R, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      qg, k_pages, v_pages)
    return (out.reshape(B, Hkv, T, n_rep, D)
            .transpose(0, 2, 1, 3, 4).reshape(B, T, H, D))


def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 page_table: jnp.ndarray, lengths: jnp.ndarray, *,
                 scale: float, n_rep: int,
                 interpret: bool = True) -> jnp.ndarray:
    """q: [B, H, D]; pages: [n_pages, page, Hkv, D];
    page_table: [B, max_pages] (pool indices, -1 = unused);
    lengths: [B] valid tokens.  -> [B, H, D].  The T=1 chunk: the one
    query sits at position ``lengths - 1``."""
    return paged_decode_chunk(q[:, None], k_pages, v_pages, page_table,
                              lengths.astype(jnp.int32) - 1, scale=scale,
                              n_rep=n_rep, interpret=interpret)[:, 0]
