"""jit'd wrappers for the decode kernels."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .paged_attention import decode_ring, paged_decode, paged_decode_chunk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window", "scale", "n_rep"))
def decode_attention_kernel(q: jnp.ndarray, cache_k: jnp.ndarray,
                            cache_v: jnp.ndarray, pos: jnp.ndarray, *,
                            window: Optional[int], scale: float,
                            n_rep: int) -> jnp.ndarray:
    """Drop-in for models.layers.decode_attention (impl='pallas')."""
    return decode_ring(q, cache_k, cache_v, pos, scale=scale, n_rep=n_rep,
                       window=window, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("scale", "n_rep"))
def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, *, scale: float,
                           n_rep: int) -> jnp.ndarray:
    """Engine-side paged decode over the KV pool (vLLM block-table analogue)."""
    return paged_decode(q, k_pages, v_pages, page_table, lengths,
                        scale=scale, n_rep=n_rep, interpret=not _on_tpu())


def paged_decode_chunk_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 page_table: jnp.ndarray, pos: jnp.ndarray,
                                 *, layer: int | jnp.ndarray, scale: float,
                                 n_rep: int) -> jnp.ndarray:
    """Chunk-extended paged decode (q [B,T,H,D]) over layer ``layer`` of
    the stacked KV pool [L, n_pages, page, Hkv, D].

    Not jitted here: callers invoke it inside an already-jitted layer scan
    (``models.transformer.decode_chunk_paged`` with ``kernel=True``)."""
    return paged_decode_chunk(q, k_pages, v_pages, page_table, pos,
                              layer=layer, scale=scale, n_rep=n_rep,
                              interpret=not _on_tpu())
