"""Property/fuzz tests for the paged-attention decode kernels.

Randomized page tables (permuted page order, -1 padding, partially filled
final pages), ragged per-row lengths, and arbitrary GQA group shapes —
the Pallas kernels (interpret mode on CPU) must match both the jnp
oracles in ``ref.py`` and a from-scratch float64 numpy dense attention
that shares no code with either.

When hypothesis is not installed, the deterministic fallback shim
(tests/_hypothesis_fallback.py) stands in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                               paged_decode_chunk_attention)
from repro.kernels.paged_attention.paged_attention import paged_decode_chunk
from repro.kernels.paged_attention.ref import (paged_decode_chunk_ref,
                                               paged_decode_ref)

SETTINGS = dict(max_examples=20, deadline=None)
TOL = dict(rtol=2e-5, atol=2e-5)


def _random_tables(rng, B, n_pages, maxp, page, max_len):
    """Per-row ragged lengths + page tables drawn as a random *permutation*
    of the pool's pages — adjacency in the table never implies adjacency in
    the pool, and the final page is partially filled whenever
    ``len % page != 0``."""
    lens = rng.integers(1, max_len + 1, B)
    perm = rng.permutation(n_pages)
    pt = np.full((B, maxp), -1, np.int64)
    used = 0
    for b in range(B):
        need = -(-int(lens[b]) // page)            # ceil-div: pages needed
        pt[b, :need] = perm[used:used + need]
        used += need
    return jnp.asarray(lens, jnp.int32), jnp.asarray(pt, jnp.int32)


def _dense_oracle(q, kp, vp, pt, qpos, scale):
    """Independent float64 numpy attention: gather per row, mask positions
    > qpos[b, t], softmax, weighted sum.  No shared code with ref.py."""
    q, kp, vp = (np.asarray(x, np.float64) for x in (q, kp, vp))
    pt = np.asarray(pt)
    B, T, H, D = q.shape
    _, page, Hkv, _ = kp.shape
    rep = H // Hkv
    C = pt.shape[1] * page
    out = np.zeros_like(q)
    for b in range(B):
        k = kp[np.maximum(pt[b], 0)].reshape(C, Hkv, D)
        v = vp[np.maximum(pt[b], 0)].reshape(C, Hkv, D)
        for t in range(T):
            n = int(qpos[b, t]) + 1                # attends positions <= qpos
            for h in range(H):
                s = (k[:n, h // rep] @ q[b, t, h]) * scale
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ v[:n, h // rep]
    return out


# ------------------------------------------------- single-token paged decode
@given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 4),
       st.integers(0, 10_000))
@settings(**SETTINGS)
def test_paged_decode_fuzz(B, Hkv, n_rep, seed):
    rng = np.random.default_rng(seed)
    page = int(rng.choice([4, 8, 16]))
    maxp = int(rng.integers(2, 6))
    n_pages = B * maxp + 2
    D = int(rng.choice([8, 16, 32]))
    lens, pt = _random_tables(rng, B, n_pages, maxp, page, maxp * page)
    q = jnp.asarray(rng.standard_normal((B, Hkv * n_rep, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    out = paged_decode_attention(q, kp, vp, pt, lens, scale=D ** -0.5,
                                 n_rep=n_rep)
    ref = paged_decode_ref(q, kp, vp, pt, lens, scale=D ** -0.5, n_rep=n_rep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    dense = _dense_oracle(q[:, None], kp, vp, pt,
                          np.asarray(lens)[:, None] - 1, D ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(out, np.float64), dense,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------- chunked paged decode
@given(st.integers(1, 3), st.integers(1, 4), st.integers(1, 2),
       st.integers(1, 3), st.integers(0, 10_000))
@settings(**SETTINGS)
def test_paged_decode_chunk_fuzz(B, T, Hkv, n_rep, seed):
    """T-token chunk over pooled pages: row t of batch b attends positions
    <= pos[b]+t.  Pages are pre-filled past pos (the engine scatters the
    chunk's K/V before attending on the non-windowed path)."""
    rng = np.random.default_rng(seed)
    page = int(rng.choice([4, 8]))
    maxp = int(rng.integers(2, 5))
    n_pages = B * maxp + 2
    D = int(rng.choice([8, 16]))
    # pos = tokens already cached; chunk occupies pos .. pos+T-1, so the
    # table must cover pos+T positions (partial final page exercised when
    # (pos+T) % page != 0)
    total, pt = _random_tables(rng, B, n_pages, maxp, page, maxp * page)
    pos = jnp.maximum(total - T, 0)
    q = jnp.asarray(rng.standard_normal((B, T, Hkv * n_rep, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    out = paged_decode_chunk_attention(q, kp[None], vp[None], pt, pos,
                                       layer=0, scale=D ** -0.5, n_rep=n_rep)
    ref = paged_decode_chunk_ref(q, kp, vp, pt, pos, scale=D ** -0.5,
                                 n_rep=n_rep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    qpos = np.asarray(pos)[:, None] + np.arange(T)[None, :]
    dense = _dense_oracle(q, kp, vp, pt, qpos, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float64), dense,
                               rtol=1e-4, atol=1e-4)


def test_paged_chunk_permutation_invariance():
    """Relabeling pool pages (and permuting the table to match) must not
    change the output: the kernel may depend only on the *logical* layout
    the table describes, never on physical page ids."""
    rng = np.random.default_rng(7)
    B, T, Hkv, n_rep, D, page, maxp, n_pages = 2, 3, 2, 2, 16, 4, 4, 12
    lens, pt = _random_tables(rng, B, n_pages, maxp, page, maxp * page)
    pos = jnp.maximum(lens - T, 0)
    q = jnp.asarray(rng.standard_normal((B, T, Hkv * n_rep, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, Hkv, D)), jnp.float32)
    base = paged_decode_chunk_attention(q, kp[None], vp[None], pt, pos,
                                        layer=0, scale=D ** -0.5, n_rep=n_rep)
    perm = rng.permutation(n_pages)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n_pages)                # new id of old page p
    pt_p = jnp.where(pt >= 0, jnp.asarray(inv)[jnp.maximum(pt, 0)], -1)
    relabeled = paged_decode_chunk_attention(
        q, jnp.asarray(np.asarray(kp)[perm])[None],
        jnp.asarray(np.asarray(vp)[perm])[None], pt_p.astype(jnp.int32), pos,
        layer=0, scale=D ** -0.5, n_rep=n_rep)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(relabeled))


def test_paged_chunk_ignores_garbage_beyond_pos():
    """Bytes past ``pos+t`` in the gathered window — stale page tails,
    -1-padded table slots aliased to page 0 — must not leak into the
    output (the COW pool recycles pages without zeroing them)."""
    rng = np.random.default_rng(11)
    B, T, Hkv, n_rep, D, page, maxp, n_pages = 2, 2, 1, 2, 8, 4, 3, 8
    lens = jnp.asarray([5, 9], jnp.int32)         # partial final pages
    pt = jnp.asarray([[2, 4, -1], [6, 1, 3]], jnp.int32)
    pos = lens - T
    q = jnp.asarray(rng.standard_normal((B, T, Hkv * n_rep, D)), jnp.float32)
    kp = rng.standard_normal((n_pages, page, Hkv, D)).astype(np.float32)
    vp = rng.standard_normal((n_pages, page, Hkv, D)).astype(np.float32)
    out = paged_decode_chunk_attention(q, jnp.asarray(kp)[None],
                                       jnp.asarray(vp)[None], pt, pos,
                                       layer=0, scale=D ** -0.5, n_rep=n_rep)
    # trash every byte beyond each row's visible range (and all unused pages)
    used = np.zeros((n_pages, page), bool)
    for b in range(B):
        for t_ in range(int(lens[b])):
            used[np.asarray(pt)[b, t_ // page], t_ % page] = True
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[~used] = 1e9
    vp2[~used] = -1e9
    out2 = paged_decode_chunk_attention(q, jnp.asarray(kp2)[None],
                                        jnp.asarray(vp2)[None], pt, pos,
                                        layer=0, scale=D ** -0.5, n_rep=n_rep)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ------------------------------------------------ stacked [L, ...] pool
L_STACK = 4


def _stacked_case(T, seed=5):
    """A [L, n_pages, page, Hkv, D] pool with a distinct slab per layer,
    ragged rows and a permuted table."""
    rng = np.random.default_rng(seed)
    B, Hkv, n_rep, D, page, maxp = 2, 2, 2, 16, 4, 4
    n_pages = B * maxp + 2
    total, pt = _random_tables(rng, B, n_pages, maxp, page, maxp * page)
    total = jnp.maximum(total, T)
    pos = total - T
    q = jnp.asarray(rng.standard_normal((B, T, Hkv * n_rep, D)), jnp.float32)
    shape = (L_STACK, n_pages, page, Hkv, D)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return q, kp, vp, pt, pos, dict(scale=D ** -0.5, n_rep=n_rep)


@pytest.mark.parametrize("layer", [0, 2, L_STACK - 1])
@pytest.mark.parametrize("T", [1, 2, 4, 8])
def test_paged_decode_chunk_reads_one_layer_of_stacked_pool(T, layer):
    """``layer=l`` over the stacked pool matches the oracle on ``pool[l]``,
    and is bitwise the single-layer caller's ``pool[l][None]``, layer 0."""
    q, kp, vp, pt, pos, kw = _stacked_case(T)
    out = paged_decode_chunk(q, kp, vp, pt, pos, layer=layer, **kw)
    ref = paged_decode_chunk_ref(q, kp[layer], vp[layer], pt, pos, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    one = paged_decode_chunk(q, kp[layer][None], vp[layer][None], pt, pos,
                             layer=0, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))


@pytest.mark.parametrize("layer", [0, 2, L_STACK - 1])
def test_paged_decode_chunk_never_reads_other_layers(layer):
    """Garbage (NaN, huge values) in every other layer's slab leaves the
    output bitwise unchanged: the kernel fetches only its layer's pages."""
    q, kp, vp, pt, pos, kw = _stacked_case(4, seed=9)
    base = paged_decode_chunk(q, kp, vp, pt, pos, layer=layer, **kw)
    others = np.arange(L_STACK) != layer
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[others] = np.nan
    vp2[others] = 1e30
    trashed = paged_decode_chunk(q, jnp.asarray(kp2), jnp.asarray(vp2), pt,
                                 pos, layer=layer, **kw)
    assert np.isfinite(np.asarray(trashed)).all()
    np.testing.assert_array_equal(np.asarray(base), np.asarray(trashed))
