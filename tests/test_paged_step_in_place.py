"""One paged step touches only the slots it writes.

``transformer.decode_chunk_paged`` carries the stacked pools
[L, n_pages, P, Hkv, Dh] through its layer scan and scatters each layer's
new tokens into them.  After one step every layer's pool must equal its
input everywhere but the routed ``(page, offset)`` slots of the valid
chunk tokens — idle rows, padding tokens and positions whose table entry
is -1 write nowhere — on the gathered path and on the Pallas kernel path
(interpret mode here).  On the gathered path the written bytes are also
those the dense ``decode_chunk`` puts in its cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer
from repro.models.model import build_model

MAX_SEQ, P = 64, 8
MAXP = MAX_SEQ // P
N_PAGES = 3 * MAXP + 2


@pytest.fixture(scope="module")
def case():
    cfg = get_smoke_config("qwen3_0_6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    perm = rng.permutation(N_PAGES)
    pt = np.full((3, MAXP), -1, np.int32)
    pt[0, :2] = perm[:2]       # chunk at 5..8 crosses into its second page
    pt[1, :1] = perm[2:3]      # one token at position 0
    pt[2, :1] = perm[3:4]      # position 8 lands on a -1 entry: dropped
    pos = np.asarray([5, 0, 8], np.int32)
    valid = np.asarray([4, 1, 2], np.int32)
    T = 4
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (3, T)), jnp.int32)
    shape = (cfg.n_layers, N_PAGES, P, cfg.n_kv_heads, cfg.head_dim_)
    kp = jnp.asarray(rng.standard_normal(shape), cfg.jnp_dtype)
    vp = jnp.asarray(rng.standard_normal(shape), cfg.jnp_dtype)
    written = np.zeros((N_PAGES, P), bool)
    slots = []                                  # (b, position, page, off)
    for b in range(3):
        for t in range(int(valid[b])):
            p = int(pos[b]) + t
            page = int(pt[b, p // P])
            if page >= 0:
                written[page, p % P] = True
                slots.append((b, p, page, p % P))
    return dict(cfg=cfg, params=params, tokens=tokens, valid=jnp.asarray(valid),
                pos=jnp.asarray(pos), pt=jnp.asarray(pt), kp=kp, vp=vp,
                written=written, slots=slots)


def _step(c, kernel):
    f = jax.jit(lambda params, toks, valid, cache, kp, vp, pt:
                transformer.decode_chunk_paged(
                    params, c["cfg"], toks, valid, cache, kp, vp, pt,
                    max_seq=MAX_SEQ, kernel=kernel))
    return f(c["params"], c["tokens"], c["valid"], {"pos": c["pos"]},
             c["kp"], c["vp"], c["pt"])


@pytest.mark.parametrize("kernel", [False, True])
def test_step_writes_only_the_routed_slots(case, kernel):
    _logits, cache, kp, vp = _step(case, kernel)
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  np.asarray(case["pos"] + case["valid"]))
    w = case["written"]
    assert w.sum() == 5
    for before, after in ((case["kp"], kp), (case["vp"], vp)):
        assert after.shape == before.shape and after.dtype == before.dtype
        before, after = np.asarray(before), np.asarray(after)
        for layer in range(before.shape[0]):
            np.testing.assert_array_equal(after[layer][~w], before[layer][~w],
                                          err_msg=f"layer {layer}")
            changed = (after[layer][w] != before[layer][w]).any(axis=(1, 2))
            assert changed.all(), f"layer {layer}: a routed slot kept its bytes"


def test_gathered_step_writes_what_the_dense_step_caches(case):
    """The gathered path's written bytes are the dense ``decode_chunk``'s
    cache entries at the same positions, in every layer."""
    cfg, pt_c = case["cfg"], jnp.maximum(case["pt"], 0)
    L_, B = case["kp"].shape[0], case["pt"].shape[0]

    def dense(pages):
        return pages[:, pt_c].reshape(L_, B, MAXP * P, *pages.shape[3:])

    _logits, cache = jax.jit(
        lambda params, toks, valid, cache: transformer.decode_chunk(
            params, cfg, toks, valid, cache))(
        case["params"], case["tokens"], case["valid"],
        {"k": dense(case["kp"]), "v": dense(case["vp"]), "pos": case["pos"]})
    _l, _c, kp, vp = _step(case, kernel=False)
    for b, p, page, off in case["slots"]:
        np.testing.assert_array_equal(np.asarray(kp[:, page, off]),
                                      np.asarray(cache["k"][:, b, p]))
        np.testing.assert_array_equal(np.asarray(vp[:, page, off]),
                                      np.asarray(cache["v"][:, b, p]))
