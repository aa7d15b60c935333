"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

Kernels execute in interpret mode on CPU (the kernel *body* runs for real);
mode='pallas' on an actual TPU takes the identical code path.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:        # only the brute-force property test needs hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    given = settings = st = None

from repro.core.graph import to_padded_neighbors
from repro.kernels import ops
from repro.kernels.ref import label_argmax_ref
from conftest import random_graph


def _tiles(n, d, seed, n_labels=None, wdtype=np.float32):
    rng = np.random.default_rng(seed)
    n_labels = n_labels or max(n // 2, 2)
    lab = rng.integers(0, n_labels, size=(n, d)).astype(np.int32)
    w = rng.uniform(0.1, 5.0, size=(n, d)).astype(wdtype)
    mask = rng.random((n, d)) < 0.8
    cur = rng.integers(0, n_labels, size=(n,)).astype(np.int32)
    return jnp.asarray(lab), jnp.asarray(w), jnp.asarray(mask), \
        jnp.asarray(cur)


# tile widths below 128 follow the graph's degree (core.graph.tile_width)
NARROW = [(8, 8), (256, 8), (48, 16), (64, 64)]


@pytest.mark.parametrize("shape", [(8, 128), (16, 128), (8, 256),
                                   (40, 128), (64, 512), (128, 384)]
                         + NARROW)
@pytest.mark.parametrize("seed", [0, 3])
def test_label_argmax_shape_sweep(shape, seed):
    lab, w, mask, cur = _tiles(*shape, seed=seed)
    for s in (0, 1, 12345):
        out_p = ops.label_argmax(lab, w, mask, cur, s, mode="interpret")
        out_r = ops.label_argmax(lab, w, mask, cur, s, mode="ref")
        for a, b in zip(out_p, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)


@pytest.mark.parametrize("shape", [(8, 128), (48, 256), (16, 640)] + NARROW)
def test_min_label_shape_sweep(shape, seed=1):
    n, d = shape
    rng = np.random.default_rng(seed)
    nbr_lab = jnp.asarray(rng.integers(0, n, (n, d)).astype(np.int32))
    nbr_comm = jnp.asarray(rng.integers(0, 4, (n, d)).astype(np.int32))
    mask = jnp.asarray(rng.random((n, d)) < 0.7)
    self_lab = jnp.arange(n, dtype=jnp.int32)
    self_comm = jnp.asarray(rng.integers(0, 4, (n,)).astype(np.int32))
    a = ops.min_label(nbr_lab, nbr_comm, mask, self_lab, self_comm,
                      mode="interpret")
    b = ops.min_label(nbr_lab, nbr_comm, mask, self_lab, self_comm,
                      mode="ref")
    assert np.array_equal(np.asarray(a), np.asarray(b))


if st is not None:
    def _property_args(fn):
        return settings(max_examples=15, deadline=None)(
            given(st.integers(1, 6), st.integers(1, 4),
                  st.integers(0, 99_999))(fn))
else:
    _property_args = pytest.mark.skip(
        reason="property tests need hypothesis (requirements-dev.txt)")


@_property_args
def test_label_argmax_property(nb, db, seed):
    """Random tiles: kernel == oracle == brute force."""
    n, d = nb * 8, db * 128
    lab, w, mask, cur = _tiles(n, d, seed)
    bl, bw, cw = (np.asarray(x) for x in
                  ops.label_argmax(lab, w, mask, cur, seed % 7,
                                   mode="interpret"))
    labn, wn, maskn, curn = (np.asarray(x) for x in (lab, w, mask, cur))
    for i in range(n):
        acc = {}
        for j in range(d):
            if maskn[i, j]:
                acc[labn[i, j]] = acc.get(labn[i, j], 0.0) + wn[i, j]
        if not acc:
            assert bw[i] == 0.0
            continue
        best = max(acc.values())
        np.testing.assert_allclose(bw[i], best, rtol=1e-5)
        assert labn[i][maskn[i]].tolist().count(bl[i]) > 0
        np.testing.assert_allclose(acc.get(bl[i], -1.0), best, rtol=1e-5)
        np.testing.assert_allclose(cw[i], acc.get(curn[i], 0.0), rtol=1e-5)


def test_kernels_on_real_graph_tiles():
    g = random_graph(60, 6.0, seed=11, weighted=True)
    nbr, nw, nmask = to_padded_neighbors(g)
    labels = jnp.arange(nbr.shape[0], dtype=jnp.int32)
    nbr_lab = labels[jnp.asarray(nbr)]
    a = ops.label_argmax(nbr_lab, jnp.asarray(nw), jnp.asarray(nmask),
                         labels, 0, mode="interpret")
    b = label_argmax_ref(nbr_lab, jnp.asarray(nw), jnp.asarray(nmask),
                         labels, jnp.int32(0))
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def _move_state(n, d, seed):
    """Wake/frontier state for the fused move kernel."""
    rng = np.random.default_rng(seed + 1000)
    chg = rng.random((n, d)) < 0.3
    active = rng.random(n) < 0.6
    cand_prev = rng.random(n) < 0.4
    klass = rng.random(n) < 0.7
    real = np.ones(n, dtype=bool)
    real[-max(n // 8, 1):] = False      # padded tail rows
    return tuple(jnp.asarray(x)
                 for x in (chg, active, cand_prev, klass, real))


@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (64, 512)] + NARROW)
@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_fused_move_matches_separate_dispatch(shape, seed, mode):
    """fused_move == wake glue + the separate label_argmax dispatch,
    bit-for-bit (labels AND the active frontier) in both kernel modes —
    including edgeless and self-loop rows the wake math must not
    resurrect, across tie-break seeds."""
    lab, w, mask, cur = _tiles(*shape, seed=seed)
    chg, active, cand_prev, klass, real = _move_state(*shape, seed)
    mask = mask.at[0].set(False)                      # edgeless row
    lab = lab.at[1].set(cur[1])                       # self-loop row
    for s in (0, 1, 12345):
        new, act = ops.fused_move(lab, w, mask, chg, cur, active,
                                  cand_prev, klass, real, s, mode=mode)
        wake = jnp.any(chg & mask, axis=1)
        act_sep = (active & ~cand_prev) | (wake & real)
        bl, bw, cw = ops.label_argmax(lab, w, mask, cur, s, mode=mode)
        adopt = (act_sep & klass) & (bw > jnp.maximum(cw, 0.0))
        new_sep = jnp.where(adopt, bl.astype(jnp.int32), cur)
        assert np.array_equal(np.asarray(new), np.asarray(new_sep)), \
            (shape, seed, mode, s)
        assert np.array_equal(np.asarray(act), np.asarray(act_sep)), \
            (shape, seed, mode, s)
        # edgeless row can never adopt; its frontier bit is wake-free
        assert int(new[0]) == int(cur[0])


@pytest.mark.parametrize("shape", [(8, 128), (48, 256)] + NARROW)
@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_fused_split_matches_separate_dispatch(shape, prune, mode):
    """fused_split == split-wake glue + the separate min_label dispatch,
    for both prune modes; chg=ones (the first-iteration trick) must
    reduce to the plain eager min_label sweep."""
    n, d = shape
    rng = np.random.default_rng(7)
    nbr_lab = jnp.asarray(rng.integers(0, n, (n, d)).astype(np.int32))
    nbr_comm = jnp.asarray(rng.integers(0, 4, (n, d)).astype(np.int32))
    mask = jnp.asarray(rng.random((n, d)) < 0.7).at[0].set(False)
    self_lab = jnp.arange(n, dtype=jnp.int32)
    self_comm = jnp.asarray(rng.integers(0, 4, (n,)).astype(np.int32))
    mres = ops.min_label(nbr_lab, nbr_comm, mask, self_lab, self_comm,
                         mode=mode)
    for chg_np in (np.ones((n, d), dtype=bool), rng.random((n, d)) < 0.4):
        chg = jnp.asarray(chg_np)
        out = ops.fused_split(nbr_lab, nbr_comm, mask, chg, self_lab,
                              self_comm, prune=prune, mode=mode)
        expect = mres
        if prune:
            same = mask & (nbr_comm == self_comm[:, None])
            wake = jnp.any(chg & same, axis=1)
            expect = jnp.where(wake, mres, self_lab)
        assert np.array_equal(np.asarray(out), np.asarray(expect)), \
            (shape, prune, mode, bool(chg_np.all()))
        if chg_np.all():
            # ones-trick: un-woken rows have no same-community neighbor,
            # where min_label already returns the row's own label
            assert np.array_equal(np.asarray(out), np.asarray(mres))


def test_vmem_tile_budget():
    """ops.pick_tile_b must keep the equality cube within the VMEM budget:
    the 4 MB target where an 8-row tile fits it, else the 8-row minimum."""
    for n_pad, d in [(1024, 128), (4096, 512), (65536, 1024), (40, 128),
                     (65536, 8), (1024, 64)]:
        t = ops.pick_tile_b(n_pad, d)
        assert n_pad % t == 0
        assert t * d * d * 4 <= 4 * 1024 * 1024 or t == 8
