"""Pallas TPU kernel: the ``scanCommunities`` + best-label hot spot.

The paper accumulates per-community weights in per-thread hashtables.  On
TPU, the histogram over a padded neighbor tile is recast as an
*equality-masked matmul*:

    scores[b, k] = sum_j w[b, j] * [labels[b, j] == labels[b, k]]

i.e. every neighbor slot k is scored with the total weight of slots carrying
the same label.  The (D, D) equality mask contracted with the weight vector
is MXU-shaped work, entirely VMEM-resident per block, and needs no data-
dependent memory access (the TPU has no efficient hashtable analogue).

Block layout: grid over row tiles; each step sees (TILE_B, D) label /
weight / mask tiles plus (TILE_B, 1) current-label column, and writes
(TILE_B, 1) best-label / best-weight / current-weight columns.  VMEM per
step: 3 * TILE_B * D * 4B for inputs + TILE_B * D * D * 4B for the equality
cube — ``tiling.pick_tile_b`` picks TILE_B (see its budget rules).

Tie-breaks match ``core.lpa`` exactly: max weight, then max label-hash
(per-iteration seed), then min label.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import CUBE_LIMIT_BYTES

_SENTINEL = 2147483647  # python literal: materialised in-trace, not captured


def _hash(labels: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    x = labels.astype(jnp.uint32) * jnp.uint32(2654435761)
    x ^= seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    return x.astype(jnp.int32) & jnp.int32(0x7FFFFFFF)


def argmax_tile_math(lab, w_raw, mask, cur, seed):
    """The (B, D)-tile argmax tie-break chain, shared with fused_sweep.

    Both the standalone and fused kernels must run the *same* op sequence so
    their float sums (and hence tie-break decisions) are bit-identical.
    Returns (best_lab, best_w, cur_w), each (B, 1).
    """
    w = jnp.where(mask, w_raw, 0.0)                      # (B, D) f32

    # Equality cube -> per-slot community scores via batched dot (MXU).
    eq = (lab[:, :, None] == lab[:, None, :]).astype(w.dtype)  # (B, D, D)
    scores = jax.lax.dot_general(
        w[:, None, :], eq,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, 0, :]     # (B, D)
    scores = jnp.where(mask, scores, -1.0)

    best_w = jnp.max(scores, axis=1, keepdims=True)      # (B, 1)
    is_best = mask & (scores >= best_w) & (best_w > 0)
    h = _hash(lab, seed)
    best_h = jnp.max(jnp.where(is_best, h, -1), axis=1, keepdims=True)
    pick = is_best & (h == best_h)
    best_lab = jnp.min(jnp.where(pick, lab, _SENTINEL), axis=1, keepdims=True)

    cur_w = jnp.sum(jnp.where(lab == cur, w, 0.0), axis=1, keepdims=True)
    return best_lab, jnp.maximum(best_w, 0.0), cur_w


def _label_argmax_kernel(seed_ref, lab_ref, w_ref, mask_ref, cur_ref,
                         best_lab_ref, best_w_ref, cur_w_ref):
    best_lab, best_w, cur_w = argmax_tile_math(
        lab_ref[...], w_ref[...], mask_ref[...], cur_ref[...],
        seed_ref[0, 0])
    best_lab_ref[...] = best_lab
    best_w_ref[...] = best_w
    cur_w_ref[...] = cur_w


def label_argmax_pallas(nbr_lab: jnp.ndarray, nbr_w: jnp.ndarray,
                        nbr_mask: jnp.ndarray, cur: jnp.ndarray,
                        seed: jnp.ndarray, *, tile_b: int,
                        interpret: bool = False):
    """pallas_call wrapper.  Shapes: (n_pad, d_max) tiles, (n_pad,) cur."""
    n_pad, d_max = nbr_lab.shape
    assert n_pad % tile_b == 0, (n_pad, tile_b)
    assert tile_b * d_max * d_max * 4 <= CUBE_LIMIT_BYTES, (tile_b, d_max)
    grid = (n_pad // tile_b,)

    row_spec = pl.BlockSpec((tile_b, d_max), lambda i: (i, 0))
    col_spec = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    seed_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    out_shape = (
        jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),    # best label
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),  # best weight
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),  # weight to current
    )
    best_lab, best_w, cur_w = pl.pallas_call(
        _label_argmax_kernel,
        name="label_argmax",
        grid=grid,
        in_specs=[seed_spec, row_spec, row_spec, row_spec, col_spec],
        out_specs=(col_spec, col_spec, col_spec),
        out_shape=out_shape,
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), nbr_lab, nbr_w, nbr_mask,
      cur.reshape(-1, 1).astype(jnp.int32))
    return best_lab[:, 0], best_w[:, 0], cur_w[:, 0]
