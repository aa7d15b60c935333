"""Pallas TPU kernels: fused wake + LPA-move and wake + min-label sweeps.

The unfused hot loop pays two dispatches per sub-sweep with the (B, D)
neighbor tiles round-tripping through HBM between them: ``label_argmax``
reads label/weight/mask tiles (9 B/cell) and a second wake pass re-reads
the changed/mask tiles (2 B/cell).  The move and split *phases* are
sequential by construction (split consumes the converged move labels), so
the fusion that actually removes HBM traffic is per-phase: fold the wake
reduction, the active-set update, and the adopt rule into the same grid
sweep that already holds the tiles in VMEM.

This requires the lazy-wake loop form (the wake for sweep *k* is applied
at the start of sweep *k+1* from the carried changed mask) — the exact
restructure the out-of-core driver already uses, proven bit-identical:
labels and iteration counts depend only on the per-sweep ``dn`` and the
active sequence, both unchanged under the reordering.

Per-sub-sweep HBM tile traffic (B*D cells dominate; columns are O(B)):

    move:  fused 10 B/cell (lab 4 + w 4 + mask 1 + changed 1)
           vs. separate 11 B/cell (argmax 9 + wake changed 1 + mask 1)
    split (lpp): fused 10 B/cell (lab 4 + comm 4 + mask 1 + changed 1)
           vs. separate 11 B/cell (min_label 9 + wake changed 1 + same 1)

Block layout matches ``label_argmax``: grid over row tiles, (TILE_B, D)
row tiles + (TILE_B, 1) state columns; the equality cube stays under
``tiling.CUBE_LIMIT_BYTES`` (asserted below, checked by R004).

Tie-breaks and the adopt rule are shared with the standalone kernels via
``argmax_tile_math`` so float sums are bit-identical across paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.label_argmax import argmax_tile_math
from repro.kernels.tiling import CUBE_LIMIT_BYTES

_SENTINEL = 2147483647  # python literal: materialised in-trace, not captured


def _fused_move_kernel(seed_ref, lab_ref, w_ref, mask_ref, chg_ref,
                       cur_ref, active_ref, candp_ref, klass_ref, real_ref,
                       new_ref, act_ref):
    lab = lab_ref[...]                                   # (B, D) int32
    mask = mask_ref[...]                                 # (B, D) bool

    # Lazy wake: apply the previous sub-sweep's changed mask, retire its
    # candidate set, then pick this sub-sweep's candidates.
    wake = jnp.any(chg_ref[...] & mask, axis=1, keepdims=True)   # (B, 1)
    act = (active_ref[...] & ~candp_ref[...]) | (wake & real_ref[...])
    cand = act & klass_ref[...]

    cur = cur_ref[...]                                   # (B, 1)
    best_lab, best_w, cur_w = argmax_tile_math(
        lab, w_ref[...], mask, cur, seed_ref[0, 0])
    adopt = cand & (best_w > jnp.maximum(cur_w, 0.0))

    new_ref[...] = jnp.where(adopt, best_lab, cur)
    act_ref[...] = act


def fused_move_pallas(nbr_lab: jnp.ndarray, nbr_w: jnp.ndarray,
                      nbr_mask: jnp.ndarray, chg_nbr: jnp.ndarray,
                      cur: jnp.ndarray, active: jnp.ndarray,
                      cand_prev: jnp.ndarray, klass: jnp.ndarray,
                      real: jnp.ndarray, seed: jnp.ndarray, *, tile_b: int,
                      interpret: bool = False):
    """One-dispatch wake + move.  Row tiles (n_pad, d_max); state (n_pad,).

    Returns (new_labels, active_out), each (n_pad,).  ``chg_nbr`` is the
    previous sub-sweep's changed mask gathered to neighbor slots;
    ``cand_prev`` that sub-sweep's candidate set (zeros on the first).
    """
    n_pad, d_max = nbr_lab.shape
    assert n_pad % tile_b == 0, (n_pad, tile_b)
    assert tile_b * d_max * d_max * 4 <= CUBE_LIMIT_BYTES, (tile_b, d_max)
    grid = (n_pad // tile_b,)

    row_spec = pl.BlockSpec((tile_b, d_max), lambda i: (i, 0))
    col_spec = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    seed_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    def col(x, dtype):
        return x.reshape(-1, 1).astype(dtype)

    new, act = pl.pallas_call(
        _fused_move_kernel,
        name="fused_move",
        grid=grid,
        in_specs=[seed_spec, row_spec, row_spec, row_spec, row_spec,
                  col_spec, col_spec, col_spec, col_spec, col_spec],
        out_specs=(col_spec, col_spec),
        out_shape=(jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.bool_)),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), nbr_lab, nbr_w, nbr_mask,
      chg_nbr, col(cur, jnp.int32), col(active, jnp.bool_),
      col(cand_prev, jnp.bool_), col(klass, jnp.bool_),
      col(real, jnp.bool_))
    return new[:, 0], act[:, 0]


def _fused_split_prune_kernel(lab_ref, comm_ref, mask_ref, chg_ref,
                              cur_ref, scomm_ref, new_ref):
    same = mask_ref[...] & (comm_ref[...] == scomm_ref[...])   # (B, D)
    # Lazy wake over same-community edges; rows not woken keep their label
    # (the lpp prune).  First iteration passes chg = ones: rows with no
    # same-community neighbor reduce to their own label anyway, so the
    # result matches the eager active0 = ones initialisation bit-for-bit.
    wake = jnp.any(chg_ref[...] & same, axis=1, keepdims=True)  # (B, 1)
    cand = jnp.where(same, lab_ref[...], _SENTINEL)
    cur = cur_ref[...]
    mres = jnp.minimum(cur, jnp.min(cand, axis=1, keepdims=True))
    new_ref[...] = jnp.where(wake, mres, cur)


def _fused_split_kernel(lab_ref, comm_ref, mask_ref, cur_ref, scomm_ref,
                        new_ref):
    same = mask_ref[...] & (comm_ref[...] == scomm_ref[...])   # (B, D)
    cand = jnp.where(same, lab_ref[...], _SENTINEL)
    new_ref[...] = jnp.minimum(cur_ref[...],
                               jnp.min(cand, axis=1, keepdims=True))


def fused_split_pallas(nbr_lab: jnp.ndarray, nbr_comm: jnp.ndarray,
                       nbr_mask: jnp.ndarray, chg_nbr: jnp.ndarray,
                       self_lab: jnp.ndarray, self_comm: jnp.ndarray, *,
                       prune: bool, tile_b: int,
                       interpret: bool = False) -> jnp.ndarray:
    """One-dispatch split-wake + min-label.  Returns new labels (n_pad,).

    ``chg_nbr`` is last iteration's changed mask gathered to neighbor
    slots (ones on the first iteration); ignored when ``prune`` is False
    (the lp mode has no active-set prune, so the wake leg is dropped and
    its tile is never read).
    """
    n_pad, d_max = nbr_lab.shape
    assert n_pad % tile_b == 0, (n_pad, tile_b)
    grid = (n_pad // tile_b,)
    row_spec = pl.BlockSpec((tile_b, d_max), lambda i: (i, 0))
    col_spec = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))

    def col(x):
        return x.reshape(-1, 1).astype(jnp.int32)

    if prune:
        kernel = _fused_split_prune_kernel
        in_specs = [row_spec, row_spec, row_spec, row_spec,
                    col_spec, col_spec]
        operands = (nbr_lab, nbr_comm, nbr_mask, chg_nbr,
                    col(self_lab), col(self_comm))
    else:
        kernel = _fused_split_kernel
        in_specs = [row_spec, row_spec, row_spec, col_spec, col_spec]
        operands = (nbr_lab, nbr_comm, nbr_mask,
                    col(self_lab), col(self_comm))
    out = pl.pallas_call(
        kernel,
        name="fused_split",
        grid=grid,
        in_specs=in_specs,
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(*operands)
    return out[:, 0]
