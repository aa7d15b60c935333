"""Pallas TPU kernel: the Split-Last min-label sweep (Algorithm 1 body).

Per vertex row: the minimum label among same-community neighbors, folded
with the vertex's own label.  Pure VPU work — a masked row-min over a
(TILE_B, D) tile.  The neighbor label/community gathers happen outside (XLA
gather from HBM); the kernel fuses mask construction + reduction so the
(B, D) intermediates never round-trip to HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SENTINEL = 2147483647  # python literal: materialised in-trace, not captured


def _min_label_kernel(nbr_lab_ref, nbr_comm_ref, mask_ref, self_lab_ref,
                      self_comm_ref, out_ref):
    nl = nbr_lab_ref[...]        # (B, D) int32: L[nbr]
    nc = nbr_comm_ref[...]       # (B, D) int32: C[nbr]
    ok = mask_ref[...] & (nc == self_comm_ref[...])   # same-community & real
    cand = jnp.where(ok, nl, _SENTINEL)
    out_ref[...] = jnp.minimum(self_lab_ref[...],
                               jnp.min(cand, axis=1, keepdims=True))


def min_label_pallas(nbr_lab: jnp.ndarray, nbr_comm: jnp.ndarray,
                     nbr_mask: jnp.ndarray, self_lab: jnp.ndarray,
                     self_comm: jnp.ndarray, *, tile_b: int,
                     interpret: bool = False) -> jnp.ndarray:
    n_pad, d_max = nbr_lab.shape
    assert n_pad % tile_b == 0, (n_pad, tile_b)
    grid = (n_pad // tile_b,)
    row_spec = pl.BlockSpec((tile_b, d_max), lambda i: (i, 0))
    col_spec = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _min_label_kernel,
        name="min_label",
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, col_spec, col_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(nbr_lab, nbr_comm, nbr_mask, self_lab.reshape(-1, 1).astype(jnp.int32),
      self_comm.reshape(-1, 1).astype(jnp.int32))
    return out[:, 0]
