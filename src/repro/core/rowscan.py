"""Segmented scans over the row blocks of an edge order.

The segment backend's sweeps reduce edge-indexed values per row: per
source, per (source, label) run, per CSR row.  Both edge orders they use
keep each row's edges together, at positions ``[row_ptr[u],
row_ptr[u+1])``: the CSR order of ``Graph`` (``src`` is the expansion of
``row_ptr``; padding edges follow the last row), and the (source, label)
sorted order of ``core.lpa``, where padding edges sort last.  So a
per-row reduction is a scan that restarts at every block, read at the
row's last position, and a per-row value reaches its row's positions by
a prefix sum: nothing is scattered or gathered with one index per edge.

Blocks are named by a non-negative *key* per position, equal within a
block and different between neighbouring blocks (the row id, or the
position where a run starts).  A value only ever meets values of its own
block.  Sums are therefore formed within a block alone, never as
differences of a global prefix sum (inexact for float weights, and for
unit weights once the running total passes 2^24).

A scan takes doubling steps (Hillis-Steele) along the 128 lanes of a
(blocks, 128) view, then joins each 128-lane block to the scan of the
blocks before it, read at their far ends; that scan is the same one, one
level down (2^23 positions: 65,536 blocks, then 512, scanned by doubling
steps alone).  A step is one elementwise pass with a shift, in a loop:
the program holds one step per level, so it compiles about as quickly as
the scatter it replaces.  (A reduce-window with a custom combine takes the
TPU compiler over a minute inside a loop; the steps unrolled take the CPU
compiler seconds a sweep.)

Every operation here is named under the scope ``rowscan``, inside the
caller's ``sweep.*`` scope.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_LANES = 128
_NO_KEY = -1  # the key of padding positions: no real block's


def _scoped(fn):
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("rowscan"):
            return fn(*args, **kwargs)
    return scoped


def _shift(x, k, fill, reverse):
    """``x`` moved ``k`` places later along its last axis (earlier, in
    reverse), ``fill`` in the places left open; ``k`` may be traced."""
    width = x.shape[-1]
    pad = jnp.full(x.shape[:-1] + (width,), fill, x.dtype)
    if reverse:
        return lax.dynamic_slice_in_dim(
            jnp.concatenate([x, pad], axis=-1), k, width, axis=-1)
    return lax.dynamic_slice_in_dim(
        jnp.concatenate([pad, x], axis=-1), width - k, width, axis=-1)


def _doubling(op, identity, keys, values, reverse):
    """Inclusive scan along the last axis by doubling steps (a loop, so
    the program holds one step however wide the axis); with ``keys`` a
    step joins only values of equal keys."""
    def step(i, values):
        k = jnp.left_shift(jnp.int32(1), i)
        joined = op(_shift(values, k, identity, reverse), values)
        if keys is None:
            return joined
        return jnp.where(_shift(keys, k, _NO_KEY - 1, reverse) == keys,
                         joined, values)

    return lax.fori_loop(0, (values.shape[-1] - 1).bit_length(), step,
                         values)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _scan(op, identity, keys, values, reverse):
    """Compiled once per shape, also where a sweep runs op by op (the
    doubling loop's body is a new function every trace)."""
    m = values.shape[0]
    if m <= 8 * _LANES:
        return _doubling(op, identity, keys, values, reverse)
    blocks = -(-m // _LANES)
    extra = blocks * _LANES - m
    if extra:
        values = jnp.concatenate([values, jnp.full((extra,), identity,
                                                   values.dtype)])
        if keys is not None:
            keys = jnp.concatenate([keys, jnp.full((extra,), _NO_KEY,
                                                   keys.dtype)])
    values = values.reshape(blocks, _LANES)
    keys = None if keys is None else keys.reshape(blocks, _LANES)
    local = _doubling(op, identity, keys, values, reverse)
    # each block joins the scan of the blocks before it (after it, in
    # reverse), read at their far ends
    end = 0 if reverse else -1
    far_keys = None if keys is None else keys[:, end]
    carry = _shift(_scan(op, identity, far_keys, local[:, end], reverse),
                   1, identity, reverse)[:, None]
    joined = op(carry, local)
    if keys is not None:
        carry_keys = _shift(far_keys, 1, _NO_KEY - 1, reverse)[:, None]
        joined = jnp.where(carry_keys == keys, joined, local)
    return joined.reshape(-1)[:m]


@_scoped
def segmented_scan(keys: jnp.ndarray | None, values: jnp.ndarray, op,
                   identity, *, reverse: bool = False) -> jnp.ndarray:
    """Inclusive scan of ``values`` within blocks of equal ``keys``.

    ``keys``: (m,) non-negative ints, one run of positions per block; None
    scans the whole array as one block.  ``op``: an associative,
    commutative binary function (``jnp.maximum``, ``jnp.minimum``,
    ``jnp.add``) with ``identity``, a constant.  With ``reverse`` each
    position gets its block's values from itself to the block's end.
    """
    values = jnp.asarray(values)
    identity = np.asarray(identity, values.dtype)[()]
    keys = None if keys is None else keys.astype(jnp.int32)
    return _scan(op, identity, keys, values, reverse)


@_scoped
def block_total(keys: jnp.ndarray, values: jnp.ndarray, op,
                identity) -> jnp.ndarray:
    """Each position's whole-block reduction, for an idempotent ``op``
    (max, min): the forward and reverse scans joined."""
    return op(segmented_scan(keys, values, op, identity),
              segmented_scan(keys, values, op, identity, reverse=True))


@_scoped
def run_keys(is_start: jnp.ndarray) -> jnp.ndarray:
    """Block keys from block-start flags: the position where each
    position's block starts."""
    pos = jnp.arange(is_start.shape[0], dtype=jnp.int32)
    return segmented_scan(None, jnp.where(is_start, pos, 0), jnp.maximum, 0)


@_scoped
def row_keys(graph) -> jnp.ndarray:
    """Block keys of a ``Graph``'s CSR order: each real edge's row, and
    ``n`` for the padding edges after the last row."""
    return jnp.where(graph.edge_mask, graph.src, graph.n).astype(jnp.int32)


@_scoped
def at_row_ends(x: jnp.ndarray, row_ptr: jnp.ndarray, fill) -> jnp.ndarray:
    """``x`` at each row's last position (an ``n``-sized read); rows
    without positions get ``fill``."""
    lo, hi = row_ptr[:-1], row_ptr[1:]
    end = jnp.maximum(hi - 1, 0)
    return jnp.where(hi > lo, x.at[end].get(indices_are_sorted=True,
                                            mode="promise_in_bounds"), fill)


@_scoped
def row_reduce(keys: jnp.ndarray, values: jnp.ndarray, row_ptr: jnp.ndarray,
               op, identity) -> jnp.ndarray:
    """(n,) reduction of ``values`` over each row's positions
    ``[row_ptr[u], row_ptr[u+1])``; ``keys`` name the rows' blocks.
    Rows without positions get ``identity``."""
    return at_row_ends(segmented_scan(keys, values, op, identity), row_ptr,
                       identity)


@_scoped
def row_broadcast(x: jnp.ndarray, row_ptr: jnp.ndarray, m: int) -> jnp.ndarray:
    """(m,) int32: ``x[u]`` at every position of row ``u`` (``x[src]``
    without the gather).

    Each row's difference from the row before is added at the row's first
    position (``n`` updates, rows without positions add theirs at the
    next row's start or beyond ``m``, where they are dropped), and a prefix
    sum carries it along the row.  Wrapping int32 arithmetic makes the sum
    of differences exact.  Positions after the last row hold the last
    row's value.
    """
    x = x.astype(jnp.int32)
    delta = x - jnp.concatenate([jnp.zeros((1,), jnp.int32), x[:-1]])
    starts = jnp.zeros((m,), jnp.int32).at[row_ptr[:-1]].add(
        delta, indices_are_sorted=True, mode="drop")
    return segmented_scan(None, starts, jnp.add, 0)

