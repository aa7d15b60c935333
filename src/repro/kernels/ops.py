"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled kernels run natively; elsewhere the
default is the pure-jnp oracle (fast XLA:CPU path) with ``interpret=True``
Pallas execution available for kernel-body validation (used by tests).

VMEM budgeting: the label_argmax equality cube costs TILE_B * D * D * 4
bytes; ``kernels/tiling.pick_tile_b`` sizes TILE_B (a multiple of 8, the
sublane count Mosaic requires, targeting a 4 MB cube).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_sweep import fused_move_pallas, fused_split_pallas
from repro.kernels.label_argmax import label_argmax_pallas
from repro.kernels.min_label import min_label_pallas
from repro.kernels.tiling import pick_tile_b

__all__ = ["pick_tile_b", "label_argmax", "min_label", "fused_move",
           "fused_split", "resolve_fuse", "flash_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_fuse(fuse_sweeps: str, kernel_mode: str) -> bool:
    """Resolve ``EngineConfig.fuse_sweeps`` against the kernel dispatch.

    'auto' fuses only when a real Pallas kernel body executes (pallas on
    TPU, or explicit interpret mode); the jnp oracle path gains nothing
    from fusion — XLA already fuses the elementwise glue — and stays the
    default-dispatch parity reference.
    """
    if fuse_sweeps == "off":
        return False
    if fuse_sweeps == "on":
        return True
    mode = kernel_mode
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    return mode in ("pallas", "interpret")


@partial(jax.jit, static_argnames=("mode",))
def label_argmax(nbr_lab, nbr_w, nbr_mask, cur, seed, mode: str = "auto"):
    """Best community label per padded row (see kernels/label_argmax.py).

    mode: 'auto' (pallas on TPU, ref elsewhere), 'pallas', 'interpret', 'ref'.
    Returns (best_label, best_weight, current_weight), each (n_pad,).
    """
    n_pad, d_max = nbr_lab.shape
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.label_argmax_ref(nbr_lab, nbr_w, nbr_mask, cur, seed)
    tile_b = pick_tile_b(n_pad, d_max)
    return label_argmax_pallas(nbr_lab, nbr_w, nbr_mask, cur,
                               jnp.asarray(seed, jnp.int32), tile_b=tile_b,
                               interpret=(mode == "interpret"))


@partial(jax.jit, static_argnames=("causal", "mode"))
def flash_attention(q, k, v, causal: bool = True, mode: str = "auto"):
    """Flash attention (kernels/flash_attention.py).

    q: (B, S, H, hd); k/v: (B, S_kv, K, hd) — the models' layout; padding to
    block multiples handled here (padded KV positions are masked by the
    causal/softmax math: they sort above the diagonal or contribute
    exp(-inf)=0 via the -inf pad of q... padded q rows are sliced off).
    mode: 'auto' (pallas on TPU, XLA oracle elsewhere) | 'interpret' | 'ref'.
    """
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import chunked_attention

    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    if mode == "ref":
        pos_q = jnp.arange(sq, dtype=jnp.int32)
        pos_k = jnp.arange(skv, dtype=jnp.int32)
        return chunked_attention(q, k, v, pos_q, pos_k, causal=causal,
                                 chunk=min(512, skv))
    bq = bk = 256
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pk and not causal:
        # padded KV under full attention would leak mass; encoders use
        # block-multiple lengths — fall back to the oracle otherwise
        pos_q = jnp.arange(sq, dtype=jnp.int32)
        pos_k = jnp.arange(skv, dtype=jnp.int32)
        return chunked_attention(q, k, v, pos_q, pos_k, causal=causal,
                                 chunk=min(512, skv))
    qt = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))), 2, 1)
    kt = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))), 2, 1)
    vt = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))), 2, 1)
    out = flash_attention_pallas(qt, kt, vt, causal=causal, block_q=bq,
                                 block_k=bk,
                                 interpret=(mode == "interpret"))
    return jnp.moveaxis(out, 1, 2)[:, :sq]


@partial(jax.jit, static_argnames=("mode",))
def min_label(nbr_lab, nbr_comm, nbr_mask, self_lab, self_comm,
              mode: str = "auto"):
    """Split-phase same-community neighbor min (see kernels/min_label.py)."""
    n_pad, d_max = nbr_lab.shape
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.min_label_ref(nbr_lab, nbr_comm, nbr_mask, self_lab,
                                 self_comm)
    tile_b = pick_tile_b(n_pad, d_max)
    return min_label_pallas(nbr_lab, nbr_comm, nbr_mask, self_lab, self_comm,
                            tile_b=tile_b, interpret=(mode == "interpret"))


@partial(jax.jit, static_argnames=("mode",))
def fused_move(nbr_lab, nbr_w, nbr_mask, chg_nbr, cur, active, cand_prev,
               klass, real, seed, mode: str = "auto"):
    """One-dispatch lazy-wake + LPA move (see kernels/fused_sweep.py).

    ``chg_nbr`` is the previous sub-sweep's changed mask gathered to
    neighbor slots; ``cand_prev`` its candidate set (zeros on the first
    sub-sweep).  Returns (new_labels, active_out), each (n_pad,).
    """
    n_pad, d_max = nbr_lab.shape
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.fused_move_ref(nbr_lab, nbr_w, nbr_mask, chg_nbr, cur,
                                  active, cand_prev, klass, real, seed)
    tile_b = pick_tile_b(n_pad, d_max)
    return fused_move_pallas(nbr_lab, nbr_w, nbr_mask, chg_nbr, cur, active,
                             cand_prev, klass, real,
                             jnp.asarray(seed, jnp.int32), tile_b=tile_b,
                             interpret=(mode == "interpret"))


@partial(jax.jit, static_argnames=("prune", "mode"))
def fused_split(nbr_lab, nbr_comm, nbr_mask, chg_nbr, self_lab, self_comm,
                prune: bool = True, mode: str = "auto"):
    """One-dispatch lazy split-wake + min-label (kernels/fused_sweep.py).

    ``chg_nbr`` is last iteration's changed mask gathered to neighbor
    slots (ones on the first iteration); ignored when ``prune`` is False.
    """
    n_pad, d_max = nbr_lab.shape
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        return ref.fused_split_ref(nbr_lab, nbr_comm, nbr_mask, chg_nbr,
                                   self_lab, self_comm, prune)
    tile_b = pick_tile_b(n_pad, d_max)
    return fused_split_pallas(nbr_lab, nbr_comm, nbr_mask, chg_nbr,
                              self_lab, self_comm, prune=prune,
                              tile_b=tile_b,
                              interpret=(mode == "interpret"))
