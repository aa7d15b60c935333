"""Parallel Label Propagation (the paper's GVE-LPA core), TPU-native.

The paper's ``lpaMove`` accumulates per-neighbor-community weights in
per-thread hashtables.  Hashtables do not vectorise; the TPU-native
formulation here is **sort + segment-reduce** (the same family of tricks the
paper cites for GPU LPA [Soman & Narang, bitonic sort]):

  1. for every directed edge (u, v, w) form the key pair (u, C[v]);
  2. lexicographically sort edges by that pair (``lax.sort`` with 2 keys —
     no 64-bit packing, so it works under JAX's default 32-bit ints);
  3. segment-sum weights over key runs -> K_{u -> c} for every (u, c) that
     actually occurs;
  4. per-source segment-max over the run sums -> best community weight, with
     deterministic tie-breaks: max weight, then max label-hash (a per-
     iteration integer mix).  The paper's hashtable iteration order is
     effectively random among equal-weight labels; a *fixed* min-label
     tie-break would cascade every unweighted graph into one monster
     community, so we keep randomness but make it a pure function of
     (label, iteration) — bit-reproducible across runs and hosts;
  5. a vertex adopts the best label only if it is *strictly* better connected
     than its current label (prevents synchronous-update oscillation and
     makes runs bit-reproducible — see DESIGN.md §2 "Determinism").

Vertex pruning (the paper's processed/unprocessed flags) is a dense boolean
``active`` mask: masked vertices keep their label; a vertex is reactivated
exactly when a neighbor changed label — identical semantics, SIMD-friendly.

GVE-LPA updates a shared label array in place (asynchronous); a fully
synchronous vectorised sweep instead oscillates and fragments (monster
communities / 2-cycles).  We adopt the *semi-synchronous* scheme the paper
cites (Cordasco & Gargano): vertices are statically split into two hashed
parity classes and each ``lpa_run`` iteration performs one sub-sweep per
class — updates in sweep A are visible to sweep B, recovering most of the
asynchronous behaviour while staying data-parallel and deterministic.

Every sweep body (here, in ``core/split.py``, ``core/batch.py`` and the
tile backend) names its operations with ``jax.named_scope`` so that a
profiler trace can split sweep device time by phase.  The scopes live
only in the compiled program's ``op_name`` metadata; HLO op and module
names, and the run time, are unchanged:

* ``sweep.gather`` — neighbour-indexed reads (``labels[graph.dst]``,
  ``labels[nbr]``, ``best_w[seg_src]``, ...) and the expressions fused
  around them;
* ``sweep.sort`` — the (source, label) sort of ``_scan_communities``;
* ``sweep.reduce`` — the per-row reductions and the adopt rule: segment
  reductions and the Pallas row kernels;
* ``sweep.wake`` — the frontier: changed masks, their counts, and which
  rows run next (``neighbors_of``, ``jnp.any(changed[nbr] & ...)``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.graph import Graph

_NEG = jnp.float32(-1.0)  # weights are positive; -1 marks "no run"


class LpaState(NamedTuple):
    labels: jnp.ndarray    # (n,) int32 community of each vertex
    active: jnp.ndarray    # (n,) bool   unprocessed flags (pruning)
    iteration: jnp.ndarray  # () int32
    delta_n: jnp.ndarray   # () int32   label changes in last iteration


def _scan_communities(graph: Graph, labels: jnp.ndarray,
                      label_bound: jnp.ndarray | int | None = None):
    """Steps 1-3: per-(src, community) connecting weights via sort+segments.

    Returns (run_src, run_label, run_wgt, run_valid), each (m_pad,).

    ``label_bound``: exclusive upper bound on real label *values*, used as
    the padding sentinel.  Defaults to ``graph.n`` — the solo/in-core case
    where labels are vertex ids of this very graph.  The out-of-core
    partition path runs sweeps over compact local row spaces whose labels
    are *global* vertex ids, so the bound there is the full graph's vertex
    count (may be traced; one executable serves every partition).
    """
    n, m_pad = graph.n, graph.m_pad
    bound = n if label_bound is None else label_bound
    with jax.named_scope("sweep.gather"):
        # Padding edges get the label sentinel so they sort last and
        # never match.
        lab_dst = jnp.where(graph.edge_mask, labels[graph.dst],
                            bound).astype(jnp.int32)
        src = jnp.where(graph.edge_mask, graph.src, n).astype(jnp.int32)
    with jax.named_scope("sweep.sort"):
        src_s, lab_s, wgt_s = jax.lax.sort((src, lab_dst, graph.wgt),
                                           num_keys=2)

    with jax.named_scope("sweep.reduce"):
        prev_src = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                    src_s[:-1]])
        prev_lab = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                    lab_s[:-1]])
        is_start = (src_s != prev_src) | (lab_s != prev_lab)
        run_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1  # in [0, R)

        run_wgt = jax.ops.segment_sum(wgt_s, run_id, num_segments=m_pad)
        run_src = jax.ops.segment_max(src_s, run_id, num_segments=m_pad)
        run_lab = jax.ops.segment_max(lab_s, run_id, num_segments=m_pad)
        run_valid = (jax.ops.segment_max(is_start.astype(jnp.int32), run_id,
                                         num_segments=m_pad) > 0)
        run_valid &= (run_lab < bound) & (run_src < n)
    return run_src, run_lab, run_wgt, run_valid


def _label_hash(labels: jnp.ndarray, iteration: jnp.ndarray) -> jnp.ndarray:
    """Deterministic per-iteration label priority (Knuth multiplicative mix)."""
    x = labels.astype(jnp.uint32) * jnp.uint32(2654435761)
    x ^= iteration.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    return x.astype(jnp.int32) & jnp.int32(0x7FFFFFFF)  # non-negative


def neighbors_of(graph: Graph, mask: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of vertices adjacent to any vertex in ``mask``."""
    with jax.named_scope("sweep.wake"):
        return jax.ops.segment_max(
            (mask[graph.dst] & graph.edge_mask).astype(jnp.int32),
            graph.src, num_segments=graph.n) > 0


def lpa_move(graph: Graph, labels: jnp.ndarray, active: jnp.ndarray,
             iteration: jnp.ndarray | int = 0,
             label_bound: jnp.ndarray | int | None = None,
             ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One synchronous LPA sweep (the paper's ``lpaMove``) over ``active``.

    Returns (new_labels, changed_mask, delta_n).  ``label_bound``: see
    :func:`_scan_communities` — only the partition path passes it.
    """
    n = graph.n
    bound = n if label_bound is None else label_bound
    run_src, run_lab, run_wgt, run_valid = _scan_communities(graph, labels,
                                                             label_bound)
    with jax.named_scope("sweep.reduce"):
        # dump invalid runs on a real id
        seg_src = jnp.where(run_valid, run_src, n - 1)
        w = jnp.where(run_valid, run_wgt, _NEG)

        # Step 4: per-source best community weight; tie-break max label
        # hash.
        best_w = jax.ops.segment_max(w, seg_src, num_segments=n)
        with jax.named_scope("sweep.gather"):
            is_best = (run_valid & (run_wgt >= best_w[seg_src])
                       & (best_w[seg_src] > 0))
        run_h = _label_hash(run_lab, jnp.asarray(iteration, jnp.int32))
        best_h = jax.ops.segment_max(jnp.where(is_best, run_h, -1), seg_src,
                                     num_segments=n)
        with jax.named_scope("sweep.gather"):
            pick = is_best & (run_h == best_h[seg_src])
        best_lab = jax.ops.segment_min(jnp.where(pick, run_lab, bound),
                                       seg_src, num_segments=n)

        # Connecting weight to the *current* community (keep unless
        # strictly worse).
        with jax.named_scope("sweep.gather"):
            to_cur = run_valid & (run_lab == labels[seg_src])
        cur_w = jax.ops.segment_max(jnp.where(to_cur, run_wgt, _NEG),
                                    seg_src, num_segments=n)

        adopt = (active & (best_lab < bound)
                 & (best_w > jnp.maximum(cur_w, 0.0)))
        new_labels = jnp.where(adopt, best_lab.astype(labels.dtype), labels)
    with jax.named_scope("sweep.wake"):
        changed = new_labels != labels
        delta_n = jnp.sum(changed.astype(jnp.int32))
    return new_labels, changed, delta_n


@partial(jax.jit, static_argnames=("max_iterations", "profile"))
def lpa_run(graph: Graph, tau: float = 0.05, max_iterations: int = 20,
            init_labels: jnp.ndarray | None = None,
            n_real: jnp.ndarray | None = None,
            init_active: jnp.ndarray | None = None,
            profile: bool = False):
    """Run LPA to convergence: ``delta_n / n <= tau`` or iteration cap.

    Faithful to Algorithm 3 lines 1-6 (the propagation phase of GSL-LPA).

    ``n_real``: optional traced scalar with the *unpadded* vertex count.
    The engine's shape-bucketed path pads graphs with isolated vertices up
    to a bucket size; those vertices can never change label, but the
    convergence threshold must still be ``tau * n_real``, not
    ``tau * n_bucket`` — passing it as a traced value keeps one compiled
    executable valid for every graph in the bucket.

    ``init_active``: optional (n,) seed for the unprocessed flags —
    GVE-LPA's pruning rule for incremental re-detection: after an edge
    delta, only the vertices whose neighborhoods changed (the affected
    frontier) start unprocessed; everything else sleeps until a neighbor
    actually changes label.  Default: all vertices unprocessed (a full
    cold/warm detection sweep).

    ``profile`` (static): additionally carry a ``(2 * max_iterations, 3)``
    int32 buffer through the loop, writing per sub-sweep at row
    ``2*it + sweep``: [candidate count, changed count, sub-sweep index].
    The buffer never feeds back into labels or the convergence test, so
    profiled runs are bit-identical; the caller fetches it once after
    convergence (no host sync in here — R001 discipline).  Returns
    ``(LpaState, buffer)`` instead of the bare state.
    """
    n = graph.n
    labels0 = (jnp.arange(n, dtype=jnp.int32) if init_labels is None
               else init_labels.astype(jnp.int32))
    active0 = (jnp.ones(n, dtype=bool) if init_active is None
               else init_active.astype(bool))
    state = LpaState(labels=labels0, active=active0,
                     iteration=jnp.int32(0), delta_n=jnp.int32(n))

    if n_real is None:
        threshold = jnp.int32(tau * n)
    else:
        threshold = (jnp.float32(tau)
                     * n_real.astype(jnp.float32)).astype(jnp.int32)

    # Static hashed parity classes for the semi-synchronous sub-sweeps.
    parity = (_label_hash(jnp.arange(n, dtype=jnp.int32), jnp.int32(-1))
              & 1).astype(bool)
    # Profile counts describe the *graph's* frontier, not the padded
    # executable's: mask bucket-padding vertices out of the candidate tally.
    real = (jnp.ones(n, dtype=bool) if n_real is None
            else jnp.arange(n, dtype=jnp.int32) < n_real)

    def cond(carry):
        s = carry[0] if profile else carry
        return (s.delta_n > threshold) & (s.iteration < max_iterations)

    def body(carry):
        s, buf = carry if profile else (carry, None)
        labels, active = s.labels, s.active
        dn_total = jnp.int32(0)
        for sweep, klass in enumerate((~parity, parity)):
            with jax.named_scope("sweep.wake"):
                cand = active & klass
            labels, changed, dn = lpa_move(graph, labels, cand,
                                           2 * s.iteration + sweep)
            # pruning: processed vertices sleep; neighbors of changed wake up
            with jax.named_scope("sweep.wake"):
                active = (active & ~cand) | neighbors_of(graph, changed)
                dn_total = dn_total + dn
            if profile:
                row = 2 * s.iteration + sweep
                buf = buf.at[row].set(jnp.stack(
                    [jnp.sum((cand & real).astype(jnp.int32)), dn, row]))
        nxt = LpaState(labels, active, s.iteration + 1, dn_total)
        return (nxt, buf) if profile else nxt

    if profile:
        buf0 = jnp.full((2 * max_iterations, 3), -1, jnp.int32)
        return jax.lax.while_loop(cond, body, (state, buf0))
    return jax.lax.while_loop(cond, body, state)


def lpa_move_reference(graph: Graph, labels: jnp.ndarray, active: jnp.ndarray,
                       iteration: jnp.ndarray | int = 0):
    """O(n * n) dense oracle of ``lpa_move`` for small-graph tests.

    Builds the full (n, n) vertex x community weight matrix:
    W[i, c] = sum of w(i,j) over neighbors j with C[j] = c.
    """
    n = graph.n
    w_ic = jnp.zeros((n, n), dtype=jnp.float32)
    lab_dst = labels[graph.dst]
    flat = graph.src * n + lab_dst
    w_ic = w_ic.reshape(-1).at[flat].add(
        jnp.where(graph.edge_mask, graph.wgt, 0.0)).reshape(n, n)
    best_w = jnp.max(w_ic, axis=1)
    # same tie-break as lpa_move: max weight, then max label hash
    is_best = (w_ic >= best_w[:, None]) & (best_w[:, None] > 0)
    h = _label_hash(jnp.arange(n, dtype=jnp.int32),
                    jnp.asarray(iteration, jnp.int32))
    best_h = jnp.max(jnp.where(is_best, h[None, :], -1), axis=1)
    pick = is_best & (h[None, :] == best_h[:, None])
    best_lab = jnp.argmax(pick, axis=1).astype(labels.dtype)
    cur_w = jnp.take_along_axis(w_ic, labels[:, None].astype(jnp.int32),
                                axis=1)[:, 0]
    adopt = active & (best_w > cur_w) & (best_w > 0)
    new_labels = jnp.where(adopt, best_lab, labels)
    changed = new_labels != labels
    return new_labels, changed, jnp.sum(changed.astype(jnp.int32))
