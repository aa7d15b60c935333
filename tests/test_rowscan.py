"""Segmented scans over row blocks (``core.rowscan``) against the
segment reductions and gathers they replace, on every CSR shape the
segment backend sweeps: plain graphs with empty rows, pow2 buckets
(isolated vertices, masked edges with ``src = 0`` after the last row),
out-of-core partition slices (halo rows with empty ranges) and packed
batches."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import random_graph
from repro.core import GraphBatch, rowscan
from repro.core.graph import build_graph
from repro.engine.backends.segment import SegmentBackend
from repro.engine.bucketing import bucket_for, pad_graph
from repro.engine.config import EngineConfig
from repro.partition.plan import attach_halos, plan_partitions
from repro.partition.slices import InMemorySource, load_partition

F32_MIN = np.finfo(np.float32).min


def _sequential(keys, values, op, identity, reverse=False):
    """Left fold within each block of equal keys, in float32/int32."""
    keys, values = np.asarray(keys), np.asarray(values)
    order = range(len(keys) - 1, -1, -1) if reverse else range(len(keys))
    out = np.empty_like(values)
    acc, prev = None, None
    for i in order:
        if keys[i] != prev:
            acc, prev = values.dtype.type(identity), keys[i]
        acc = values.dtype.type(op(acc, values[i]))
        out[i] = acc
    return out


def _graph_with_empty_rows(seed):
    """Vertices 0, 7, the last three and every fifth are edgeless."""
    rng = np.random.default_rng(seed)
    n = 60
    idle = {0, 7, n - 1, n - 2, n - 3} | set(range(5, n, 5))
    live = np.array([v for v in range(n) if v not in idle])
    e = rng.choice(live, size=(150, 2))
    return build_graph(e, n=n)


def _partition_graphs(seed):
    """Every slice of a 4-way split, padded as the segment backend pads
    it: owned rows, then halo rows without positions."""
    g = random_graph(90, 4.0, seed=seed)
    source = InMemorySource(g)
    plan = attach_halos(plan_partitions(np.asarray(g.row_ptr),
                                        num_partitions=4),
                        lambda lo, hi: source.window("dst", lo, hi))
    slices = [load_partition(source, p) for p in plan.parts]
    shapes = SimpleNamespace(n_loc=max(s.n_local for s in slices) + 3,
                             m=max(len(s.src) for s in slices) + 40)
    backend = SegmentBackend()
    return [backend.prepare_partition(s, shapes, EngineConfig())[0]
            for s in slices]


def _csr_cases():
    g = random_graph(70, 3.0, seed=11)
    gw = random_graph(50, 5.0, seed=4, weighted=True)
    cases = [("empty_rows", _graph_with_empty_rows(3)),
             ("plain", g),
             ("pow2_bucket", pad_graph(g, bucket_for(g))),
             ("weighted_bucket", pad_graph(gw, bucket_for(gw)))]
    cases += [(f"partition{i}", pg) for i, pg in enumerate(_partition_graphs(2))]
    batch = GraphBatch.pack([random_graph(n, 3.0, seed=n) for n in (9, 1, 40, 17)]
                            + [build_graph(np.zeros((0, 2), int), n=3)])
    cases.append(("batch", batch.graph))
    cases.append(("batch_bucket", pad_graph(batch.graph,
                                            bucket_for(batch.graph))))
    return cases


CSR_CASES = _csr_cases()
CSR_IDS = [name for name, _ in CSR_CASES]


@pytest.mark.parametrize("m", [1, 5, 127, 128, 129, 1000, 128 * 128 + 77])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("kind", ["max", "min", "add"])
def test_segmented_scan_matches_sequential(kind, reverse, m):
    """Blocks of every length (one position up to several 128-lane blocks,
    across the two join levels), each scanned on its own."""
    rng = np.random.default_rng(m)
    keys = np.sort(rng.integers(0, max(m // 6, 1) + 1, m)).astype(np.int32)
    keys[m // 2:] += rng.integers(0, 2)  # one block may cross the middle
    jop, op, identity = {"max": (jnp.maximum, np.maximum, -1),
                         "min": (jnp.minimum, np.minimum, 2 ** 31 - 1),
                         "add": (jnp.add, np.add, 0)}[kind]
    values = rng.integers(0, 1000, m).astype(np.int32)
    got = rowscan.segmented_scan(jnp.asarray(keys), jnp.asarray(values),
                                 jop, identity, reverse=reverse)
    want = _sequential(keys, values, op, identity, reverse)
    assert np.array_equal(np.asarray(got), want)


def test_segmented_sum_exact_for_integer_weights():
    """Integer-valued float32 weights sum exactly within each run, also
    where the total before a run is far past 2^24."""
    rng = np.random.default_rng(0)
    m = 3 * 128 * 128 + 11
    keys = np.sort(rng.integers(0, 4000, m)).astype(np.int32)
    w = rng.integers(1, 4000, m).astype(np.float32)
    assert w.astype(np.float64).sum() > 2 ** 24 * 4
    got = np.asarray(rowscan.segmented_scan(jnp.asarray(keys), jnp.asarray(w),
                                            jnp.add, 0.0))
    want = _sequential(keys, w.astype(np.float64), np.add, 0.0)
    assert np.array_equal(got, want.astype(np.float32))
    seg = jax.ops.segment_sum(jnp.asarray(w), jnp.asarray(keys),
                              num_segments=4000)
    ends = np.r_[keys[1:] != keys[:-1], True]
    assert np.array_equal(got[ends], np.asarray(seg)[keys[ends]])


def test_segmented_sum_stays_within_its_run():
    """With non-integer weights each run's sum is its own float32 sum:
    within float32 rounding of the run's exact sum, whatever came before.
    A difference of global prefix sums fails the same bound here (runs of
    small weights after a run of large ones)."""
    rng = np.random.default_rng(1)
    sizes = rng.integers(1, 300, 400)
    keys = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    w = rng.uniform(0.01, 1.0, len(keys)).astype(np.float32)
    w[keys % 7 == 0] *= 1e6
    got = np.asarray(rowscan.segmented_scan(jnp.asarray(keys), jnp.asarray(w),
                                            jnp.add, 0.0))
    ends = np.cumsum(sizes) - 1
    exact = np.add.reduceat(w.astype(np.float64), np.r_[0, ends[:-1] + 1])
    seq = _sequential(keys, w, np.add, 0.0)[ends]
    bound = sizes * np.finfo(np.float32).eps * exact
    assert np.all(np.abs(got[ends] - exact) <= bound)
    assert np.all(np.abs(seq - exact) <= bound)
    prefix = np.cumsum(w, dtype=np.float32)
    diff = prefix[ends] - np.r_[0, prefix[ends[:-1]]]
    assert np.any(np.abs(diff - exact) > bound)


@pytest.mark.parametrize("name,graph", CSR_CASES, ids=CSR_IDS)
@pytest.mark.parametrize("kind", ["max", "min", "sum"])
def test_row_reduce_matches_segment_ops(name, graph, kind):
    """Per-row reductions over CSR positions equal the ``segment_*`` ops
    over ``graph.src`` (masked edges contribute the identity; rows
    without edges read the identity)."""
    rng = np.random.default_rng(len(name))
    vals = rng.integers(0, 50, graph.m_pad).astype(np.int32)
    keys = rowscan.row_keys(graph)
    jop, seg, identity = {
        "max": (jnp.maximum, jax.ops.segment_max, -1),
        "min": (jnp.minimum, jax.ops.segment_min, 2 ** 31 - 1),
        "sum": (jnp.add, jax.ops.segment_sum, 0)}[kind]
    v = jnp.where(graph.edge_mask, jnp.asarray(vals), identity)
    got = rowscan.row_reduce(keys, v, graph.row_ptr, jop, identity)
    want = seg(v, graph.src, num_segments=graph.n)
    deg = np.asarray(graph.row_ptr[1:] - graph.row_ptr[:-1])
    want = np.where(deg > 0, np.asarray(want), identity)
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("name,graph", CSR_CASES, ids=CSR_IDS)
def test_row_broadcast_matches_gather(name, graph):
    """``row_broadcast(x)`` is ``x[src]`` at every real edge, for any
    int32 values (differences wrap)."""
    rng = np.random.default_rng(7)
    x = rng.integers(-2 ** 31, 2 ** 31 - 1, graph.n, dtype=np.int64)
    x = jnp.asarray(x.astype(np.int32))
    got = np.asarray(rowscan.row_broadcast(x, graph.row_ptr, graph.m_pad))
    mask = np.asarray(graph.edge_mask)
    assert np.array_equal(got[mask], np.asarray(x[graph.src])[mask])


@pytest.mark.parametrize("name,graph", CSR_CASES, ids=CSR_IDS)
def test_block_total_and_row_ends(name, graph):
    """``block_total`` gives every position its whole row's maximum, and
    ``at_row_ends`` reads it back per row."""
    rng = np.random.default_rng(9)
    w = jnp.where(graph.edge_mask,
                  jnp.asarray(rng.uniform(0, 9, graph.m_pad), jnp.float32),
                  F32_MIN)
    keys = rowscan.row_keys(graph)
    total = rowscan.block_total(keys, w, jnp.maximum, F32_MIN)
    row_max = jax.ops.segment_max(w, graph.src, num_segments=graph.n)
    mask = np.asarray(graph.edge_mask)
    assert np.array_equal(np.asarray(total)[mask],
                          np.asarray(row_max[graph.src])[mask])
    ends = rowscan.at_row_ends(total, graph.row_ptr, F32_MIN)
    deg = np.asarray(graph.row_ptr[1:] - graph.row_ptr[:-1])
    assert np.array_equal(np.asarray(ends),
                          np.where(deg > 0, np.asarray(row_max), F32_MIN))


def test_run_keys_name_each_run_by_its_start():
    starts = jnp.asarray([True, False, False, True, True, False])
    assert rowscan.run_keys(starts).tolist() == [0, 0, 0, 3, 4, 4]
