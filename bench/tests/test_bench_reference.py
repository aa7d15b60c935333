"""The plain reference agrees with the engine, and its controls fail.

The benchmark decides ``correct`` by comparing the engine's labels with
``lpabench.reference``.  Here, at sizes a test run holds, on the CPU: the
reference equals what ``Engine.fit`` returns on every backend that takes the
graph, and the controls -- the reference in bfloat16, and the reference
without Split-Last -- give labels that the comparison refuses.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import pytest
from tiny_cells import BENCH

from lpabench import graphs, reference, spec

KRON = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "permute": True}
ROAD = {"side": 48, "avg_degree": 2.13, "weights": "travel_time",
        "weight_min": 100,
        "weight_max": 10000}


def make(family, params, seed):
    return spec.generator(BENCH, family).generate(params,
                                                  graphs.rng_for(seed))


@pytest.mark.parametrize("family,params,seed", [
    ("kronecker", KRON, 1), ("kronecker", KRON, 2**31 + 9),
    ("kronecker", {**KRON, "scale": 7}, 4),
    ("road", ROAD, 1), ("road", ROAD, 2**33 + 1)])
def test_reference_equals_engine(family, params, seed):
    from repro.core.graph import build_graph
    from repro.engine import CompileCache, Engine, EngineConfig
    n, e, w = make(family, params, seed)
    expected, iterations = reference.detect(n, e, w)
    g = build_graph(e, w, n=n)
    for backend in ("segment", "tile"):
        res = Engine(EngineConfig(backend=backend),
                     cache=CompileCache()).fit(g)
        assert reference.mismatched_vertices(res.labels, expected) == 0
        assert res.lpa_iterations == iterations


def test_reference_communities_are_connected():
    n, e, w = make("road", ROAD, 5)
    labels, _ = reference.detect(n, e, w)
    g = reference.DirectedCsr(n, e, w)
    again = reference.split_last(g, labels)
    assert reference.mismatched_vertices(reference.compact(again),
                                         labels) == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails_on_travel_times(seed):
    n, e, w = make("road", ROAD, seed)
    expected, _ = reference.detect(n, e, w)
    control, _ = reference.detect(n, e, w, dtype=ml_dtypes.bfloat16)
    assert reference.mismatched_vertices(control, expected) > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_without_split_last_fails(seed):
    import control
    n, e, w = make("kronecker", {**KRON, "scale": 8}, seed)
    expected, _ = reference.detect(n, e, w)
    assert reference.mismatched_vertices(control.unsplit(n, e, w),
                                         expected) > 0


def test_mismatch_counts_a_missing_answer():
    expected = np.arange(5, dtype=np.int32)
    assert reference.mismatched_vertices(np.arange(4), expected) == 5
    assert reference.mismatched_vertices(expected[::-1], expected) == 4
