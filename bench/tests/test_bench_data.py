"""The benchmark's own generators and plain references, against the
program at small sizes on the CPU."""
import numpy as np
import pytest

from . import _paths  # noqa: F401
from ..data import kronecker, laplace3d
from ..data.ref_coarsen import coarsen_reference
from ..data.ref_mis2 import (
    closed_adjacency,
    mis2_reference,
    mis2_violations,
    priority_hash,
)

KRON = {"scale": 9, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "generator_seed": 1}
LAP = {"nx": 7, "ny": 6, "nz": 5}


def graph(csr):
    import repro
    from repro.graphs import CSRGraph

    return repro.Graph(CSRGraph(*csr))


def test_laplace_identity_copy_equals_program_generator():
    from repro.graphs import laplace3d as program_laplace3d

    indptr, indices = laplace3d.generate(LAP, None)
    g = program_laplace3d(7, 6, 5).graph
    assert np.array_equal(indptr, np.asarray(g.indptr))
    assert np.array_equal(indices, np.asarray(g.indices))


def test_laplace_seed_permutes_within_planes():
    a = laplace3d.generate(LAP, 3)
    b = laplace3d.generate(LAP, 3)
    c = laplace3d.generate(LAP, 4)
    ident = laplace3d.generate(LAP, None)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert sorted(np.diff(a[0])) == sorted(np.diff(ident[0]))
    perm = laplace3d.plane_permutation(7, 30, 3)
    assert np.array_equal(perm // 30, np.arange(210) // 30)
    assert np.array_equal(np.sort(perm), np.arange(210))


def test_kronecker_seeded():
    a = kronecker.generate(KRON, 5)
    b = kronecker.generate(KRON, 5)
    c = kronecker.generate(KRON, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    # the run's seed relabels the vertices: same degree sequence
    assert np.array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(c[0])))
    d = kronecker.generate(dict(KRON, generator_seed=2), 5)
    assert not np.array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(d[0])))
    indptr, indices = a
    v = len(indptr) - 1
    rows = np.repeat(np.arange(v), np.diff(indptr))
    adj = closed_adjacency(indptr, indices)
    assert (adj != adj.T).nnz == 0                      # symmetric
    assert (indices[rows == indices].size == v)         # every self loop


def test_priority_hash_matches_program():
    from repro.core.hashing import np_priorities

    ids = np.arange(1000)
    for rnd in (0, 3, 77):
        assert np.array_equal(priority_hash(rnd, ids),
                              np_priorities("xorshift_star", rnd, ids))


@pytest.mark.parametrize("gen,cfg,seed", [(laplace3d, LAP, 2),
                                          (kronecker, KRON, 3)])
def test_replay_equals_dense_engine(gen, cfg, seed):
    import repro

    csr = gen.generate(cfg, seed)
    r = repro.mis2(graph(csr), engine="dense")
    ref = mis2_reference(*csr)
    assert np.array_equal(r.in_set, ref["in_set"])
    assert r.iterations == ref["rounds"] and ref["undecided"] == 0
    assert mis2_violations(closed_adjacency(*csr), ref["in_set"]) == (0, 0)
    active = np.arange(len(csr[0]) - 1) % 3 != 0
    r = repro.mis2(graph(csr), engine="dense", active=active)
    ref = mis2_reference(*csr, active=active)
    assert np.array_equal(r.in_set, ref["in_set"])
    assert r.iterations == ref["rounds"]


@pytest.mark.parametrize("gen,cfg,seed", [(laplace3d, LAP, 2),
                                          (kronecker, KRON, 3)])
def test_coarsen_reference_equals_program(gen, cfg, seed):
    import repro

    csr = gen.generate(cfg, seed)
    a = repro.coarsen(graph(csr), method="two_phase")
    ref = coarsen_reference(*csr)
    assert np.array_equal(a.labels, ref["labels"])
    assert a.num_aggregates == ref["num_aggregates"]
    assert a.iterations == ref["rounds"]


def test_least_bytes_counts_each_pass():
    """The path 0-1-2 with self loops: each pass of round 0 reads 7
    neighbor ids and 3 distinct tuples and writes 3 results."""
    indptr = np.array([0, 2, 5, 7])
    indices = np.array([0, 1, 0, 1, 2, 1, 2])
    ref = mis2_reference(indptr, indices)
    assert ref["in_set"].sum() == 1 and ref["rounds"] >= 1
    assert ref["least_bytes"] >= 4 * (7 + 3 + 3) * 2
    assert ref["least_bytes"] % 4 == 0
    one = mis2_reference(indptr, indices, max_rounds=1)
    assert one["least_bytes"] == 4 * (7 + 3 + 3) * 2
