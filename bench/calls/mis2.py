"""``repro.mis2(graph, engine=None)``, checked against the host replay
of Alg. 1: the same set, vertex for vertex, in the same number of
rounds, distance-2 independent and maximal.  Every limit is 0: the
answer is exact by the algorithm's definition."""
from __future__ import annotations

import numpy as np

from ..data.ref_mis2 import closed_adjacency, mis2_reference, mis2_violations

LIMITS = {"set_mismatch": 0, "d2_conflicts": 0, "unreached": 0,
          "rounds_diff": 0}


def invoke(repro, graph, kwargs):
    r = repro.mis2(graph, **kwargs)
    return {"in_set": r.in_set, "rounds": r.iterations}


def reference(indptr, indices, kwargs):
    return mis2_reference(indptr, indices)


def control(indptr, indices, kwargs):
    """The replay stopped one round before its fixed point: the answer
    is no longer maximal."""
    rounds = mis2_reference(indptr, indices)["rounds"]
    return mis2_reference(indptr, indices, max_rounds=rounds - 1)


def compare(answer, ref, ctx) -> dict:
    in_set = np.asarray(answer["in_set"], dtype=bool)
    if in_set.shape != ref["in_set"].shape:
        n = len(ref["in_set"])
        return {"set_mismatch": n, "d2_conflicts": n, "unreached": n,
                "rounds_diff": abs(int(answer["rounds"]) - ref["rounds"])}
    conflicts, unreached = mis2_violations(ctx.adjacency(), in_set)
    return {"set_mismatch": int((in_set != ref["in_set"]).sum()),
            "d2_conflicts": conflicts, "unreached": unreached,
            "rounds_diff": abs(int(answer["rounds"]) - ref["rounds"])}
