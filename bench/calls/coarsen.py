"""``repro.coarsen(graph, **kwargs)`` (paper Alg. 3 with
``method="two_phase"``), checked against the plain host Alg. 3: the same
label for every vertex, the same number of aggregates and of MIS-2
rounds, and no vertex left unaggregated.  Every limit is 0."""
from __future__ import annotations

import numpy as np

from ..data.ref_coarsen import coarsen_reference
from ..data.ref_mis2 import mis2_reference

LIMITS = {"label_mismatch": 0, "unlabeled": 0, "aggregates_diff": 0,
          "rounds_diff": 0}


def invoke(repro, graph, kwargs):
    r = repro.coarsen(graph, **kwargs)
    return {"labels": r.labels, "num_aggregates": r.num_aggregates,
            "rounds": r.iterations}


def _min_secondary(kwargs) -> int:
    if kwargs.get("method") != "two_phase":
        raise ValueError(f"no plain reference for coarsen {kwargs}")
    return int(kwargs.get("min_secondary_neighbors", 2))


def reference(indptr, indices, kwargs):
    return coarsen_reference(indptr, indices, _min_secondary(kwargs))


def control(indptr, indices, kwargs):
    """Alg. 3 whose MIS-2 phases stop one round before the fixed point
    of the whole graph's MIS-2: the roots are no longer maximal."""
    rounds = mis2_reference(indptr, indices)["rounds"]
    return coarsen_reference(indptr, indices, _min_secondary(kwargs),
                             max_rounds=rounds - 1)


def compare(answer, ref, ctx) -> dict:
    labels = np.asarray(answer["labels"])
    n = len(ref["labels"])
    mismatch = int((labels != ref["labels"]).sum()) \
        if labels.shape == ref["labels"].shape else n
    return {"label_mismatch": mismatch,
            "unlabeled": int((labels < 0).sum()),
            "aggregates_diff": abs(int(answer["num_aggregates"])
                                   - ref["num_aggregates"]),
            "rounds_diff": abs(int(answer["rounds"]) - ref["rounds"])}
