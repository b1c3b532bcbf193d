"""Paper-scale hybrid-layout benchmark (ISSUE 10 tentpole metric).

Exercises the degree-aware hybrid layout (sliced-ELL + COO spill,
``graphs.hybrid``) in exactly the regime it exists for: a Chung-Lu
power-law graph whose hub row makes the monolithic padded-ELL layout
infeasible (``Graph.ell`` raises :class:`LayoutOverflowError` past
``ELL_BYTE_LIMIT``) while the total edge count stays modest.

Measured per scale:

* layout build — CSR -> hybrid conversion wall time plus the layout's own
  accounting (slice widths/rows, spill rows/entries, padded bytes vs. the
  monolithic estimate, padding ratio);
* MIS-2 (``engine="pallas_hybrid"``) — solve wall time, iterations, the
  slab rows processed, and the compile accounting (the resident fixed
  point is ONE dispatch; jit churn is O(#slices), not O(graph));
* two-phase coarsening (``mis2_engine="pallas_hybrid"``) — end-to-end
  Algorithm 3 over the hybrid join loops: wall time, aggregate count,
  coarsening ratio.

Full mode runs V = 1M (the ISSUE 10 acceptance scale) and *asserts* the
monolithic padded-ELL is infeasible; ``--quick`` (the CI examples-smoke
lane) keeps the same shape at V = 20k, where the monolith still fits —
the record carries ``ell_infeasible`` so the trajectory distinguishes the
two regimes.  The headline record is appended to
``BENCH_hybrid_layout.json`` (root mirror committed).
"""
from __future__ import annotations

import time

from benchmarks.common import emit, emit_trajectory, standalone, timeit


def run(quick: bool = False) -> None:
    from repro.api import Graph, coarsen, mis2
    from repro.graphs.generators import powerlaw_graph
    from repro.graphs.hybrid import ELL_BYTE_LIMIT, LayoutOverflowError

    if quick:
        v, repeats = 20_000, 3
    else:
        v, repeats = 1_000_000, 1

    t0 = time.perf_counter()
    g = Graph(powerlaw_graph(v, 8.0, exponent=2.5, seed=42))
    gen_s = time.perf_counter() - t0

    est = g.ell_bytes_estimate()
    infeasible = est > ELL_BYTE_LIMIT
    if not quick:
        # the acceptance regime: the monolithic layout must be refused
        assert infeasible, (
            f"V={v} power-law monolith estimate {est:,} B unexpectedly fits "
            f"the {ELL_BYTE_LIMIT:,} B budget — not the paper-scale regime")
    if infeasible:
        try:
            g.ell
        except LayoutOverflowError:
            pass
        else:
            raise AssertionError("Graph.ell materialized past ELL_BYTE_LIMIT")

    t0 = time.perf_counter()
    hyb = g.hybrid()
    build_s = time.perf_counter() - t0

    r = mis2(g, engine="pallas_hybrid")            # warmup/compile
    mis2_s = timeit(lambda: mis2(g, engine="pallas_hybrid"), repeats=repeats)
    c = r.collectives

    agg = coarsen(g, method="two_phase", mis2_engine="pallas_hybrid")
    coarsen_s = timeit(
        lambda: coarsen(g, method="two_phase", mis2_engine="pallas_hybrid"),
        repeats=repeats)

    layout = {
        "num_slices": hyb.num_slices,
        "slice_widths": list(hyb.slice_widths),
        "spill_rows": hyb.num_spill_rows,
        "spill_entries": hyb.num_spill_entries,
        "hybrid_bytes": hyb.padded_bytes,
        "monolith_ell_bytes_estimate": est,
        "padding_ratio": round(hyb.padding_ratio, 4),
    }
    rows = [
        {"stage": "generate", "seconds": gen_s, "V": v,
         "detail": f"entries={g.num_entries} max_degree={g.max_degree}"},
        {"stage": "hybrid_build", "seconds": build_s, "V": v,
         "detail": (f"slices={hyb.num_slices} spill_rows="
                    f"{hyb.num_spill_rows} padding_ratio="
                    f"{hyb.padding_ratio:.3f}")},
        {"stage": "mis2_hybrid", "seconds": mis2_s, "V": v,
         "detail": (f"iterations={r.iterations} compiles={r.num_compiles} "
                    f"slab_rows={sum(c['slice_rows_processed'])}")},
        {"stage": "coarsen_two_phase_hybrid", "seconds": coarsen_s, "V": v,
         "detail": (f"aggregates={agg.num_aggregates} ratio="
                    f"{agg.coarsening_ratio:.2f}")},
    ]
    emit("hybrid_layout", rows)

    assert r.converged and agg.converged
    # compile accounting: the resident fixed point is one jitted dispatch,
    # so jit churn is bounded by the slice count, not the graph
    assert r.num_compiles <= hyb.num_slices + 1, (
        f"{r.num_compiles} compiles for {hyb.num_slices} slices")

    emit_trajectory("hybrid_layout", {
        "quick": quick,
        "V": v,
        "entries": int(g.num_entries),
        "max_degree": int(g.max_degree),
        "ell_infeasible": bool(infeasible),
        "layout": layout,
        "generate_s": round(gen_s, 4),
        "hybrid_build_s": round(build_s, 4),
        "mis2_s": round(mis2_s, 4),
        "mis2_iterations": int(r.iterations),
        "mis2_num_compiles": int(r.num_compiles),
        "mis2_slab_rows": int(sum(c["slice_rows_processed"])),
        "coarsen_s": round(coarsen_s, 4),
        "num_aggregates": int(agg.num_aggregates),
        "coarsening_ratio": round(agg.coarsening_ratio, 3),
    })


if __name__ == "__main__":
    standalone(run)
