#!/usr/bin/env python
"""Execution-shape regression gates over the ``repro.obs`` registry.

Each gate runs a small workload under ``obs.capture()`` and asserts the
*shape* of the execution — the counters the whole optimization story hangs
on — by diffing registry snapshots:

* ``resident``   the device-resident MIS-2 hot loop is exactly ONE jitted
  dispatch with ZERO in-loop host syncs (PR 4's contract).
* ``serve``      a warmed server keeps the request path compile-free:
  dispatching distinct graphs in a configured bucket shape performs ZERO
  runtime compiles (PR 6's contract).
* ``serve_dedup``  N concurrent same-digest requests coalesce to exactly
  ONE compute, and a fault-degraded server still serves the referent
  digest with ZERO request-path compiles (the hardening contract).
* ``dist``       the sharded engine's collective traffic matches the §V-C
  analytic model byte-for-byte: the registry delta equals
  ``collective_bytes_per_iteration(V, P) x iterations`` and the result's
  own ``collectives`` accounting.

Usage::

    PYTHONPATH=src python tools/check_shape.py [--gates resident,serve,dist]

Prints one PASS/FAIL line per gate; exits nonzero if any gate fails.
CI runs this in the test lane (the ``obs-gates`` step).
"""
from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


class GateFailure(AssertionError):
    pass


def _expect(cond: bool, msg: str) -> None:
    if not cond:
        raise GateFailure(msg)


# ---------------------------------------------------------------------------
# gate: resident — 1 dispatch, 0 host syncs per solve
# ---------------------------------------------------------------------------

def gate_resident() -> str:
    import repro
    from repro import obs
    from repro.graphs.generators import random_uniform_graph

    g = repro.Graph(random_uniform_graph(4000, 8.0, seed=7))
    repro.mis2(g, engine="compacted_resident")      # warm the jit cache
    with obs.capture() as cap:
        r = repro.mis2(g, engine="compacted_resident")
    _expect(r.iterations > 1, "workload too easy: need a multi-round solve")
    dispatches = cap.value("mis2.resident_dispatches")
    syncs = cap.value("mis2.host_syncs")
    _expect(dispatches == 1,
            f"resident solve took {dispatches} dispatches, want exactly 1")
    _expect(syncs == 0,
            f"resident solve paid {syncs} in-loop host syncs, want 0")
    return (f"1 dispatch, 0 host syncs across {r.iterations} rounds "
            f"(engine={r.engine})")


# ---------------------------------------------------------------------------
# gate: serve — warmed buckets keep the request path compile-free
# ---------------------------------------------------------------------------

def gate_serve() -> str:
    import repro
    from repro import obs
    from repro.graphs.generators import random_uniform_graph
    from repro.serve import Server, ServerConfig, warm_buckets_for

    graphs = [repro.Graph(random_uniform_graph(600, 6.0, seed=s))
              for s in range(4)]
    config = ServerConfig(max_batch=4, max_delay_s=0.0,
                          warm_buckets=warm_buckets_for(graphs),
                          single_fast_path=False)
    server = Server(config)
    try:
        with obs.capture() as cap:
            futures = [server.submit("mis2", g) for g in graphs]
            server.flush()
            results = [f.result(timeout=120) for f in futures]
        _expect(all(r.converged for r in results), "serve results diverged")
        compiles = cap.value("serve.warm.runtime_compiles")
        dispatches = cap.value("serve.dispatches")
        _expect(dispatches >= 1, "server never dispatched")
        _expect(compiles == 0,
                f"warm request path paid {compiles} runtime compiles, want 0")
    finally:
        server.stop()
    return (f"{len(graphs)} graphs through warmed buckets: "
            f"0 request-path compiles ({int(dispatches)} dispatches)")


# ---------------------------------------------------------------------------
# gate: serve_dedup — N concurrent same-digest requests: exactly 1 compute,
# and a degraded (fault-injected) server keeps the request path compile-free
# ---------------------------------------------------------------------------

def gate_serve_dedup() -> str:
    import repro
    from repro import obs
    from repro.graphs.generators import random_uniform_graph
    from repro.serve import (Fault, FaultPlan, RetryPolicy, Server,
                             ServerConfig, warm_buckets_for)

    n = 8
    base = repro.Graph(random_uniform_graph(600, 6.0, seed=3))
    clones = [repro.Graph(base.csr) for _ in range(n)]     # digest-equal
    warm = warm_buckets_for([base])

    # --- phase 1: N concurrent same-digest requests -> exactly 1 compute
    server = Server(ServerConfig(max_batch=n, max_delay_s=0.0,
                                 warm_buckets=warm, single_fast_path=False))
    try:
        with obs.capture() as cap:
            futures = [server.submit("mis2", g) for g in clones]
            server.flush()
            results = [f.result(timeout=120) for f in futures]
        digests = {r.digest for r in results}
        _expect(len(digests) == 1,
                f"same-key requests returned {len(digests)} digests, want 1")
        dedup_hits = cap.value("serve.dedup_hits")
        computes = (cap.value("serve.single_dispatches")
                    + cap.value("serve.batched_graphs"))
        compiles = cap.value("serve.warm.runtime_compiles")
        _expect(dedup_hits == n - 1,
                f"{n} same-digest submits coalesced {dedup_hits} joins, "
                f"want {n - 1}")
        _expect(computes == 1,
                f"{n} same-digest requests cost {computes} computes, want "
                "exactly 1")
        _expect(compiles == 0,
                f"dedup path paid {compiles} runtime compiles, want 0")
    finally:
        server.stop()

    # --- phase 2: degraded server (seeded transient engine fault, retried)
    # still serves the correct digest with 0 request-path compiles
    referent = repro.mis2(base, engine="dense")     # warm referent programs
    plan = FaultPlan(seed=5, sites={
        "engine": Fault("error", count=1, transient=True)})
    server = Server(ServerConfig(max_batch=n, max_delay_s=0.0,
                                 warm_buckets=warm, single_fast_path=False,
                                 faults=plan,
                                 retry=RetryPolicy(base_backoff_s=0.0)))
    try:
        with obs.capture() as cap:
            fut = server.submit("mis2", base)
            server.flush()
            degraded = fut.result(timeout=120)
        _expect(degraded.digest == referent.digest,
                f"degraded response digest {degraded.digest} != referent "
                f"{referent.digest}")
        retries = cap.value("serve.retries", {"site": "engine"})
        compiles = cap.value("serve.warm.runtime_compiles")
        _expect(retries == 1,
                f"transient fault provoked {retries} retries, want 1")
        _expect(compiles == 0,
                f"degraded request path paid {compiles} runtime compiles, "
                "want 0")
    finally:
        server.stop()
    return (f"{n} same-digest requests -> 1 compute ({int(dedup_hits)} "
            f"joins); degraded serve digest-correct after {int(retries)} "
            "retry, 0 compiles")


# ---------------------------------------------------------------------------
# gate: dist — registry collective bytes == analytic model == result record
# ---------------------------------------------------------------------------

def gate_dist() -> str:
    import jax

    import repro
    from repro import obs
    from repro.core.dist import collective_bytes_per_iteration
    from repro.graphs.generators import random_uniform_graph

    devices = jax.devices()
    v = 2048
    g = repro.Graph(random_uniform_graph(v, 8.0, seed=11))
    with obs.capture() as cap:
        r = repro.mis2(g, engine="distributed")
    variant = r.collectives["variant"]
    got = cap.value("dist.collective_bytes", {"variant": variant})
    per = collective_bytes_per_iteration(v, len(devices),
                                         variant == "single_gather")
    want = per["result_bytes_per_iteration"] * r.iterations
    _expect(got == want,
            f"registry recorded {got} collective bytes, analytic model says "
            f"{want} ({variant}, {len(devices)} devices, "
            f"{r.iterations} iterations)")
    _expect(got == r.collectives["result_bytes_total"],
            f"registry ({got}) disagrees with the result's own accounting "
            f"({r.collectives['result_bytes_total']})")
    return (f"{int(got)} bytes == analytic model == result record "
            f"({variant}, {len(devices)} devices, {r.iterations} iters)")


# ---------------------------------------------------------------------------
# gate: hybrid_traffic — the hybrid solve's work counts agree with its
# rounds (registry ``mis2.rounds`` == result), it is one resident
# dispatch, and a warm repeat compiles nothing
# ---------------------------------------------------------------------------

def gate_hybrid_traffic() -> str:
    import repro
    from repro import obs
    from repro.graphs.generators import powerlaw_graph

    g = repro.Graph(powerlaw_graph(4000, 8.0, seed=7))
    repro.mis2(g, engine="pallas_hybrid")           # warm the jit cache
    with obs.capture() as cap:
        r = repro.mis2(g, engine="pallas_hybrid")
    _expect(r.iterations > 1, "workload too easy: need a multi-round solve")
    c = r.collectives
    _expect(c["variant"] == "hybrid", f"unexpected variant {c['variant']!r}")
    rounds = cap.value("mis2.rounds", {"layout": "hybrid"})
    _expect(rounds == r.iterations,
            f"registry recorded {rounds} hybrid rounds, the result says "
            f"{r.iterations}")
    _expect(c["spill_entries"] > 0, "workload has no spill: need a hub")
    _expect(c["spill_passes"] == 2 * r.iterations,
            f"{c['spill_passes']} spill passes for {r.iterations} rounds, "
            "want two a round")
    dispatches = cap.value("mis2.resident_dispatches")
    syncs = cap.value("mis2.host_syncs")
    compiles = cap.value("jit.compiles")
    _expect(dispatches == 1,
            f"hybrid solve took {dispatches} dispatches, want exactly 1")
    _expect(syncs == 0,
            f"hybrid solve paid {syncs} in-loop host syncs, want 0")
    _expect(compiles == 0 and r.num_compiles == 1,
            f"warm hybrid solve compiled {compiles} programs "
            f"(num_compiles={r.num_compiles}), want 0 (1)")
    return (f"{int(rounds)} rounds == result, {c['spill_passes']} spill "
            f"passes ({len(c['slice_widths'])} slices + "
            f"{c['spill_entries']} spill entries), 1 dispatch, 0 compiles")


GATES = {
    "resident": gate_resident,
    "serve": gate_serve,
    "serve_dedup": gate_serve_dedup,
    "dist": gate_dist,
    "hybrid_traffic": gate_hybrid_traffic,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gates", default=",".join(GATES),
                    help="comma-separated subset of " + ",".join(GATES))
    args = ap.parse_args()
    names = [n.strip() for n in args.gates.split(",") if n.strip()]
    unknown = [n for n in names if n not in GATES]
    if unknown:
        print(f"unknown gate(s): {', '.join(unknown)}", file=sys.stderr)
        return 2
    failed = 0
    for name in names:
        try:
            detail = GATES[name]()
        except GateFailure as e:
            print(f"FAIL  {name:<9} {e}")
            failed += 1
        except Exception:
            print(f"FAIL  {name:<9} crashed:")
            traceback.print_exc()
            failed += 1
        else:
            print(f"PASS  {name:<9} {detail}")
    if failed:
        print(f"{failed}/{len(names)} execution-shape gates failed")
        return 1
    print(f"all {len(names)} execution-shape gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
