#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, through the facade and
the server a user would call.

    python3 chip_smoke.py             # phases A-C on one chip
    python3 chip_smoke.py --chips 4   # the distributed phase on a 4-chip mesh

Phase A  Laplace3D 100^3 (V = 1,000,000; Table III's largest Laplace case):
         ``repro.mis2`` with ``engine=None``, ``pallas_resident`` and
         ``dense`` (equal digests, independence + maximality checked on
         the host); the same call on Laplace3D 50^3 pinned to the CPU
         device gives the TPU's digest (the paper's portability claim);
         ``repro.coarsen(method="two_phase")``; ``repro.amg_setup`` on
         Laplace3D 50^3 matches the CPU device's level by level, and the
         ``resident`` engine refuses the TPU (its float64 is emulated,
         not IEEE, so its hierarchy could not match ``host``).
Phase B  the V = 1M power-law graph (``powerlaw_graph(1_000_000, 8.0,
         exponent=2.5, seed=42)``): ``engine=None`` must resolve to
         ``pallas_hybrid``, its digest must equal the CPU device's, and
         ``repro.coarsen(..., mis2_engine="pallas_hybrid")`` must label
         every vertex.
Phase C  a ``repro.serve.Server`` with warm buckets answers 16 mixed
         mis2/color/coarsen/amg_setup requests over five graphs; served digests equal
         facade digests and no request falls back, retries, is shed or
         compiles on the request path.
--chips 4  ``distributed`` and ``distributed_single_gather`` MIS-2 and
         ``two_phase_distributed`` coarsening of Laplace3D 100^3 over a
         mesh of all four devices, each compared with ``dense`` on device 0.

Every line but the last is one JSON smoke record (device kind, seconds of
the first call, which includes compilation, and of a second call,
iterations, sizes, digests, peak device memory): smoke output, not
benchmark metrics.  The last line is ``{"ok": true, "device": {...}}``.
A failed check or a phase that raises exits non-zero without that line;
so does a run that finds no TPU (there is no CPU fallback) or one started
outside a checkout of this repository.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

LAPLACE_N = 100             # Table III's largest Laplace3D case
PORTABLE_N = 50             # cross-platform digest + AMG setup size
POWERLAW = dict(num_vertices=1_000_000, avg_degree=8.0, exponent=2.5, seed=42)


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(step: str, **rec) -> None:
    print(json.dumps({"smoke": step, **rec}, default=str), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def twice(fn):
    """(result, first-call seconds incl. compile, second-call seconds);
    both calls must agree bit for bit."""
    r1, t1 = timed(fn)
    r2, t2 = timed(fn)
    check(r1.digest == r2.digest, f"repeat call changed digest {r1.digest} "
                                  f"-> {r2.digest}")
    return r1, t1, t2


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_mis2(csr, in_set, what: str) -> None:
    """Distance-2 independence and maximality on the host: with ``A`` the
    closed-neighborhood adjacency and ``y = A x``, no vertex may see two
    set vertices (``y <= 1``) and every vertex must reach one within two
    hops (``A y >= 1``)."""
    import numpy as np
    import scipy.sparse as sp

    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    v = len(indptr) - 1
    a = sp.csr_matrix((np.ones(len(indices), np.int32), indices, indptr),
                      shape=(v, v))
    a = ((a + sp.identity(v, np.int32, format="csr")) != 0).astype(np.int32)
    y = a @ np.asarray(in_set, dtype=np.int32)
    check(int(y.max()) <= 1, f"{what}: distance-2 independence violated")
    check(bool(((a @ y) > 0).all()), f"{what}: maximality violated")


def check_labels(agg, v: int, what: str) -> None:
    import numpy as np

    labels = np.asarray(agg.labels)
    check(labels.shape == (v,), f"{what}: labels shape {labels.shape}")
    check(int(labels.min()) >= 0, f"{what}: unaggregated vertices")
    sizes = np.bincount(labels, minlength=agg.num_aggregates)
    check(len(sizes) == agg.num_aggregates and int(sizes.min()) > 0,
          f"{what}: aggregate ids not dense in [0, {agg.num_aggregates})")


def on_cpu(fn):
    """Run a facade call pinned to the host CPU device."""
    import jax

    import repro

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        return fn(repro.Backend(device=cpu))


def phase_a(dev, n: int = LAPLACE_N, n_small: int = PORTABLE_N) -> None:
    import repro
    from repro.graphs import laplace3d

    g = repro.Graph(laplace3d(n))
    v = g.num_vertices
    digests = {}
    for engine in (None, "pallas_resident", "dense"):
        r, t1, t2 = twice(lambda: repro.mis2(g, engine=engine))
        emit("A.mis2", device=dev.device_kind, graph=f"laplace3d({n})",
             v=v, engine=r.engine, first_call_s=t1, second_call_s=t2,
             iterations=r.iterations, size=r.size, digest=r.digest)
        check(r.converged, f"mis2[{r.engine}] did not converge")
        digests[r.engine] = r.digest
        if engine is None:
            check_mis2(g.csr, r.in_set, f"mis2[{r.engine}] laplace3d({n})")
    check(len(set(digests.values())) == 1, f"engine digests differ: {digests}")

    small = laplace3d(n_small)
    r_tpu = repro.mis2(repro.Graph(small))
    r_cpu = on_cpu(lambda be: repro.mis2(repro.Graph(small), backend=be))
    emit("A.portability", graph=f"laplace3d({n_small})",
         tpu=[r_tpu.provenance.backend, r_tpu.engine, r_tpu.digest],
         cpu=[r_cpu.provenance.backend, r_cpu.engine, r_cpu.digest])
    check(r_tpu.provenance.backend == "tpu"
          and r_cpu.provenance.backend == "cpu", "portability run platforms")
    check(r_tpu.digest == r_cpu.digest, "TPU and CPU digests differ")

    agg, t1, t2 = twice(lambda: repro.coarsen(g, method="two_phase"))
    ref = repro.coarsen(g, method="two_phase", mis2_engine="dense")
    emit("A.coarsen", graph=f"laplace3d({n})", first_call_s=t1,
         second_call_s=t2, num_aggregates=agg.num_aggregates,
         mis2_iterations=agg.iterations, digest=agg.digest)
    check_labels(agg, v, "coarsen two_phase")
    check(agg.digest == ref.digest, "coarsen differs from the dense engine")

    # AMG setup: the TPU's float64 is emulated and not IEEE, so
    # engine=None must pick the host engine (f64 arithmetic in numpy; the
    # aggregation fixed points run on the TPU) and must match the same
    # setup on the CPU device level by level; the resident engine, whose
    # f64 arithmetic would run on the TPU, must refuse with a typed error
    gs = repro.Graph(small)
    amg, t1 = timed(lambda: repro.amg_setup(gs))
    amg_cpu, tc = timed(lambda: on_cpu(
        lambda be: repro.amg_setup(repro.Graph(small), backend=be)))
    emit("A.amg_setup", graph=f"laplace3d({n_small})", engine=amg.engine,
         tpu_s=t1, cpu_s=tc, levels=amg.level_sizes,
         tpu=amg.level_digests, cpu=amg_cpu.level_digests)
    check(amg.engine == "host" and amg.provenance.backend == "tpu"
          and amg_cpu.provenance.backend == "cpu", "AMG engine/platforms")
    check(amg.level_sizes == amg_cpu.level_sizes
          and amg.level_digests == amg_cpu.level_digests,
          "AMG setup on the TPU differs from the CPU device's")
    try:
        repro.amg_setup(gs, engine="resident")
        refused = None
    except repro.api.InexactFloat64Error as err:
        refused = str(err)
    emit("A.amg_resident", refused=refused)
    check(refused is not None,
          "resident AMG setup ran on a platform without IEEE float64")
    emit("A.memory", peak_bytes_in_use=peak_bytes(dev))


def phase_b(dev, powerlaw: dict = POWERLAW) -> None:
    import repro
    from repro.graphs import powerlaw_graph

    csr = powerlaw_graph(**powerlaw)
    g = repro.Graph(csr)
    v = g.num_vertices
    r, t1, t2 = twice(lambda: repro.mis2(g))
    hyb = g.hybrid()
    emit("B.mis2", device=dev.device_kind, graph="powerlaw", v=v,
         engine=r.engine, first_call_s=t1, second_call_s=t2,
         iterations=r.iterations, size=r.size,
         slice_widths=list(hyb.slice_widths),
         spill_rows=hyb.num_spill_rows, digest=r.digest)
    check(r.engine == "pallas_hybrid",
          f"engine=None resolved to {r.engine}, not pallas_hybrid")
    check(r.converged, "pallas_hybrid did not converge")
    r_cpu, tc = timed(lambda: on_cpu(
        lambda be: repro.mis2(repro.Graph(csr), backend=be)))
    emit("B.portability", cpu=[r_cpu.provenance.backend, r_cpu.engine,
                               r_cpu.digest], cpu_s=tc)
    check(r_cpu.engine == "pallas_hybrid"
          and r_cpu.provenance.backend == "cpu", "CPU reference engine")
    check(r.digest == r_cpu.digest, "TPU and CPU pallas_hybrid digests differ")
    check_mis2(csr, r.in_set, "mis2[pallas_hybrid] powerlaw")

    agg, t1, t2 = twice(lambda: repro.coarsen(g, method="two_phase",
                                              mis2_engine="pallas_hybrid"))
    emit("B.coarsen", first_call_s=t1, second_call_s=t2,
         num_aggregates=agg.num_aggregates, digest=agg.digest)
    check_labels(agg, v, "coarsen pallas_hybrid")
    emit("B.memory", peak_bytes_in_use=peak_bytes(dev))


def phase_c(dev, sizes=(24, 28, 32), rand_v: int = 20_000) -> None:
    import repro
    from repro.graphs import laplace3d, random_uniform_graph
    from repro.serve import Server, ServerConfig, warm_buckets_for

    graphs = [repro.Graph(laplace3d(k)) for k in sizes] + [
        repro.Graph(random_uniform_graph(rand_v, 6.0, seed=s))
        for s in (1, 2)]
    # the AMG request names the host engine: phase A already holds the
    # resident engine to it, and every new matrix shape costs the resident
    # setup minutes of TPU compiles (its sorts; see PERF.md)
    requests = ([(kind, g, None) for kind in ("mis2", "color", "coarsen")
                 for g in graphs] + [("amg_setup", graphs[0], "host")])
    (srv, t_start) = timed(lambda: Server(ServerConfig(
        max_batch=8, warm_buckets=warm_buckets_for(graphs))))
    t0 = time.perf_counter()
    futs = [srv.submit(kind, g, engine=engine) for kind, g, engine in requests]
    srv.flush()
    served = [f.result(timeout=900) for f in futs]
    t_serve = time.perf_counter() - t0
    stats = srv.server_stats()
    facade = {"mis2": repro.mis2, "color": repro.color,
              "coarsen": repro.coarsen, "amg_setup": repro.amg_setup}
    for (kind, g, engine), res in zip(requests, served):
        want = facade[kind](g) if engine is None else facade[kind](
            g, engine=engine)
        check(res.digest == want.digest,
              f"served {kind} digest {res.digest} != facade {want.digest}")
        if kind == "amg_setup":
            check(res.level_digests == want.level_digests,
                  "served AMG level digests differ from the facade's")
    emit("C.serve", device=dev.device_kind, requests=len(requests),
         startup_s=t_start, serve_s=t_serve,
         dispatches=stats["dispatches"], fallbacks=stats["fallbacks"],
         retries=stats["retries"], shed=stats["shed"],
         runtime_compiles=stats["compiles"]["runtime_cold"],
         startup_compiles=stats["compiles"]["startup_aot"])
    for name, val in (("fallbacks", stats["fallbacks"]),
                      ("retries", stats["retries"]), ("shed", stats["shed"]),
                      ("runtime_compiles", stats["compiles"]["runtime_cold"])):
        check(val == 0, f"serve.{name} = {val}")
    emit("C.memory", peak_bytes_in_use=peak_bytes(dev))


def phase_distributed(devices, n: int = LAPLACE_N) -> None:
    import numpy as np
    from jax.sharding import Mesh

    import repro
    from repro.graphs import laplace3d

    mat = laplace3d(n)
    mesh = Mesh(np.array(devices), ("x",))
    on_mesh = repro.Backend(mesh=mesh)
    on_dev0 = repro.Backend(device=devices[0])
    ref = repro.mis2(repro.Graph(mat), engine="dense", backend=on_dev0)
    emit("D.mis2", engine="dense", device=str(devices[0]),
         iterations=ref.iterations, size=ref.size, digest=ref.digest)
    g = repro.Graph(mat)
    for engine in ("distributed", "distributed_single_gather"):
        r, t1, t2 = twice(lambda: repro.mis2(g, engine=engine,
                                             backend=on_mesh))
        emit("D.mis2", engine=engine, devices=len(devices),
             first_call_s=t1, second_call_s=t2, iterations=r.iterations,
             size=r.size, digest=r.digest)
        check(r.digest == ref.digest, f"{engine} digest differs from dense")
    cref = repro.coarsen(repro.Graph(mat), method="two_phase",
                         mis2_engine="dense", backend=on_dev0)
    agg, t1, t2 = twice(lambda: repro.coarsen(
        g, method="two_phase_distributed", backend=on_mesh))
    emit("D.coarsen", method="two_phase_distributed", first_call_s=t1,
         second_call_s=t2, num_aggregates=agg.num_aggregates,
         digest=agg.digest, reference=cref.digest)
    check(agg.digest == cref.digest,
          "two_phase_distributed differs from two_phase on device 0")
    emit("D.memory", peak_bytes_in_use=[peak_bytes(d) for d in devices])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed phase on a 4-chip mesh")
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "__init__.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
              "this smoke run never falls back to the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.compile_cache import setup_compile_cache

    emit("setup", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__,
         compile_cache=setup_compile_cache())
    if args.chips == 4:
        phases = [("distributed", lambda: phase_distributed(devices[:4]))]
    else:
        phases = [("A", lambda: phase_a(dev)), ("B", lambda: phase_b(dev)),
                  ("C", lambda: phase_c(dev))]
    failed = []
    for name, run in phases:
        try:
            _, dt = timed(run)
            emit(f"{name}.done", seconds=dt)
        except Exception as err:  # noqa: BLE001 - every phase runs, any fails
            traceback.print_exc()
            emit(f"{name}.failed", error=f"{type(err).__name__}: {err}")
            failed.append(name)
    if failed:
        print(f"chip_smoke: phase(s) {', '.join(failed)} failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
