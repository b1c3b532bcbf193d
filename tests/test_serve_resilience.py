"""repro.serve hardening: the robustness contract under fire.

Every test here drives the server through some failure mode — overload,
quota exhaustion, expired deadlines, injected engine faults, corrupted
or torn persistent entries, shutdown races — and asserts the one
invariant that matters: **every submitted future resolves with either a
digest-correct Result or a typed ServeError** (no hangs, no silent wrong
answers), and concurrent same-key requests cost exactly one compute.

Fault injection is seeded and deterministic (per-site RNG streams), so
these assertions are exact counts, not probabilistic bounds.
"""
import os
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import repro
from repro import obs
from repro.graphs import laplace3d, random_uniform_graph
from repro.serve import (
    AdmissionController,
    Batcher,
    DeadlineExceeded,
    DigestMismatch,
    EngineFailure,
    Fault,
    FaultPlan,
    InjectedFault,
    PendingRequest,
    QuotaConfig,
    QuotaExceeded,
    RetryPolicy,
    ServeError,
    Server,
    ServerClosed,
    ServerConfig,
    ServerOverloaded,
    TokenBucket,
)


def _graph(seed=0, n=80, deg=4.0):
    return repro.Graph(random_uniform_graph(n, deg, seed=seed))


# ---------------------------------------------------------------------------
# in-flight dedup: one compute per unique key
# ---------------------------------------------------------------------------

def test_dedup_coalesces_same_key_submits_onto_one_future():
    g = _graph(1)
    clones = [repro.Graph(g.csr) for _ in range(5)]     # digest-equal
    srv = Server(ServerConfig())
    with obs.capture() as cap:
        futs = [srv.submit("mis2", c) for c in [g] + clones]
        srv.flush()
    assert all(f is futs[0] for f in futs)      # joiners share the primary
    digests = {f.result(timeout=30).digest for f in futs}
    assert digests == {repro.mis2(g).digest}
    assert srv.stats.dedup_hits == len(clones)
    assert srv.stats.single_dispatches + srv.stats.batched_graphs == 1
    assert cap.value("serve.dedup_hits") == len(clones)


def test_dedup_distinguishes_engine_and_options():
    g = repro.Graph(laplace3d(4))
    srv = Server(ServerConfig())
    f1 = srv.submit("mis2", g)
    f2 = srv.submit("mis2", g, engine="dense")          # explicit engine
    f3 = srv.submit("color", g)                         # different kind
    srv.flush()
    assert len({id(f) for f in (f1, f2, f3)}) == 3
    assert srv.stats.dedup_hits == 0
    assert f1.result().digest == f2.result().digest     # still bit-identical


def test_dedup_disabled_computes_separately():
    g = _graph(2)
    srv = Server(ServerConfig(dedup=False, cache_bytes=0,
                              single_fast_path=True, max_batch=1))
    f1 = srv.submit("mis2", g)
    f2 = srv.submit("mis2", repro.Graph(g.csr))
    srv.flush()
    assert f1 is not f2
    assert srv.stats.dedup_hits == 0
    assert srv.stats.single_dispatches == 2
    assert f1.result().digest == f2.result().digest


def test_dedup_key_released_after_completion():
    g = _graph(3)
    srv = Server(ServerConfig(cache_bytes=0))   # no cache: recompute path
    f1 = srv.submit("mis2", g)
    srv.flush()
    f2 = srv.submit("mis2", g)                  # no longer in flight
    srv.flush()
    assert f1 is not f2
    assert f2.result().digest == f1.result().digest
    assert srv.server_stats()["inflight_keys"] == 0


# ---------------------------------------------------------------------------
# admission control: quota, bounded queue, deadline feasibility
# ---------------------------------------------------------------------------

def test_token_bucket_refill_with_manual_clock():
    b = TokenBucket(rate=2.0, burst=4.0, now=0.0)
    assert all(b.try_take(0.0) for _ in range(4))   # burst drained
    assert not b.try_take(0.0)
    assert not b.try_take(0.4)                      # 0.8 tokens: not enough
    assert b.try_take(0.5)                          # 1.0 accumulated
    assert b.try_take(10.0)                         # refill caps at burst
    assert sum(b.try_take(10.0) for _ in range(9)) == 3


def test_admission_controller_policies_with_injected_clock():
    clock = [0.0]
    ctl = AdmissionController(max_pending=2,
                              quota=QuotaConfig(rate=1.0, burst=2.0),
                              clock=lambda: clock[0])
    ctl.admit(caller="a", pending=0)
    ctl.admit(caller="a", pending=0)
    with pytest.raises(QuotaExceeded):              # burst of 2 spent
        ctl.admit(caller="a", pending=0)
    assert ctl.denials == {"a": 1}
    ctl.admit(caller="b", pending=0)                # independent bucket
    clock[0] = 1.0                                  # 1 token refilled for a
    ctl.admit(caller="a", pending=0)
    with pytest.raises(ServerOverloaded):
        ctl.admit(caller="c", pending=2)
    ctl.admit(caller="c", pending=2, joining=True)  # joins skip the queue
    with pytest.raises(DeadlineExceeded):
        ctl.admit(caller="d", deadline_s=0.0)
    with pytest.raises(DeadlineExceeded):           # infeasible deadline
        ctl.admit(caller="e", deadline_s=0.01, est_wait_s=1.0)
    ctl.admit(caller="f", deadline_s=2.0, est_wait_s=1.0)


def test_server_sheds_overload_with_typed_error_and_counter():
    srv = Server(ServerConfig(max_pending=1, dedup=False))
    with obs.capture() as cap:
        f1 = srv.submit("mis2", _graph(4))
        f2 = srv.submit("mis2", _graph(5))
    with pytest.raises(ServerOverloaded) as ei:
        f2.result(timeout=5)
    assert ei.value.retryable
    assert cap.value("serve.shed", {"reason": "overloaded"}) == 1
    srv.flush()
    assert f1.result(timeout=30).converged          # admitted one unharmed


def test_server_quota_is_per_caller():
    srv = Server(ServerConfig(quota=QuotaConfig(rate=0.0, burst=1.0),
                              dedup=False, cache_bytes=0))
    f_a1 = srv.submit("mis2", _graph(6), caller="alice")
    f_a2 = srv.submit("mis2", _graph(7), caller="alice")
    f_b = srv.submit("mis2", _graph(8), caller="bob")
    srv.flush()
    with pytest.raises(QuotaExceeded):
        f_a2.result(timeout=5)
    assert f_a1.result(timeout=30).converged
    assert f_b.result(timeout=30).converged
    assert srv.server_stats()["quota_denials"] == {"alice": 1}


def test_expired_deadline_is_shed_at_submit():
    srv = Server(ServerConfig())
    fut = srv.submit("mis2", _graph(9), deadline_s=0.0)
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=5)
    assert srv.stats.shed == 1
    assert len(srv.batcher) == 0                    # never queued


def test_cache_hits_bypass_admission():
    g = _graph(10)
    srv = Server(ServerConfig(quota=QuotaConfig(rate=0.0, burst=1.0)))
    first = srv.request("mis2", g)
    # quota is spent, but the cached answer is served unconditionally
    again = srv.submit("mis2", g).result(timeout=5)
    assert again.digest == first.digest
    with pytest.raises(QuotaExceeded):
        srv.submit("mis2", _graph(11)).result(timeout=5)


# ---------------------------------------------------------------------------
# batcher deadline semantics, injected clock (no sleeps, no wall time)
# ---------------------------------------------------------------------------

def _req(kind="mis2", key=("k",), deadline=None):
    return PendingRequest(kind=kind, graph=None, params={"p": key},
                          engine=None, backend=None, cache_key=key,
                          future=Future(), deadline=deadline)


def test_batcher_zero_delay_dispatches_immediately():
    b = Batcher(max_batch=8, max_delay_s=0.0)
    b.add(_req(key=("a",)), now=100.0)
    groups = b.due(now=100.0)                       # same instant: already due
    assert len(groups) == 1 and len(b) == 0


def test_batcher_force_flush_pops_every_group():
    b = Batcher(max_batch=8, max_delay_s=10.0)
    b.add(_req(key=("a",)), now=0.0)
    b.add(_req(kind="color", key=("b",)), now=0.0)
    b.add(_req(kind="coarsen", key=("c",)), now=0.0)
    assert b.due(now=0.1) == []                     # nothing due yet
    groups = b.due(now=0.1, force=True)
    assert len(groups) == 3 and len(b) == 0


def test_batcher_next_deadline_orders_batching_and_request_deadlines():
    b = Batcher(max_batch=8, max_delay_s=5.0)
    assert b.next_deadline(now=0.0) is None
    b.add(_req(key=("a",)), now=0.0)                # batch deadline at t=5
    assert b.next_deadline(now=0.0) == pytest.approx(5.0)
    b.add(_req(kind="color", key=("b",), deadline=2.0), now=0.0)
    assert b.next_deadline(now=0.0) == pytest.approx(2.0)   # request sooner
    assert b.next_deadline(now=1.5) == pytest.approx(0.5)
    assert b.next_deadline(now=3.0) == 0.0          # clamped, already late


def test_batcher_pop_expired_evicts_only_expired_requests():
    b = Batcher(max_batch=8, max_delay_s=100.0)
    live = _req(key=("a",), deadline=50.0)
    dead = _req(key=("a",), deadline=1.0)
    never = _req(kind="color", key=("b",))          # no deadline
    for r in (live, dead, never):
        b.add(r, now=0.0)
    expired = b.pop_expired(now=2.0)
    assert expired == [dead]
    assert len(b) == 2
    groups = b.due(now=2.0, force=True)
    popped = [r for _, reqs in groups for r in reqs]
    assert live in popped and never in popped and dead not in popped


def test_server_evicts_expired_request_before_dispatch():
    srv = Server(ServerConfig(max_delay_s=100.0))
    fut = srv.submit("mis2", _graph(12), deadline_s=0.001)
    time.sleep(0.01)
    with obs.capture() as cap:
        srv.pump()                                  # not forced: only evicts
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=5)
    assert srv.stats.expired == 1
    assert srv.stats.dispatches == 0                # never computed
    assert cap.value("serve.shed", {"reason": "expired"}) == 1


# ---------------------------------------------------------------------------
# stop(): terminal, typed, race-free
# ---------------------------------------------------------------------------

def test_stop_fails_queued_futures_with_server_closed():
    srv = Server(ServerConfig(max_delay_s=100.0))
    futs = [srv.submit("mis2", _graph(s)) for s in (13, 14)]
    srv.stop()
    for fut in futs:
        with pytest.raises(ServerClosed):
            fut.result(timeout=5)
    with pytest.raises(ServerClosed):               # post-stop submit
        srv.submit("mis2", _graph(15)).result(timeout=5)
    with pytest.raises(ServerClosed):
        srv.request("mis2", _graph(16))
    with pytest.raises(ServerClosed):
        srv.open_stream(_graph(17))
    with pytest.raises(ServerClosed):
        srv.start()
    srv.stop()                                      # idempotent


def test_concurrent_submitters_racing_shutdown_never_hang():
    srv = Server(ServerConfig(max_delay_s=0.0, poll_interval_s=0.001))
    srv.start()
    graphs = [_graph(20 + s, n=40) for s in range(4)]
    futures, lock = [], threading.Lock()
    stop_submitting = threading.Event()

    def submitter(i):
        k = 0
        while not stop_submitting.is_set():
            fut = srv.submit("mis2", graphs[(i + k) % len(graphs)])
            with lock:
                futures.append(fut)
            k += 1
            time.sleep(0.001)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    srv.stop()                                      # race against submitters
    stop_submitting.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert futures
    referents = {g.digest: repro.mis2(g).digest for g in graphs}
    served = closed = 0
    for fut in futures:
        try:
            res = fut.result(timeout=10)            # must resolve: no hangs
        except ServerClosed:
            closed += 1
        else:
            assert res.digest in referents.values()
            served += 1
    assert served + closed == len(futures)


# ---------------------------------------------------------------------------
# fault injection: deterministic, retried, degraded — never wrong
# ---------------------------------------------------------------------------

def test_fault_plan_is_deterministic_per_seed():
    def firing_pattern(seed):
        plan = FaultPlan(seed=seed, sites={
            "engine": Fault("error", rate=0.4)})
        return [plan.should_fire("engine") is not None for _ in range(64)]

    a, b = firing_pattern(7), firing_pattern(7)
    assert a == b                                   # same seed: same trace
    assert firing_pattern(8) != a                   # different seed differs
    assert 0 < sum(a) < 64                          # genuinely probabilistic


def test_fault_count_caps_firings():
    plan = FaultPlan(seed=0, sites={"engine": Fault("error", count=2)})
    fired = sum(plan.should_fire("engine") is not None for _ in range(10))
    assert fired == 2 and plan.fired["engine"] == 2


def test_transient_fault_retried_to_correct_digest():
    g = _graph(30)
    plan = FaultPlan(seed=1, sites={
        "engine": Fault("error", count=2, transient=True)})
    srv = Server(ServerConfig(
        faults=plan, retry=RetryPolicy(max_attempts=3, base_backoff_s=0.0)))
    with obs.capture() as cap:
        res = srv.request("mis2", g)
    assert res.digest == repro.mis2(g).digest
    assert srv.stats.retries == 2
    assert cap.value("serve.retries", {"site": "engine"}) == 2
    assert cap.value("serve.faults.injected", {"site": "engine"}) == 2


def test_persistent_fault_degrades_to_fallback_engine():
    g = _graph(31)
    plan = FaultPlan(seed=1, sites={
        "engine": Fault("error", transient=False)})
    srv = Server(ServerConfig(faults=plan))
    with obs.capture() as cap:
        res = srv.request("mis2", g)
    assert res.digest == repro.mis2(g, engine="dense").digest
    assert res.engine == "dense"
    assert srv.stats.fallbacks == 1
    assert cap.value("serve.fallbacks",
                     {"from": "auto", "to": "dense"}) == 1


def test_exhausted_retry_budget_falls_back():
    g = _graph(32)
    plan = FaultPlan(seed=1, sites={
        "engine": Fault("error", transient=True)})      # fires every visit
    srv = Server(ServerConfig(
        faults=plan, retry=RetryPolicy(max_attempts=2, base_backoff_s=0.0)))
    res = srv.request("mis2", g)
    assert res.digest == repro.mis2(g).digest
    assert srv.stats.retries == 1                   # attempts 1->2, then
    assert srv.stats.fallbacks == 1                 # budget spent: fallback


def test_fallback_disabled_surfaces_injected_fault():
    plan = FaultPlan(seed=1, sites={
        "engine": Fault("error", transient=False)})
    srv = Server(ServerConfig(
        faults=plan, retry=RetryPolicy(fallback=False)))
    fut = srv.submit("mis2", _graph(33))
    srv.flush()
    with pytest.raises(InjectedFault):
        fut.result(timeout=5)


def test_slow_fault_delays_but_serves_correctly():
    g = _graph(34)
    plan = FaultPlan(seed=1, sites={
        "dispatch": Fault("slow", count=1, delay_s=0.05)})
    srv = Server(ServerConfig(faults=plan))
    t0 = time.perf_counter()
    res = srv.request("mis2", g)
    assert time.perf_counter() - t0 >= 0.05
    assert res.digest == repro.mis2(g).digest


def test_streaming_repair_fault_degrades_to_exact_recompute():
    g = repro.Graph(laplace3d(4))
    plan = FaultPlan(seed=2, sites={"repair": Fault("error", count=1)})
    srv = Server(ServerConfig(faults=plan))
    sess = srv.open_stream(g)
    with obs.capture() as cap:
        res = sess.apply_delta(edge_adds=[(0, 9)])
    assert sess.last_repair.degraded
    assert sess.last_repair.mode == "recompute"
    assert cap.value("serve.fallbacks",
                     {"from": "repair", "to": "recompute"}) == 1
    assert res.digest == repro.mis2(sess.graph, engine="dense",
                                    options=sess.options).digest
    sess.apply_delta(edge_adds=[(1, 11)])           # fault spent: repairs
    assert sess.last_repair.mode == "repair"
    assert not sess.last_repair.degraded


def test_real_engine_exception_wrapped_as_engine_failure():
    srv = Server(ServerConfig(retry=RetryPolicy(fallback=False)))
    boom = RuntimeError("engine exploded")

    def exploding(reqs):
        raise boom

    srv._compute = exploding
    fut = srv.submit("mis2", _graph(35))
    srv.flush()
    with pytest.raises(EngineFailure) as ei:
        fut.result(timeout=5)
    assert ei.value.__cause__ is boom


def test_compile_error_fails_request_instead_of_falling_back():
    """A kernel that does not lower on this platform is an engine defect:
    the default fallback policy must not hide it behind the dense
    referent's (correct) answer."""
    srv = Server(ServerConfig())
    lowering = NotImplementedError("Only 2D gather is supported")

    def failing(reqs):
        raise RuntimeError("pallas_call failed") from lowering

    srv._compute = failing
    fut = srv.submit("mis2", _graph(37))
    srv.flush()
    with pytest.raises(EngineFailure, match="failed to compile") as ei:
        fut.result(timeout=5)
    assert ei.value.__cause__.__cause__ is lowering
    assert srv.stats.fallbacks == 0


# ---------------------------------------------------------------------------
# the digest ledger: one key, one digest, forever
# ---------------------------------------------------------------------------

def test_digest_ledger_refuses_conflicting_digest():
    g = _graph(36)
    srv = Server(ServerConfig(cache_bytes=0))       # force recompute path
    first = srv.request("mis2", g)
    key = next(iter(srv._ledger))
    srv._ledger[key] = "poisoned_digest!"           # simulate corruption
    fut = srv.submit("mis2", g)
    srv.flush()
    with pytest.raises(DigestMismatch):
        fut.result(timeout=5)
    assert first.converged                          # first answer unaffected


def test_digest_ledger_accepts_repeat_of_same_digest():
    g = _graph(37)
    srv = Server(ServerConfig(cache_bytes=0))
    a = srv.request("mis2", g)
    b = srv.request("mis2", g)                      # recomputed, same bytes
    assert a.digest == b.digest
    assert srv.server_stats()["ledger_keys"] == 1


# ---------------------------------------------------------------------------
# chaos: seeded faults + overload + deadlines, typed-or-correct throughout
# ---------------------------------------------------------------------------

def test_chaos_run_every_response_typed_or_digest_correct():
    graphs = [_graph(40 + s, n=60) for s in range(6)]
    referents = {g.digest: repro.mis2(g).digest for g in graphs}
    plan = FaultPlan(seed=9, sites={
        "engine": Fault("error", rate=0.3, transient=True),
        "dispatch": Fault("slow", rate=0.2, delay_s=0.002),
    })
    srv = Server(ServerConfig(
        max_pending=4, quota=QuotaConfig(rate=200.0, burst=8.0),
        default_deadline_s=30.0, faults=plan,
        retry=RetryPolicy(max_attempts=2, base_backoff_s=0.0),
        cache_bytes=0, dedup=False))
    futs = []
    for round_ in range(6):
        for i, g in enumerate(graphs):
            futs.append((g, srv.submit("mis2", g,
                                       caller=f"c{(round_ + i) % 3}")))
        srv.flush()
    srv.flush()
    served = shed = 0
    for g, fut in futs:
        assert fut.done()                           # nothing hangs
        try:
            res = fut.result()
        except ServeError:
            shed += 1
        else:
            assert res.digest == referents[g.digest]    # never wrong
            served += 1
    assert served > 0                               # progress under chaos
    assert served + shed == len(futs)
    stats = srv.server_stats()
    assert stats["retries"] + stats["fallbacks"] > 0    # faults really fired


# ---------------------------------------------------------------------------
# persistent tier: atomic, digest-verified, restart-safe
# ---------------------------------------------------------------------------

def test_persist_roundtrip_survives_restart(tmp_path):
    d = str(tmp_path / "tier")
    g = _graph(50)
    gc = repro.Graph(laplace3d(4))
    srv = Server(ServerConfig(persist_dir=d))
    ref_mis2 = srv.request("mis2", g)
    ref_color = srv.request("color", gc)
    ref_coarsen = srv.request("coarsen", gc)
    assert srv.persist.stats.writes == 3
    srv.stop()

    srv2 = Server(ServerConfig(persist_dir=d))      # fresh process stand-in
    assert srv2.request("mis2", g).digest == ref_mis2.digest
    assert srv2.request("color", gc).digest == ref_color.digest
    got = srv2.request("coarsen", gc)
    assert got.digest == ref_coarsen.digest
    assert np.array_equal(got.roots, ref_coarsen.roots)
    assert got.num_aggregates == ref_coarsen.num_aggregates
    assert srv2.persist.stats.hits == 3
    assert srv2.stats.dispatches == 0               # rehydrated, not computed
    assert srv2.persist.stats.corrupt == 0


def test_persist_corrupt_entry_dropped_never_served(tmp_path):
    d = str(tmp_path / "tier")
    g = _graph(51)
    plan = FaultPlan(seed=3, sites={
        "persist_corrupt": Fault("corrupt", count=1)})
    srv = Server(ServerConfig(persist_dir=d, faults=plan))
    ref = srv.request("mis2", g)                    # written corrupted
    srv.stop()

    with obs.capture() as cap:
        srv2 = Server(ServerConfig(persist_dir=d))
        res = srv2.request("mis2", g)               # verify -> drop -> compute
    assert res.digest == ref.digest
    assert srv2.persist.stats.corrupt == 1
    assert srv2.persist.stats.hits == 0
    assert srv2.stats.dispatches == 1               # recomputed honestly
    assert cap.value("serve.persist.corrupt") == 1
    assert len(srv2.persist) == 1                   # recompute re-persisted


def test_persist_torn_write_leaves_no_entry_and_is_swept(tmp_path):
    d = str(tmp_path / "tier")
    g = _graph(52)
    plan = FaultPlan(seed=4, sites={
        "persist_write": Fault("error", count=1)})
    srv = Server(ServerConfig(persist_dir=d, faults=plan))
    ref = srv.request("mis2", g)                    # commit crashed
    assert srv.persist.stats.writes == 0
    assert any(n.endswith(".tmp") for n in os.listdir(d))
    srv.stop()

    srv2 = Server(ServerConfig(persist_dir=d))
    assert srv2.persist.stats.torn_cleaned == 1     # orphan swept at open
    assert not any(n.endswith(".tmp") for n in os.listdir(d))
    assert srv2.request("mis2", g).digest == ref.digest
    assert srv2.stats.dispatches == 1               # disk had nothing usable


def test_persist_byte_budget_evicts_oldest(tmp_path):
    from repro.serve.persist import PersistTier

    tier = PersistTier(str(tmp_path / "tier"))
    graphs = [_graph(60 + s, n=120) for s in range(4)]
    results = [repro.mis2(g) for g in graphs]
    keys = [("mis2", g.digest, "auto", ()) for g in graphs]
    entry_bytes = []
    for k, r in zip(keys, results):
        assert tier.store(k, r)
        entry_bytes.append(tier.stats.bytes_used - sum(entry_bytes))
    # rebuild with a budget that holds ~2 entries
    budget = entry_bytes[-1] + entry_bytes[-2] + entry_bytes[-3] // 2
    tier2 = PersistTier(str(tmp_path / "tier2"), max_bytes=budget)
    for k, r in zip(keys, results):
        assert tier2.store(k, r)
        time.sleep(0.01)                            # distinct mtimes
    assert tier2.stats.evictions >= 1
    assert tier2.stats.bytes_used <= budget
    assert tier2.load(keys[-1]).digest == results[-1].digest    # newest kept
    assert tier2.load(keys[0]) is None              # oldest evicted
    assert tier2.stats.corrupt == 0


def test_persist_skips_amg_and_server_still_serves(tmp_path):
    from repro.graphs import er_laplacian

    d = str(tmp_path / "tier")
    m = repro.Graph(er_laplacian(120, 5.0, seed=6))
    srv = Server(ServerConfig(persist_dir=d))
    res = srv.request("amg_setup", m)
    assert res.num_levels >= 1
    assert srv.persist.stats.writes == 0            # memory-only kind
    assert len(srv.persist) == 0
    # ...but the in-memory cache still serves it
    assert srv.submit("amg_setup", m).result(timeout=5).digest == res.digest


def test_persist_wrong_key_same_address_not_served(tmp_path):
    from repro.serve.persist import PersistTier

    tier = PersistTier(str(tmp_path / "tier"))
    g = _graph(53)
    res = repro.mis2(g)
    key = ("mis2", g.digest, "auto", ())
    assert tier.store(key, res)
    # manifest key must match the *requested* key, not just the address
    other = ("mis2", g.digest, "dense", ())
    assert tier.load(other) is None
    assert tier.load(key).digest == res.digest


# ---------------------------------------------------------------------------
# I/O containment and lock scope: a broken disk or a slow retry never
# hangs a future, kills the pump thread, or blocks submit()
# ---------------------------------------------------------------------------

def _enospc(*a, **k):
    raise OSError(28, "No space left on device")


def test_persist_store_io_error_degrades_to_memory_only(tmp_path,
                                                        monkeypatch):
    g = _graph(70)
    srv = Server(ServerConfig(persist_dir=str(tmp_path / "tier")))
    monkeypatch.setattr("repro.serve.persist.np.savez", _enospc)
    fut = srv.submit("mis2", g)
    srv.flush()
    res = fut.result(timeout=30)            # resolves: no hang, no raise
    assert res.digest == repro.mis2(g).digest
    assert srv.persist.stats.io_errors == 1
    assert srv.persist.stats.writes == 0
    # the memory tier still serves the entry
    assert srv.submit("mis2", g).result(timeout=5).digest == res.digest


def test_pump_thread_survives_persist_io_errors(tmp_path, monkeypatch):
    monkeypatch.setattr("repro.serve.persist.np.savez", _enospc)
    srv = Server(ServerConfig(persist_dir=str(tmp_path / "tier"),
                              max_delay_s=0.0, poll_interval_s=0.001))
    g1, g2 = _graph(71), _graph(72)
    with srv:
        r1 = srv.submit("mis2", g1).result(timeout=30)
        r2 = srv.submit("mis2", g2).result(timeout=30)  # pump still alive
    assert r1.digest == repro.mis2(g1).digest
    assert r2.digest == repro.mis2(g2).digest
    assert srv.persist.stats.io_errors == 2


def test_pump_crash_fails_queued_futures_and_loop_survives():
    srv = Server(ServerConfig(max_delay_s=0.0, poll_interval_s=0.001,
                              cache_bytes=0))
    crashed = {"n": 0}
    orig_due = srv.batcher.due

    def flaky_due(now, force=False):
        if crashed["n"] == 0 and len(srv.batcher):
            crashed["n"] += 1
            raise RuntimeError("boom outside dispatch fan-out")
        return orig_due(now, force=force)

    srv.batcher.due = flaky_due
    with srv:
        fut = srv.submit("mis2", _graph(73))
        with pytest.raises(EngineFailure):  # typed, not a silent hang
            fut.result(timeout=30)
        g = _graph(74)                      # the loop kept pumping
        res = srv.submit("mis2", g).result(timeout=30)
    assert crashed["n"] == 1
    assert res.digest == repro.mis2(g).digest


def test_persist_load_utime_race_is_a_miss(tmp_path, monkeypatch):
    from repro.serve.persist import PersistTier

    tier = PersistTier(str(tmp_path / "tier"))
    g = _graph(75)
    key = ("mis2", g.digest, "auto", ())
    assert tier.store(key, repro.mis2(g))

    def vanished(*a, **k):
        raise FileNotFoundError("entry evicted by a sharing process")

    monkeypatch.setattr("repro.serve.persist.os.utime", vanished)
    misses = tier.stats.misses
    assert tier.load(key) is None           # a miss, never an exception
    assert tier.stats.misses == misses + 1


def test_persist_tampered_toplevel_digest_is_corrupt(tmp_path):
    import json

    from repro.serve.persist import PersistTier, entry_name

    tier = PersistTier(str(tmp_path / "tier"))
    g = _graph(76)
    key = ("mis2", g.digest, "auto", ())
    assert tier.store(key, repro.mis2(g))
    # corrupt ONLY the top-level digest; arrays and their digests stay valid
    mpath = os.path.join(tier.directory, entry_name(key), "manifest.json")
    with open(mpath) as fh:
        manifest = json.load(fh)
    manifest["digest"] = "0" * 16
    with open(mpath, "w") as fh:
        json.dump(manifest, fh)
    assert tier.load(key) is None           # dropped, not rehydrated
    assert tier.stats.corrupt == 1


def test_admission_bucket_recycling_is_lru_not_fifo(monkeypatch):
    from repro.serve import admission

    monkeypatch.setattr(admission, "MAX_TRACKED_CALLERS", 2)
    clock = [0.0]
    ctl = AdmissionController(quota=QuotaConfig(rate=0.0, burst=1.0),
                              clock=lambda: clock[0])
    ctl.admit(caller="hot")                 # hot's burst is now spent
    ctl.admit(caller="b")
    with pytest.raises(QuotaExceeded):
        ctl.admit(caller="hot")             # denied; refreshes hot's bucket
    ctl.admit(caller="c")                   # at capacity: evicts b, NOT hot
    with pytest.raises(QuotaExceeded):
        ctl.admit(caller="hot")             # hot never reset to full burst


def test_submit_not_blocked_by_slow_dispatch():
    plan = FaultPlan(seed=5, sites={
        "dispatch": Fault("slow", count=1, delay_s=0.5)})
    srv = Server(ServerConfig(faults=plan, max_delay_s=0.0,
                              poll_interval_s=0.001))
    with srv:
        slow = srv.submit("mis2", _graph(77))
        time.sleep(0.1)                     # pump is inside the 0.5s fault
        g = _graph(78)
        t0 = time.perf_counter()
        fast = srv.submit("mis2", g)
        submit_latency = time.perf_counter() - t0
        assert slow.result(timeout=30).converged
        assert fast.result(timeout=30).digest == repro.mis2(g).digest
    # pre-fix the injected sleep ran under the server lock, so this
    # submit would have blocked for the remaining ~0.4s of the fault
    assert submit_latency < 0.25
