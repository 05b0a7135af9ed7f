"""Tests for the sharded routing plane (repro.engine.router + fleet
migration hooks).

Covers: golden bit-identity of the 1-shard router vs a plain FleetEngine
(all 10 scenarios — 5 drift + 5 ingest — under all 3 schedulers),
multi-shard trace identity under the unlimited scheduler, live tenant
migration mid-stream with bitwise-preserved traces and α charge ledgers
(including an in-flight incremental migration transplanted with its
partially-summed ledger — the FleetEngine.remove_tenant regression),
the EventSink protocol (ServeFrontend over a router ≡ over a fleet),
declarative SchedulerSpec construction with the single-use instance
shim, hysteresis-gated load rebalancing, and the process-parallel
ProcessShardSet agreeing with the inline router.
"""
import functools

import numpy as np
import pytest

from repro.core import (OreoConfig, build_default_layout, make_generator,
                        workload as wl)
from repro.core import layout_manager as lm
from repro.core.workload import make_drift_scenario, make_ingest_scenario
from repro.engine import (EventSink, FleetEngine, FleetRouter, IngestConfig,
                          InMemoryBackend, KConcurrentScheduler,
                          LayoutEngine, OreoPolicy, RebalanceConfig,
                          SchedulerSpec, TokenBucketScheduler,
                          UnlimitedScheduler, as_scheduler_spec)
from repro.serve import FrontendConfig, ServeFrontend


# ---------------------------------------------------------------------------
# Helpers / fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tenant_data():
    return {f"t{t}": np.random.default_rng(700 + t).uniform(
        0, 100, size=(2_000, 5)) for t in range(8)}


@pytest.fixture(scope="module")
def bounds(tenant_data):
    lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)
    return lo, hi


def oreo_engine(data, ingest=None, incremental=False, rows_per_tick=None,
                alpha=10.0, delta=5, seed=2):
    cfg = OreoConfig(alpha=alpha, seed=seed, delta=delta,
                     manager=lm.LayoutManagerConfig(target_partitions=8,
                                                    window_size=60,
                                                    gen_every=30))
    policy = OreoPolicy(data, build_default_layout(0, data, 8),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta,
                        ingest=ingest, incremental=incremental,
                        rows_per_tick=rows_per_tick)


SCHEDULER_SPECS = [
    ("unlimited", SchedulerSpec.unlimited()),
    ("k1", SchedulerSpec.k_concurrent(1)),
    ("bucket", SchedulerSpec.token_bucket(rate=0.01, capacity=1.0,
                                          initial=0.0)),
]

DRIFT_SCENARIOS = ["sudden_shift", "gradual_drift", "cyclic_diurnal",
                   "flash_crowd", "template_churn"]
INGEST_SCENARIOS = ["trickle", "append_heavy", "mixed_rw", "ingest_burst",
                    "bulk_load"]


def make_stream(scenario, lo, hi, num_tenants=2, qpt=60, seed=7):
    if scenario in DRIFT_SCENARIOS:
        return make_drift_scenario(scenario, lo, hi,
                                   num_tenants=num_tenants,
                                   queries_per_tenant=qpt, seed=seed)
    return make_ingest_scenario(scenario, lo, hi, num_tenants=num_tenants,
                                queries_per_tenant=qpt, seed=seed)


def make_tenants(fs, tenant_data, scenario, **engine_kw):
    ingest = IngestConfig() if scenario in INGEST_SCENARIOS else None
    return {tid: oreo_engine(tenant_data[tid], ingest=ingest, **engine_kw)
            for tid in fs.tenant_ids}


def assert_same_trace(a, b):
    assert np.array_equal(a.query_costs, b.query_costs)
    assert a.reorg_indices == b.reorg_indices
    assert np.array_equal(a.state_seq, b.state_seq)


# ---------------------------------------------------------------------------
# Golden identity: 1-shard router == plain fleet, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", DRIFT_SCENARIOS + INGEST_SCENARIOS)
def test_one_shard_router_bit_identical_to_fleet(scenario, tenant_data,
                                                 bounds):
    """All 10 scenarios x all 3 schedulers: a 1-shard router is trace-
    bitwise invisible — per-tenant traces, deferral counters, and the
    scheduler stats all equal the plain fleet's."""
    lo, hi = bounds
    for _, spec in SCHEDULER_SPECS:
        fs = make_stream(scenario, lo, hi)
        ref = FleetEngine(make_tenants(fs, tenant_data, scenario),
                          spec.build()).run(fs)
        router = FleetRouter(make_tenants(fs, tenant_data, scenario),
                             num_shards=1, scheduler=spec)
        got = router.run(fs)
        for tid in fs.tenant_ids:
            assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
        assert ref.ticks == got.ticks
        assert ref.swaps_deferred == got.swaps_deferred
        assert ref.deferred_ticks == got.deferred_ticks
        assert ref.scheduler_stats == got.scheduler_stats
        assert ref.scheduler == got.scheduler


def test_multi_shard_router_matches_unsharded_unlimited(tenant_data,
                                                        bounds):
    """Under the unlimited scheduler sharding is invisible: 8 tenants
    over 4 shards reproduce the unsharded traces bitwise, with the
    fleet counters summing across shards."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=8,
                             queries_per_tenant=80, seed=7)
    ref = FleetEngine(make_tenants(fs, tenant_data, "sudden_shift")).run(fs)
    router = FleetRouter(make_tenants(fs, tenant_data, "sudden_shift"),
                         num_shards=4)
    got = router.run(fs)
    assert len(set(router.placement().values())) > 1   # actually sharded
    for tid in fs.tenant_ids:
        assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
    assert got.ticks == ref.ticks
    assert set(got.scheduler_stats["shards"]) == set(router.shard_ids)


def test_router_run_batched_matches_run(tenant_data, bounds):
    lo, hi = bounds
    fs = make_drift_scenario("gradual_drift", lo, hi, num_tenants=4,
                             queries_per_tenant=60, seed=3)
    a = FleetRouter(make_tenants(fs, tenant_data, "gradual_drift"),
                    num_shards=2).run(fs)
    b = FleetRouter(make_tenants(fs, tenant_data, "gradual_drift"),
                    num_shards=2).run_batched(fs)
    for tid in fs.tenant_ids:
        assert np.array_equal(a.per_tenant[tid].query_costs,
                              b.per_tenant[tid].query_costs)
        assert np.array_equal(a.per_tenant[tid].state_seq,
                              b.per_tenant[tid].state_seq)


def test_router_topology_and_validation(tenant_data):
    with pytest.raises(ValueError, match="at least one tenant"):
        FleetRouter({})
    tenants = {tid: oreo_engine(d) for tid, d in tenant_data.items()}
    router = FleetRouter(tenants, num_shards=4)
    assert router.shard_ids == ["s0", "s1", "s2", "s3"]
    assert router.num_shards == 4
    assert sorted(router.tenant_ids) == sorted(tenant_data)
    placement = router.placement()
    for tid, sid in placement.items():
        assert router.shard_of(tid) == sid
        assert tid in router.shard(sid).tenant_ids
        assert router.tenant(tid) is tenants[tid]
    with pytest.raises(KeyError):
        router.shard_of("nope")
    with pytest.raises(KeyError):
        router.submit(wl.QueryEvent("nope", wl.Query(
            np.zeros(5), np.ones(5))))
    with pytest.raises(KeyError):
        router.migrate_tenant("t0", "s9")


def test_router_rejects_mixed_incremental_modes(tenant_data):
    tenants = {"t0": oreo_engine(tenant_data["t0"]),
               "t1": oreo_engine(tenant_data["t1"], incremental=True)}
    with pytest.raises(ValueError, match="mix incremental and atomic"):
        FleetRouter(tenants, num_shards=2)


# ---------------------------------------------------------------------------
# Live migration: traces and charge ledgers survive re-sharding bitwise
# ---------------------------------------------------------------------------

def test_migration_mid_stream_preserves_traces_bitwise(tenant_data, bounds):
    """Move half the tenants between shards mid-stream; every per-tenant
    trace still equals the never-sharded run bit for bit, and submits
    after the move route to the new home via a directory override."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=8,
                             queries_per_tenant=80, seed=7)
    ref = FleetEngine(make_tenants(fs, tenant_data, "sudden_shift")).run(fs)
    router = FleetRouter(make_tenants(fs, tenant_data, "sudden_shift"),
                         num_shards=4)
    events = list(fs)
    half = len(events) // 2
    for ev in events[:half]:
        router.submit(ev)
    router.drain()
    moved = []
    for tid in fs.tenant_ids[:4]:
        src = router.shard_of(tid)
        dst = next(s for s in router.shard_ids if s != src)
        assert router.migrate_tenant(tid, dst)
        assert router.shard_of(tid) == dst
        moved.append(tid)
    assert router.migrations == 4
    assert not router.migrate_tenant(moved[0], router.shard_of(moved[0]))
    for ev in events[half:]:
        router.submit(ev)
    router.drain()
    got = router.result()
    for tid in fs.tenant_ids:
        assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
    stats = router.stats()
    assert stats["migrations"] == 4
    assert stats["queue_depth"] == 0


def test_migration_carries_queued_events(tenant_data, bounds):
    """Events already queued for the tenant move with it (taken from the
    source inbox, replayed on the target) — nothing is lost or
    reordered."""
    lo, hi = bounds
    fs = make_drift_scenario("cyclic_diurnal", lo, hi, num_tenants=4,
                             queries_per_tenant=60, seed=5)
    ref = FleetEngine(make_tenants(fs, tenant_data, "cyclic_diurnal")).run(fs)
    router = FleetRouter(make_tenants(fs, tenant_data, "cyclic_diurnal"),
                         num_shards=2)
    for ev in fs:                       # queue everything, drain nothing
        router.submit(ev)
    tid = fs.tenant_ids[0]
    src = router.shard_of(tid)
    dst = next(s for s in router.shard_ids if s != src)
    assert router.migrate_tenant(tid, dst)
    router.drain()
    got = router.result()
    for t in fs.tenant_ids:
        assert_same_trace(ref.per_tenant[t], got.per_tenant[t])


def test_remove_tenant_refuses_queued_inbox_events(tenant_data, bounds):
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=2,
                             queries_per_tenant=10, seed=1)
    fleet = FleetEngine(make_tenants(fs, tenant_data, "sudden_shift"))
    for ev in fs:
        fleet.submit(ev)
    tid = fs.tenant_ids[0]
    with pytest.raises(ValueError, match="take_inbox"):
        fleet.remove_tenant(tid)
    inbox = fleet.take_inbox(tid)
    assert [ev.tenant_id for ev in inbox] == [tid] * len(inbox)
    assert fleet.queue_depth == len(list(fs)) - len(inbox)
    fleet.remove_tenant(tid)            # now legal
    assert tid not in fleet.tenant_ids


# ---------------------------------------------------------------------------
# The remove_tenant regression: detach mid-(incremental)-migration
# ---------------------------------------------------------------------------

def drive_until_in_flight(fleet, tid, events):
    """Feed events one at a time until ``tid`` has a partially-charged
    in-flight incremental migration; returns the remaining events."""
    events = list(events)
    while events:
        fleet.submit(events.pop(0))
        fleet.drain()
        ex = fleet.tenant(tid).reorg_executor
        active = ex.active
        if active is not None and 0.0 < active.charged < active.alpha:
            return events
    raise AssertionError("no partially-charged migration materialized")


def test_detach_mid_migration_transplants_partial_ledger(tenant_data,
                                                         bounds):
    """Detach a tenant while an incremental migration is in flight with a
    partially-summed charge ledger, re-attach it to a second fleet, and
    finish the stream there: the trace and every MigrationRecord charge
    ledger are bitwise identical to the never-detached run, with each
    ledger still telescoping to exactly α."""
    lo, hi = bounds
    tid = "t0"
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=1,
                             queries_per_tenant=200, seed=9)
    events = list(fs)
    def make():
        return FleetEngine({tid: oreo_engine(
            tenant_data[tid], incremental=True, rows_per_tick=40)})

    ref_fleet = make()
    ref = ref_fleet.run(events)

    fleet1 = make()
    remaining = drive_until_in_flight(fleet1, tid, events)
    record = fleet1.tenant(tid).reorg_executor.active
    partial = list(record.charges)
    assert 0.0 < record.charged < record.alpha

    engine = fleet1.remove_tenant(tid)
    assert tid not in fleet1.tenant_ids
    assert engine.reorg_executor.active is record       # still in flight

    fleet2 = FleetEngine({}, incremental=True)
    fleet2.add_tenant(tid, engine)
    for ev in remaining:
        fleet2.submit(ev)
    fleet2.drain()
    got = fleet2.result()

    assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
    ref_migs = ref_fleet.tenant(tid).reorg_executor.migrations
    got_migs = fleet2.tenant(tid).reorg_executor.migrations
    assert len(ref_migs) == len(got_migs)
    for a, b in zip(ref_migs, got_migs):
        assert a.charges == b.charges                   # bitwise ledger
        assert a.completed_at == b.completed_at
        if b.completed_at >= 0:
            assert b.charged == b.alpha                 # telescopes to α
    # the transplanted record kept its pre-detach prefix untouched
    assert any(m.charges[:len(partial)] == partial for m in got_migs)


def test_detach_with_finish_closes_ledger_on_alpha(tenant_data, bounds):
    """remove_tenant(finish=True) completes the in-flight migration at
    the detach index; the ledger closes bitwise on α and the tenant is
    immediately re-attachable with no executor state in flight."""
    lo, hi = bounds
    tid = "t0"
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=1,
                             queries_per_tenant=200, seed=9)
    fleet1 = FleetEngine({tid: oreo_engine(
        tenant_data[tid], incremental=True, rows_per_tick=40)})
    remaining = drive_until_in_flight(fleet1, tid, list(fs))
    record = fleet1.tenant(tid).reorg_executor.active
    detach_index = fleet1.tenant(tid)._index

    engine = fleet1.remove_tenant(tid, finish=True)
    assert engine.reorg_executor.active is None
    assert record.charged == record.alpha               # closed bitwise
    assert record.completed_at == detach_index
    assert sum(rows for _, rows, _ in record.charges) == record.total_rows

    fleet2 = FleetEngine({}, incremental=True)
    fleet2.add_tenant(tid, engine)
    for ev in remaining:
        fleet2.submit(ev)
    fleet2.drain()
    res = fleet2.result().per_tenant[tid]
    costs = np.asarray(res.query_costs)
    assert np.all((costs >= 0) & (costs <= 1))
    for mig in fleet2.tenant(tid).reorg_executor.migrations:
        if mig.completed_at >= 0:
            assert mig.charged == mig.alpha


def test_router_migration_of_incremental_tenants_bitwise(tenant_data,
                                                         bounds):
    """End to end through the router: incremental tenants with a tight
    row budget, migrated mid-stream, still reproduce the unsharded
    traces and ledgers bitwise."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=4,
                             queries_per_tenant=100, seed=11)
    def make():
        return {tid: oreo_engine(tenant_data[tid], incremental=True,
                                 rows_per_tick=60)
                for tid in fs.tenant_ids}

    ref_fleet = FleetEngine(make())
    ref = ref_fleet.run(fs)
    router = FleetRouter(make(), num_shards=2)
    events = list(fs)
    third = len(events) // 3
    for ev in events[:third]:
        router.submit(ev)
    router.drain()
    for tid in fs.tenant_ids:
        src = router.shard_of(tid)
        dst = next(s for s in router.shard_ids if s != src)
        router.migrate_tenant(tid, dst)
    for ev in events[third:]:
        router.submit(ev)
    router.drain()
    got = router.result()
    for tid in fs.tenant_ids:
        assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
        a = ref_fleet.tenant(tid).reorg_executor.migrations
        b = router.tenant(tid).reorg_executor.migrations
        assert [m.charges for m in a] == [m.charges for m in b]


# ---------------------------------------------------------------------------
# EventSink: the serving tier sits over a fleet or a router unchanged
# ---------------------------------------------------------------------------

PERMISSIVE = dict(queue_capacity=100_000, breaker_open_frac=None,
                  record_latency=False)


def test_fleet_and_router_satisfy_event_sink(tenant_data):
    fleet = FleetEngine({"t0": oreo_engine(tenant_data["t0"])})
    router = FleetRouter({"t0": oreo_engine(tenant_data["t0"])})
    assert isinstance(fleet, EventSink)
    assert isinstance(router, EventSink)
    assert fleet.shard_fleets() == [fleet]
    assert router.shard_fleets() == [router.shard("s0")]


def test_frontend_over_one_shard_router_matches_fleet(tenant_data, bounds):
    """ServeFrontend(FleetRouter) at 1 shard ≡ ServeFrontend(FleetEngine):
    the serving tier cannot tell them apart, trace-bitwise."""
    lo, hi = bounds
    for scenario in ("sudden_shift", "trickle"):
        fs = make_stream(scenario, lo, hi)
        fleet = FleetEngine(make_tenants(fs, tenant_data, scenario))
        ref = ServeFrontend(fleet, FrontendConfig(**PERMISSIVE)).run(fs)
        router = FleetRouter(make_tenants(fs, tenant_data, scenario))
        got = ServeFrontend(router, FrontendConfig(**PERMISSIVE)).run(fs)
        for tid in fs.tenant_ids:
            assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
        assert ref.scheduler_stats == got.scheduler_stats


def test_frontend_over_multi_shard_router(tenant_data, bounds):
    """A multi-shard router behind the frontend still reproduces the
    unsharded traces (unlimited scheduler), and the frontend's
    scheduler stats nest per shard."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=4,
                             queries_per_tenant=60, seed=7)
    fleet = FleetEngine(make_tenants(fs, tenant_data, "sudden_shift"))
    ref = ServeFrontend(fleet, FrontendConfig(**PERMISSIVE)).run(fs)
    router = FleetRouter(make_tenants(fs, tenant_data, "sudden_shift"),
                         num_shards=2)
    fe = ServeFrontend(router, FrontendConfig(**PERMISSIVE))
    got = fe.run(fs)
    for tid in fs.tenant_ids:
        assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
    sched = fe.stats()["scheduler"]
    assert len(sched["shards"]) == 2    # per-shard scheduler stats nest


# ---------------------------------------------------------------------------
# SchedulerSpec: declarative construction + the single-use instance shim
# ---------------------------------------------------------------------------

def test_scheduler_spec_builds_fresh_instances():
    spec = SchedulerSpec.k_concurrent(2)
    a, b = spec.build(), spec.build()
    assert a is not b
    assert isinstance(a, KConcurrentScheduler)
    assert a.k == 2
    assert spec.name == a.name
    bucket = SchedulerSpec.token_bucket(rate=0.5, capacity=2.0,
                                        initial=1.0)
    sched = bucket.build()
    assert isinstance(sched, TokenBucketScheduler)
    assert isinstance(SchedulerSpec.unlimited().build(),
                      UnlimitedScheduler)
    with pytest.raises(ValueError, match="unknown scheduler kind"):
        SchedulerSpec(kind="nope").build()


def test_fleet_engine_accepts_spec(tenant_data):
    fleet = FleetEngine({"t0": oreo_engine(tenant_data["t0"])},
                        SchedulerSpec.k_concurrent(1))
    assert isinstance(fleet.scheduler, KConcurrentScheduler)


def test_instance_shim_warns_and_is_single_use(tenant_data):
    with pytest.warns(DeprecationWarning, match="SchedulerSpec"):
        shim = as_scheduler_spec(KConcurrentScheduler(1))
    built = shim.build()
    assert isinstance(built, KConcurrentScheduler)
    with pytest.raises(ValueError, match="cannot be shared"):
        shim.build()
    with pytest.raises(TypeError):
        as_scheduler_spec(object())


def test_router_with_instance_scheduler_refuses_multiple_shards(
        tenant_data):
    """A bare scheduler instance cannot be shared across shards — the
    single-use shim lets a 1-shard router keep working and makes a
    multi-shard router fail loudly instead of silently sharing state."""
    def tenants():
        return {tid: oreo_engine(d)
                for tid, d in list(tenant_data.items())[:4]}

    with pytest.warns(DeprecationWarning):
        router = FleetRouter(tenants(), num_shards=1,
                             scheduler=KConcurrentScheduler(1))
    assert isinstance(router.shard("s0").scheduler, KConcurrentScheduler)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="cannot be shared"):
            FleetRouter(tenants(), num_shards=2,
                        scheduler=KConcurrentScheduler(1))


# ---------------------------------------------------------------------------
# Load-skew rebalancing: hysteresis-gated, at drain boundaries only
# ---------------------------------------------------------------------------

def test_rebalancer_moves_hot_tenant_once(tenant_data, bounds):
    """Skew every event onto one shard: after a full window the meter
    fires exactly once (hysteresis disarms), the move lands as a
    directory override, and traffic follows the tenant."""
    lo, hi = bounds
    tenants = {tid: oreo_engine(d) for tid, d in tenant_data.items()}
    cfg = RebalanceConfig(window=64, high=1.3, low=1.05)
    router = FleetRouter(tenants, num_shards=2, rebalance=cfg)
    by_shard = {}
    for tid in router.tenant_ids:
        by_shard.setdefault(router.shard_of(tid), []).append(tid)
    hot = max(by_shard, key=lambda s: len(by_shard[s]))
    assert len(by_shard[hot]) >= 2      # 8 tenants over 2 shards
    rng = np.random.default_rng(3)

    def q():
        lo_q = rng.uniform(lo, hi)
        return wl.Query(lo_q, np.minimum(lo_q + 5.0, hi))

    # two windows of traffic pinned to the hot shard, spread over its
    # tenants so the hottest tenant's share fits under the mean
    for _ in range(3):
        for _ in range(cfg.window):
            for tid in by_shard[hot]:
                router.submit(wl.QueryEvent(tid, q()))
        router.drain()
    assert router.migrations == 1       # armed once, then disarmed
    overrides = router.directory.overrides
    assert len(overrides) == 1
    moved_tid, new_home = next(iter(overrides.items()))
    assert new_home != hot
    assert router.shard_of(moved_tid) == new_home
    stats = router.stats()
    assert stats["rebalancer"]["moves_suggested"] == 1
    assert stats["rebalancer"]["armed"] is False
    # traffic now follows the override
    router.submit(wl.QueryEvent(moved_tid, q()))
    assert router.shard(new_home).queue_depth == 1


def test_rebalancer_idle_without_config(tenant_data, bounds):
    lo, hi = bounds
    router = FleetRouter({tid: oreo_engine(d)
                          for tid, d in tenant_data.items()}, num_shards=2)
    assert router.maybe_rebalance() is None
    assert router.stats()["rebalancer"] is None


# ---------------------------------------------------------------------------
# Process-parallel shards (repro.launch.shard_host)
# ---------------------------------------------------------------------------

def _make_tenant_engine(seed):
    """Module-level so spawn workers can unpickle it."""
    data = np.random.default_rng(700 + seed).uniform(
        0, 100, size=(2_000, 5))
    return oreo_engine(data)


def test_process_shard_set_matches_inline_router(tenant_data, bounds):
    """Two spawned shard processes under the router's placement produce
    the same merged result as the inline router — and migration works
    across process boundaries."""
    shard_host = pytest.importorskip("repro.launch.shard_host")
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=4,
                             queries_per_tenant=40, seed=7)
    factories = {f"t{t}": functools.partial(_make_tenant_engine, t)
                 for t in range(4)}
    inline = FleetRouter({tid: f() for tid, f in factories.items()},
                         num_shards=2)
    ref = inline.run(fs)
    with shard_host.ProcessShardSet(factories, num_shards=2) as procs:
        assert procs.shard_ids == inline.shard_ids
        for tid in factories:
            assert procs.shard_of(tid) == inline.shard_of(tid)
        for ev in fs:
            procs.submit(ev)
        procs.drain()
        got = procs.result()
        for tid in fs.tenant_ids:
            assert_same_trace(ref.per_tenant[tid], got.per_tenant[tid])
        assert got.ticks == ref.ticks
        # migrate one tenant across processes and keep serving
        tid = fs.tenant_ids[0]
        dst = next(s for s in procs.shard_ids if s != procs.shard_of(tid))
        assert procs.migrate_tenant(tid, dst)
        assert procs.shard_of(tid) == dst
        extra = make_drift_scenario("sudden_shift", lo, hi, num_tenants=4,
                                    queries_per_tenant=10, seed=8)
        for ev in extra:
            procs.submit(ev)
        assert procs.drain() == len(list(extra))
        assert procs.stats()["migrations"] == 1


def test_process_shard_set_refuses_kernel_drain_while_parent_holds_tpu(
        tenant_data, bounds, monkeypatch):
    """A worker cannot reach a chip the parent holds: a pallas drain fails
    loudly before any worker is asked, and a numpy drain still serves."""
    shard_host = pytest.importorskip("repro.launch.shard_host")
    from repro.kernels import _backend
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=2,
                             queries_per_tenant=10, seed=9)
    factories = {f"t{t}": functools.partial(_make_tenant_engine, t)
                 for t in range(2)}
    monkeypatch.setattr(_backend, "initialized_platform", lambda: "tpu")
    with shard_host.ProcessShardSet(factories, num_shards=1) as procs:
        for ev in fs:
            procs.submit(ev)
        with pytest.raises(RuntimeError, match="holds the TPU"):
            procs.drain(batched=True, compute="pallas_fused")
        assert procs.drain(batched=True, compute="numpy") == len(list(fs))


@pytest.mark.parametrize("platform", [None, "cpu"])
def test_worker_kernel_drain_allowed_when_parent_holds_no_tpu(platform,
                                                              monkeypatch):
    shard_host = pytest.importorskip("repro.launch.shard_host")
    from repro.kernels import _backend
    monkeypatch.setattr(_backend, "initialized_platform", lambda: platform)
    shard_host._check_worker_compute("pallas_fused")
    shard_host._check_worker_compute("numpy")
