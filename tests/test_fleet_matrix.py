"""Tests for the packed multi-tenant FleetMatrix plane and the batched
fleet step path: incremental mirroring (tenant attach/detach, state
add/evict), bit-identical fused estimation, golden run_batched-vs-loop
traces across every drift scenario x scheduler, and primed-estimate
staleness handling."""
import numpy as np
import pytest

from repro.core import (OreoConfig, build_default_layout, layouts,
                        make_generator, workload as wl)
from repro.core import layout_manager as lm
from repro.core.workload import make_drift_scenario
from repro.engine import (Decision, FleetEngine, FleetMatrix,
                          InMemoryBackend, KConcurrentScheduler,
                          LayoutEngine, OreoPolicy, StateMatrix,
                          ThresholdSwitchPolicy, TokenBucketScheduler,
                          UnlimitedScheduler)


def make_meta(rng, partitions, columns, rows_per=50):
    data = rng.uniform(0, 100, size=(partitions * rows_per, columns))
    assignment = np.repeat(np.arange(partitions), rows_per)
    return layouts.metadata_from_assignment(data, assignment, partitions)


def make_query(rng, columns, bounded=None):
    lo = np.full(columns, -np.inf)
    hi = np.full(columns, np.inf)
    cols = (rng.choice(columns, size=bounded, replace=False)
            if bounded is not None else range(columns))
    for c in cols:
        a, b = np.sort(rng.uniform(0, 100, size=2))
        lo[c], hi[c] = a, b
    return lo, hi


# ---------------------------------------------------------------------------
# Incremental mirroring
# ---------------------------------------------------------------------------

def test_attach_syncs_existing_states_and_follows_events():
    rng = np.random.default_rng(0)
    sm = StateMatrix()
    sm.register(1, make_meta(rng, 4, 3))
    sm.register(2, make_meta(rng, 6, 3))
    fm = FleetMatrix()
    fm.attach("a", sm)
    assert fm.tenant_ids == ["a"]
    assert fm.state_ids("a") == sm.state_ids == [1, 2]
    # post-attach events stream through the listener
    sm.register(3, make_meta(rng, 2, 3))
    assert fm.state_ids("a") == sm.state_ids
    sm.deregister(1)        # swap-with-last in both planes
    assert fm.state_ids("a") == sm.state_ids
    assert all(fm.slot("a", sid) == sm.slot(sid) for sid in sm.state_ids)
    fm.detach("a")
    sm.register(4, make_meta(rng, 2, 3))     # no listener anymore
    assert "a" not in fm


def test_mirror_bounds_match_local_plane_exactly():
    rng = np.random.default_rng(1)
    sm = StateMatrix()
    fm = FleetMatrix()
    fm.attach("a", sm)
    for sid, p in [(5, 4), (7, 8), (9, 3)]:
        sm.register(sid, make_meta(rng, p, 2))
    sm.deregister(7)
    for sid in sm.state_ids:
        got = fm._mins[fm.tenant_row("a"), fm.slot("a", sid)]
        meta = sm.metadata(sid)
        np.testing.assert_array_equal(got[:meta.num_partitions],
                                      meta.mins)
        assert np.all(np.isinf(got[meta.num_partitions:]))


def test_detach_swaps_last_tenant_row_into_hole():
    rng = np.random.default_rng(2)
    sms = {}
    fm = FleetMatrix()
    for tid in ["a", "b", "c"]:
        sms[tid] = StateMatrix()
        sms[tid].register(0, make_meta(rng, 4, 2))
        fm.attach(tid, sms[tid])
    assert [fm.tenant_row(t) for t in ["a", "b", "c"]] == [0, 1, 2]
    fm.detach("a")
    assert len(fm) == 2 and fm.tenant_row("c") == 0
    # the moved tenant still scores correctly after the row swap
    lo, hi = make_query(rng, 2)
    frame = fm.estimate_frame([("c", lo, hi)])
    np.testing.assert_array_equal(frame[0][1], sms["c"].estimate(lo, hi))
    # detach is idempotent for unknown ids; double attach rejected
    fm.detach("zz")
    with pytest.raises(ValueError):
        fm.attach("b", sms["b"])
    fm.detach_all()
    assert len(fm) == 0


def test_capacity_growth_preserves_plane():
    rng = np.random.default_rng(3)
    fm = FleetMatrix(tenant_capacity=1, state_capacity=1)
    sms = {}
    for t in range(5):                      # tenant rows grow
        tid = f"t{t}"
        sms[tid] = StateMatrix()
        fm.attach(tid, sms[tid])
        for s in range(4):                  # slots grow
            sms[tid].register(s, make_meta(rng, 2 + 3 * s, 2))  # pcap grows
    for tid, sm in sms.items():
        lo, hi = make_query(rng, 2)
        frame = fm.estimate_frame([(tid, lo, hi)])
        version, costs = frame[0][0], frame[0][1]
        assert version == sm.version
        np.testing.assert_array_equal(costs, sm.estimate(lo, hi))


def test_column_count_mismatch_rejected():
    rng = np.random.default_rng(4)
    sm2 = StateMatrix()
    sm2.register(0, make_meta(rng, 4, 2))
    sm3 = StateMatrix()
    sm3.register(0, make_meta(rng, 4, 3))
    fm = FleetMatrix()
    fm.attach("a", sm2)
    with pytest.raises(ValueError):
        fm.attach("b", sm3)


# ---------------------------------------------------------------------------
# Fused estimation: bit-identical to every tenant's own plane
# ---------------------------------------------------------------------------

def test_estimate_frames_bit_identical_mixed_shapes():
    """Random tenants with mixed partition counts (uniform and ragged
    planes, so both the fused einsum and the per-tenant fallback paths
    run), random partially-bounded queries, several frames per pass."""
    rng = np.random.default_rng(5)
    columns = 4
    fm = FleetMatrix()
    sms = {}
    for t in range(6):
        tid = f"t{t}"
        sm = StateMatrix()
        parts = ([4] * 3 if t % 2 == 0          # uniform plane
                 else [3, 6, 2])                # ragged plane
        for sid, p in enumerate(parts):
            sm.register(sid, make_meta(rng, p, columns))
        sms[tid] = sm
        fm.attach(tid, sm)
    tids = sorted(sms)
    for trial in range(10):
        frames = []
        for _ in range(3):
            frame = []
            for tid in rng.permutation(tids)[:4]:
                bounded = int(rng.integers(0, columns + 1))
                lo, hi = make_query(rng, columns, bounded=bounded)
                frame.append((str(tid), lo, hi))
            frames.append(frame)
        out = fm.estimate_frames(frames)
        for frame, results in zip(frames, out):
            for (tid, lo, hi), res in zip(frame, results):
                assert res is not None
                version, costs = res[0], res[1]
                sm = sms[tid]
                assert version == sm.version
                want = sm.estimate(lo, hi)
                assert np.array_equal(costs, want)      # bitwise


def test_estimate_frame_unknown_or_empty_tenants_yield_none():
    rng = np.random.default_rng(6)
    fm = FleetMatrix()
    sm = StateMatrix()
    fm.attach("a", sm)                      # attached but no states yet
    lo, hi = make_query(rng, 3)
    assert fm.estimate_frame([("a", lo, hi), ("ghost", lo, hi)]) \
        == [None, None]
    sm.register(0, make_meta(rng, 4, 3))
    res = fm.estimate_frame([("a", lo, hi), ("ghost", lo, hi)])
    assert res[0] is not None and res[1] is None


def test_estimate_frame_serve_shadow_score_rides_along():
    rng = np.random.default_rng(7)
    data = rng.uniform(0, 100, size=(400, 3))
    backend = InMemoryBackend(data)
    lay = build_default_layout(0, data, 4)
    backend.register(lay)
    backend.activate(0)                     # registers SERVING_SHADOW (-1)
    fm = FleetMatrix()
    fm.attach("a", backend.state_matrix)
    lo, hi = make_query(rng, 3)
    version, costs, serve = fm.estimate_frame([("a", lo, hi)])[0]
    q = wl.Query(lo=lo, hi=hi)
    assert serve == backend.serve(q)        # exact shadow score
    slot = backend.state_matrix.slot(InMemoryBackend.SERVING_SHADOW)
    assert serve == float(costs[slot])


def test_pallas_fleet_compute_close_to_numpy():
    jax = pytest.importorskip("jax")  # noqa: F841
    rng = np.random.default_rng(8)
    sm = StateMatrix()
    for sid in range(3):
        sm.register(sid, make_meta(rng, 4, 3))
    exact = FleetMatrix(compute_backend="numpy")
    kern = FleetMatrix(compute_backend="pallas")
    exact.attach("a", sm)
    kern.attach("a", sm)
    lo, hi = make_query(rng, 3, bounded=2)
    want = exact.estimate_frame([("a", lo, hi)])[0][1]
    got = kern.estimate_frame([("a", lo, hi)])[0][1]
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# Primed estimates: consumed only when still valid
# ---------------------------------------------------------------------------

def test_primed_estimates_fall_back_on_version_churn():
    rng = np.random.default_rng(9)
    data = rng.uniform(0, 100, size=(600, 3))
    backend = InMemoryBackend(data)
    for sid in range(3):
        backend.register(build_default_layout(sid, data, 4,
                                              sort_col=sid % 3))
    q = wl.Query(*make_query(rng, 3, bounded=2))
    m = backend.state_matrix
    exact = backend.estimate_costs(range(3), q)
    # valid prime: bogus costs ARE consumed (proves the fast path runs)
    backend.prime_estimates(q, m.version, np.full(len(m), 0.5))
    assert all(v == 0.5 for v in backend.estimate_costs(range(3),
                                                        q).values())
    # stale prime (version bumped by state churn): exact path again
    backend.prime_estimates(q, m.version, np.full(len(m), 0.25))
    backend.register(build_default_layout(7, data, 4))
    assert backend.estimate_costs(range(3), q) == exact
    # different query object: prime ignored
    q2 = wl.Query(lo=q.lo.copy(), hi=q.hi.copy())
    backend.prime_estimates(q, m.version, np.full(len(m), 0.25))
    assert backend.estimate_costs(range(3), q2) \
        == backend.estimate_costs(range(3), q2)


def test_estimate_vector_matches_estimate_costs_and_serves_exact():
    rng = np.random.default_rng(10)
    data = rng.uniform(0, 100, size=(500, 3))
    backend = InMemoryBackend(data)
    for sid in range(3):
        backend.register(build_default_layout(sid, data, 4,
                                              sort_col=sid % 3))
    backend.activate(0)
    q = wl.Query(*make_query(rng, 3, bounded=2))
    vec = backend.estimate_vector(q)
    by_id = backend.estimate_costs(range(3), q)
    m = backend.state_matrix
    assert all(vec[m.slot(s)] == by_id[s] for s in range(3))
    # the fused serve memo is bit-exact vs a cold serve
    memo_serve = backend.serve(q)
    backend._serve_memo = None
    assert backend.serve(q) == memo_serve


def test_step_fast_trace_identical_to_step():
    rng = np.random.default_rng(11)
    data = rng.uniform(0, 100, size=(800, 4))
    queries = [wl.Query(*make_query(rng, 4, bounded=2)) for _ in range(40)]

    def engine():
        gen = make_generator("qdtree")
        cfg = OreoConfig(alpha=5.0, seed=3, delta=2,
                         manager=lm.LayoutManagerConfig(
                             target_partitions=4, window_size=20,
                             gen_every=10))
        policy = OreoPolicy(data, build_default_layout(0, data, 4), gen,
                            cfg)
        return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta)

    a, b = engine(), engine()
    for q in queries:
        a.step(q)
        b.step_fast(q)
    ra, rb = a.result(), b.result()
    assert np.array_equal(ra.query_costs, rb.query_costs)
    assert ra.reorg_indices == rb.reorg_indices
    assert np.array_equal(ra.state_seq, rb.state_seq)


# ---------------------------------------------------------------------------
# run_batched: golden identity with the stepwise loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tenant_data():
    return {f"t{t}": np.random.default_rng(100 + t).uniform(
        0, 100, size=(3_000, 6)) for t in range(3)}


@pytest.fixture(scope="module")
def bounds(tenant_data):
    lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)
    return lo, hi


def oreo_engine(data, alpha=10.0, delta=5, seed=2):
    gen = make_generator("qdtree")
    cfg = OreoConfig(alpha=alpha, seed=seed, delta=delta,
                     manager=lm.LayoutManagerConfig(target_partitions=8,
                                                    window_size=60,
                                                    gen_every=30))
    policy = OreoPolicy(data, build_default_layout(0, data, 8), gen, cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta)


SCHEDULERS = [
    ("unlimited", UnlimitedScheduler),
    ("k1", lambda: KConcurrentScheduler(1)),
    ("bucket", lambda: TokenBucketScheduler(rate=0.01, capacity=1.0,
                                            initial=0.0)),
]

ALL_SCENARIOS = ["sudden_shift", "gradual_drift", "cyclic_diurnal",
                 "flash_crowd", "template_churn"]


@pytest.mark.parametrize("scenario", ALL_SCENARIOS)
def test_run_batched_bit_identical_to_loop(scenario, tenant_data, bounds):
    """The acceptance gate: batched traces == stepwise traces, bit for
    bit, for every scenario under every scheduler (state churn included,
    exercising the primed-estimate fallback)."""
    lo, hi = bounds
    for _, factory in SCHEDULERS:
        fs = make_drift_scenario(scenario, lo, hi, num_tenants=3,
                                 queries_per_tenant=120, seed=7)
        loop = FleetEngine({tid: oreo_engine(tenant_data[tid])
                            for tid in fs.tenant_ids}, factory())
        r_loop = loop.run(fs)
        batched = FleetEngine({tid: oreo_engine(tenant_data[tid])
                               for tid in fs.tenant_ids}, factory())
        r_batched = batched.run_batched(fs)
        assert batched.fleet_matrix is not None
        for tid in fs.tenant_ids:
            a, b = r_loop.per_tenant[tid], r_batched.per_tenant[tid]
            assert np.array_equal(a.query_costs, b.query_costs)
            assert a.reorg_indices == b.reorg_indices
            assert np.array_equal(a.state_seq, b.state_seq)
        assert r_loop.swaps_deferred == r_batched.swaps_deferred
        assert r_loop.deferred_ticks == r_batched.deferred_ticks
        assert r_loop.scheduler_stats.get("grants") \
            == r_batched.scheduler_stats.get("grants")


def test_run_batched_requires_matrix_backed_backends(tenant_data):
    data = tenant_data["t0"]
    gen = make_generator("qdtree")
    cfg = OreoConfig(alpha=5.0, seed=1, delta=2)
    policy = OreoPolicy(data, build_default_layout(0, data, 8), gen, cfg)
    engine = LayoutEngine(policy, InMemoryBackend(data,
                                                  compute="reference"))
    fleet = FleetEngine({"t0": engine})
    with pytest.raises(ValueError, match="reference"):
        fleet.run_batched([])


def test_run_batched_resumable_and_mixed_with_step(tenant_data, bounds):
    """run_batched can be interleaved with plain step() calls; the plane
    stays attached and maintained across calls."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=3,
                             queries_per_tenant=90, seed=3)
    events = list(fs)
    ref = FleetEngine({tid: oreo_engine(tenant_data[tid])
                       for tid in fs.tenant_ids})
    r_ref = ref.run(events)
    mixed = FleetEngine({tid: oreo_engine(tenant_data[tid])
                         for tid in fs.tenant_ids})
    cut = len(events) // 3
    mixed.run_batched(events[:cut])
    version_before = mixed.fleet_matrix.version
    for tid, q in events[cut:2 * cut]:
        mixed.step(tid, q)
    # stepping outside run_batched still streams into the plane
    assert mixed.fleet_matrix.version >= version_before
    r_mixed = mixed.run_batched(events[2 * cut:])
    for tid in fs.tenant_ids:
        a, b = r_ref.per_tenant[tid], r_mixed.per_tenant[tid]
        assert np.array_equal(a.query_costs, b.query_costs)
        assert np.array_equal(a.state_seq, b.state_seq)


# ---------------------------------------------------------------------------
# Dynamic tenant membership
# ---------------------------------------------------------------------------

class FlipFlopPolicy:
    name = "FlipFlop"

    def __init__(self, layouts_, period, alpha=1.0):
        self.layouts = list(layouts_)
        self.period = period
        self.alpha = alpha
        self.cur = 0

    def bind(self, backend):
        for lay in self.layouts:
            backend.register(lay)
        return self.layouts[0].layout_id

    def decide(self, index, query, backend):
        if (index + 1) % self.period == 0:
            self.cur = 1 - self.cur
            return Decision(state=self.layouts[self.cur].layout_id,
                            reorg=True)
        return Decision(state=self.layouts[self.cur].layout_id)

    def info(self):
        return {}


def flipflop_engine(data, period=5, delta=2):
    lays = [build_default_layout(0, data, 8, sort_col=0),
            build_default_layout(1, data, 8, sort_col=1)]
    return LayoutEngine(FlipFlopPolicy(lays, period), InMemoryBackend(data),
                        delta=delta)


def full_scan(columns):
    return wl.Query(lo=np.full(columns, -np.inf),
                    hi=np.full(columns, np.inf))


def test_run_batched_identical_for_non_estimating_policies(tenant_data):
    """Regression: a policy that never calls estimate_costs (FlipFlop)
    cannot refresh the serve memo itself, so a swap landing at an earlier
    event of a multi-frame pass must invalidate the pass's pre-swap shadow
    scores — the version guard on the primed serve memo — or the batched
    trace silently serves stale costs."""
    d = tenant_data["t0"]
    rng = np.random.default_rng(4)
    events = []
    for i in range(120):
        lo = np.full(6, -np.inf)
        hi = np.full(6, np.inf)
        col = i % 6
        lo[col], hi[col] = np.sort(rng.uniform(0, 100, size=2))
        events.append(wl.QueryEvent("a", wl.Query(lo=lo, hi=hi)))
    for frames_per_pass in (1, 8, 64):
        loop = FleetEngine({"a": flipflop_engine(d, period=5, delta=2)})
        r_loop = loop.run(events)
        batched = FleetEngine({"a": flipflop_engine(d, period=5, delta=2)})
        r_batched = batched.run_batched(
            events, frames_per_pass=frames_per_pass)
        assert np.array_equal(r_loop.per_tenant["a"].query_costs,
                              r_batched.per_tenant["a"].query_costs), \
            f"stale serve memo leaked at frames_per_pass={frames_per_pass}"


def test_run_batched_rejects_unknown_compute_on_reuse(tenant_data):
    d = tenant_data["t0"]
    fleet = FleetEngine({"a": flipflop_engine(d)})
    q = full_scan(6)
    fleet.run_batched([wl.QueryEvent("a", q)])
    with pytest.raises(ValueError, match="compute"):
        fleet.run_batched([wl.QueryEvent("a", q)], compute="Pallas")


def test_add_and_remove_tenant_mid_flight(tenant_data):
    d = tenant_data["t0"]
    fleet = FleetEngine({"a": flipflop_engine(d)})
    q = full_scan(6)
    fleet.step("a", q)
    fleet.add_tenant("b", flipflop_engine(d))
    with pytest.raises(ValueError):
        fleet.add_tenant("b", flipflop_engine(d))
    fleet.step("b", q)
    assert set(fleet.tenant_ids) == {"a", "b"}
    engine = fleet.remove_tenant("b")
    assert engine.governor is None
    assert len(engine.result().query_costs) == 1
    assert fleet.tenant_ids == ["a"]
    # removed tenant is gone from the aggregate result
    assert set(fleet.result().per_tenant) == {"a"}
    with pytest.raises(KeyError):
        fleet.remove_tenant("b")


def test_remove_tenant_releases_scheduler_grants(tenant_data):
    d = tenant_data["t0"]
    sched = KConcurrentScheduler(1)
    fleet = FleetEngine({"a": flipflop_engine(d, period=1, delta=100),
                         "b": flipflop_engine(d, period=1, delta=100)},
                        sched)
    q = full_scan(6)
    fleet.step("a", q)      # a charges and acquires the single work unit
    fleet.step("b", q)      # b charges and queues behind a
    assert sched.in_flight == 1
    fleet.remove_tenant("a")
    assert sched.in_flight == 0     # a's grant returned to the pool
    fleet.step("b", q)              # b's queued work can now be granted
    assert sched.in_flight == 1


def test_add_tenant_attaches_to_existing_fleet_matrix(tenant_data):
    d = tenant_data["t0"]
    fleet = FleetEngine({"a": flipflop_engine(d)})
    q = full_scan(6)
    fleet.run_batched([wl.QueryEvent("a", q)])
    assert "a" in fleet.fleet_matrix
    fleet.add_tenant("b", flipflop_engine(d))
    assert "b" in fleet.fleet_matrix
    fleet.remove_tenant("b")
    assert "b" not in fleet.fleet_matrix


# ---------------------------------------------------------------------------
# pallas_fused backend: golden identity + the dense bulk decide path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", ALL_SCENARIOS)
def test_run_batched_pallas_fused_bit_identical_to_loop(scenario,
                                                        tenant_data,
                                                        bounds):
    """The megakernel backend honours the same bit-identity contract as
    compute="numpy": the float32 guard routes non-representable operands
    to the exact path, so fused-backend batched traces equal the stepwise
    loop under every scheduler."""
    lo, hi = bounds
    for _, factory in SCHEDULERS:
        fs = make_drift_scenario(scenario, lo, hi, num_tenants=3,
                                 queries_per_tenant=120, seed=7)
        loop = FleetEngine({tid: oreo_engine(tenant_data[tid])
                            for tid in fs.tenant_ids}, factory())
        r_loop = loop.run(fs)
        batched = FleetEngine({tid: oreo_engine(tenant_data[tid])
                               for tid in fs.tenant_ids}, factory())
        r_batched = batched.run_batched(fs, compute="pallas_fused")
        for tid in fs.tenant_ids:
            a, b = r_loop.per_tenant[tid], r_batched.per_tenant[tid]
            assert np.array_equal(a.query_costs, b.query_costs)
            assert a.reorg_indices == b.reorg_indices
            assert np.array_equal(a.state_seq, b.state_seq)
        assert r_loop.swaps_deferred == r_batched.swaps_deferred
        assert r_loop.deferred_ticks == r_batched.deferred_ticks
        assert r_loop.scheduler_stats.get("grants") \
            == r_batched.scheduler_stats.get("grants")


def threshold_engine(data, threshold, alpha=10.0, delta=2):
    space = [build_default_layout(sid, data, 8, sort_col=sid % data.shape[1])
             for sid in range(3)]
    return LayoutEngine(ThresholdSwitchPolicy(space, alpha=alpha,
                                              threshold=threshold),
                        InMemoryBackend(data), delta=delta)


@pytest.mark.parametrize("compute", ["numpy", "pallas_fused"])
@pytest.mark.parametrize("threshold", [0.0, 0.05, 1e9])
def test_threshold_bulk_path_bit_identical_to_loop(compute, threshold,
                                                   tenant_data, bounds):
    """Batch-decidable fleet (every policy implements decide_frames): the
    bulk decide path commits whole passes without per-event Python, and
    passes with switch/swap activity fall back — traces stay bit-identical
    to the loop under every scheduler, with and without reorgs."""
    lo, hi = bounds
    for _, factory in SCHEDULERS:
        fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=3,
                                 queries_per_tenant=120, seed=13)
        loop = FleetEngine({tid: threshold_engine(tenant_data[tid],
                                                  threshold)
                            for tid in fs.tenant_ids}, factory())
        r_loop = loop.run(fs)
        batched = FleetEngine({tid: threshold_engine(tenant_data[tid],
                                                     threshold)
                               for tid in fs.tenant_ids}, factory())
        r_batched = batched.run_batched(fs, compute=compute)
        for tid in fs.tenant_ids:
            a, b = r_loop.per_tenant[tid], r_batched.per_tenant[tid]
            assert np.array_equal(a.query_costs, b.query_costs)
            assert a.reorg_indices == b.reorg_indices
            assert np.array_equal(a.state_seq, b.state_seq)
        assert r_loop.swaps_deferred == r_batched.swaps_deferred
        assert r_loop.scheduler_stats.get("grants") \
            == r_batched.scheduler_stats.get("grants")


def test_bulk_path_engages_without_per_event_decide(tenant_data, bounds,
                                                    monkeypatch):
    """On a switch-free stretch the whole run must resolve through
    decide_frames — a single decide() call means the bulk path silently
    disengaged."""
    lo, hi = bounds
    fs = make_drift_scenario("sudden_shift", lo, hi, num_tenants=3,
                             queries_per_tenant=100, seed=17)

    def boom(self, index, query, backend):
        raise AssertionError("bulk path disengaged: decide() was called")

    monkeypatch.setattr(ThresholdSwitchPolicy, "decide", boom)
    fleet = FleetEngine({tid: threshold_engine(tenant_data[tid], 1e9,
                                               delta=0)
                         for tid in fs.tenant_ids})
    result = fleet.run_batched(fs)
    assert all(len(r.query_costs) == 100
               for r in result.per_tenant.values())


def test_bulk_path_runs_megakernel_on_f32_exact_data(monkeypatch):
    """float32-exact plane + queries under compute="pallas_fused": the
    megakernel actually scores the passes (no silent numpy fallback), and
    the trace still equals the stepwise loop bit for bit."""
    from repro.engine import compute as engine_compute
    rng = np.random.default_rng(23)
    data = {f"t{t}": rng.uniform(0, 100, size=(2_000, 4)).astype(
        np.float32).astype(np.float64) for t in range(3)}
    events = []
    for i in range(90):
        for tid in data:
            lo = np.full(4, -np.inf)
            hi = np.full(4, np.inf)
            col = (i + int(tid[1])) % 4
            a, b = np.sort(rng.uniform(0, 100, size=2).astype(
                np.float32).astype(np.float64))
            lo[col], hi[col] = a, b
            events.append(wl.QueryEvent(tid, wl.Query(lo=lo, hi=hi)))
    loop = FleetEngine({tid: threshold_engine(d, 0.05) for tid, d
                        in data.items()})
    r_loop = loop.run(events)
    calls = []
    real = engine_compute.fused_frames_scan

    def spy(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(engine_compute, "fused_frames_scan", spy)
    batched = FleetEngine({tid: threshold_engine(d, 0.05) for tid, d
                           in data.items()})
    r_batched = batched.run_batched(events, compute="pallas_fused")
    assert calls, "megakernel never ran on f32-exact operands"
    for tid in data:
        a, b = r_loop.per_tenant[tid], r_batched.per_tenant[tid]
        assert np.array_equal(a.query_costs, b.query_costs)
        assert a.reorg_indices == b.reorg_indices
        assert np.array_equal(a.state_seq, b.state_seq)


# ---------------------------------------------------------------------------
# Device-resident plane (pallas_fused): rebuilt once per plane version
# ---------------------------------------------------------------------------

def f32(a):
    return a.astype(np.float32).astype(np.float64)


def f32_meta(rng, partitions, columns, rows_per=50):
    data = f32(rng.uniform(0, 100, size=(partitions * rows_per, columns)))
    assignment = np.repeat(np.arange(partitions), rows_per)
    return layouts.metadata_from_assignment(data, assignment, partitions)


@pytest.fixture
def tracer():
    from repro import obs
    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()


def resident_fleet(rng, backend="pallas_fused"):
    """Tenants a, b with two float32-exact states of 4 partitions each, a
    fused fleet with room for one more tenant and two more states, and a
    numpy twin attached to the same tenant planes."""
    sms = {}
    for tid in "ab":
        sms[tid] = StateMatrix()
        for sid in range(2):
            sms[tid].register(sid, f32_meta(rng, 4, 3))
    fused = FleetMatrix(backend, tenant_capacity=3, state_capacity=4)
    exact = FleetMatrix("numpy", tenant_capacity=3, state_capacity=4)
    for tid, sm in sms.items():
        fused.attach(tid, sm)
        exact.attach(tid, sm)
    return fused, exact, sms


def f32_frames(rng, tids, n=2):
    frames = []
    for _ in range(n):
        frame = []
        for tid in tids:
            lo, hi = make_query(rng, 3, bounded=2)
            frame.append((tid, f32(lo), f32(hi)))
        frames.append(frame)
    return frames


def new_tenant(fleets, sms, rng, tid, states=2):
    sms[tid] = StateMatrix()
    for sid in range(states):
        sms[tid].register(sid, f32_meta(rng, 4, 3))
    for fm in fleets:
        fm.attach(tid, sms[tid])


def detach(fleets, tid):
    for fm in fleets:
        fm.detach(tid)


CHURN = {
    "attach": lambda f, s, r: [lambda: new_tenant(f, s, r, "c")],
    "register": lambda f, s, r: [
        lambda: s["a"].register(2, f32_meta(r, 4, 3))],
    "deregister_swap_with_last": lambda f, s, r: [
        lambda: s["a"].deregister(0)],
    "serving_shadow_reregister": lambda f, s, r: [
        lambda: s["b"].register(-1, f32_meta(r, 4, 3)),
        lambda: s["b"].register(-1, f32_meta(r, 4, 3))],
    "grow_tenants": lambda f, s, r: [
        lambda: new_tenant(f, s, r, "c"), lambda: new_tenant(f, s, r, "d")],
    "grow_states": lambda f, s, r: [
        lambda: s["a"].register(2, f32_meta(r, 4, 3)),
        lambda: s["a"].register(3, f32_meta(r, 4, 3)),
        lambda: s["a"].register(4, f32_meta(r, 4, 3))],
    "grow_partitions": lambda f, s, r: [
        lambda: s["b"].register(2, f32_meta(r, 9, 3))],
    "detach": lambda f, s, r: [lambda: detach(f, "a")],
}


@pytest.mark.parametrize("churn", sorted(CHURN))
def test_resident_plane_refreshes_once_per_mutation_and_stays_exact(
        churn, tracer):
    """Every plane mutation gives exactly one rebuild of the device plane,
    at the next pass, and none on later passes at the same version; each
    pass equals the numpy pass bit for bit, and the device plane holds the
    host twins' float32 values."""
    rng = np.random.default_rng(31)
    fused, exact, sms = resident_fleet(rng)
    steps = [lambda: None] + CHURN[churn]((fused, exact), sms, rng)
    refreshes = 0
    for step in steps:
        step()
        for _ in range(3):
            frames = f32_frames(rng, fused.tenant_ids)
            got = fused.estimate_frames(frames)
            want = exact.estimate_frames(frames)
            for g_frame, w_frame in zip(got, want):
                for g, w in zip(g_frame, w_frame):
                    assert g[0] == w[0] and g[2] == w[2]
                    assert np.array_equal(g[1], w[1])       # bitwise
        counters = tracer.snapshot()["counters"]
        assert counters["plane.refreshes"] == refreshes + 1
        refreshes += 1
        version, minsT, maxsT = fused._device_plane
        assert version == fused.version
        assert minsT.dtype == maxsT.dtype == np.float32
        assert np.array_equal(np.asarray(minsT),
                              fused._minsT.astype(np.float32))
        assert np.array_equal(np.asarray(maxsT),
                              fused._maxsT.astype(np.float32))
    assert "plane.fallbacks" not in counters
    assert counters["plane.passes"] == 3 * len(steps)


@pytest.mark.parametrize("case", ["numpy", "pallas", "switch_to_numpy",
                                  "switch_to_pallas", "detach_all",
                                  "same_backend_keeps"])
def test_only_the_fused_pass_holds_a_device_plane(case, tracer):
    rng = np.random.default_rng(37)
    backend = case if case in ("numpy", "pallas") else "pallas_fused"
    fm, _, _ = resident_fleet(rng, backend)
    fm.estimate_frames(f32_frames(rng, fm.tenant_ids, n=1))
    built = fm._device_plane
    assert (built is None) == (backend != "pallas_fused")
    if case.startswith("switch_to_"):
        fm.set_compute_backend(case[len("switch_to_"):])
        assert fm._device_plane is None
        fm.estimate_frames(f32_frames(rng, fm.tenant_ids, n=1))
        assert fm._device_plane is None
    elif case == "detach_all":
        fm.detach_all()
        assert fm._device_plane is None
    elif case == "same_backend_keeps":
        fm.set_compute_backend("pallas_fused")
        fm.estimate_frames(f32_frames(rng, fm.tenant_ids, n=1))
        assert fm._device_plane is built
    counters = tracer.snapshot()["counters"]
    assert counters.get("plane.refreshes", 0) == (backend == "pallas_fused")
