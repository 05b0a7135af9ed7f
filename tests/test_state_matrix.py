"""StateMatrix metadata plane: packed-tensor maintenance, golden parity with
the reference evaluation paths, and the pluggable compute backends."""
import numpy as np
import pytest

from repro.core import layouts
from repro.core import workload as wl
from repro.engine import InMemoryBackend, StateMatrix


def make_meta(rng, p, c=6, n=3000):
    data = rng.uniform(0, 1, (n, c))
    order = np.argsort(data[:, int(rng.integers(c))], kind="stable")
    assignment = np.empty(n, dtype=np.int64)
    assignment[order] = np.arange(n) * p // n
    return layouts.metadata_from_assignment(data, assignment, p)


def make_query(rng, c=6):
    """Random conjunctive range query; unconstrained columns are [-inf, inf]
    exactly like the workload generator produces."""
    lo = np.full(c, -np.inf)
    hi = np.full(c, np.inf)
    k = int(rng.integers(0, c + 1))
    for col in rng.choice(c, size=k, replace=False):
        lo[col] = rng.uniform(0, 0.7)
        hi[col] = lo[col] + rng.uniform(0, 0.4)
    return lo, hi


@pytest.mark.parametrize("counts", [(16, 16, 16, 16),   # uniform: batched path
                                    (16, 7, 32, 5)])    # ragged: per-state path
def test_estimate_bit_identical_to_reference_paths(counts):
    rng = np.random.default_rng(0)
    metas = [make_meta(rng, p) for p in counts]
    sm = StateMatrix()
    for i, m in enumerate(metas):
        sm.register(i, m)
    for _ in range(30):
        lo, hi = make_query(rng)
        got = sm.estimate(lo, hi)
        ref = layouts.eval_cost_states(metas, lo, hi)
        singles = np.array([float(layouts.eval_cost(m, lo, hi))
                            for m in metas])
        assert np.array_equal(got, ref)          # bit-for-bit
        assert np.array_equal(got, singles)


def test_register_deregister_slot_swap_keeps_exact_metadata():
    rng = np.random.default_rng(1)
    metas = {i: make_meta(rng, int(rng.integers(4, 24))) for i in range(6)}
    sm = StateMatrix()
    for i, m in metas.items():
        sm.register(i, m)
    sm.deregister(2)        # middle slot: last slot swaps into the hole
    sm.deregister(5)
    sm.deregister(99)       # unknown id: no-op
    assert sorted(sm.state_ids) == [0, 1, 3, 4]
    assert len(sm) == 4 and 2 not in sm and 0 in sm
    for i in (0, 1, 3, 4):
        view = sm.metadata(i)
        assert np.array_equal(view.mins, metas[i].mins)
        assert np.array_equal(view.maxs, metas[i].maxs)
        assert np.array_equal(view.rows, metas[i].rows)
    lo, hi = make_query(rng)
    live = [metas[i] for i in sm.state_ids]
    assert np.array_equal(sm.estimate(lo, hi),
                          layouts.eval_cost_states(live, lo, hi))


def test_register_overwrite_and_partition_growth():
    rng = np.random.default_rng(2)
    sm = StateMatrix()
    small = make_meta(rng, 6)
    sm.register(0, small)
    assert sm.partition_capacity == 6
    big = make_meta(rng, 40)        # forces the plane to regrow P_cap
    sm.register(1, big)
    assert sm.partition_capacity == 40
    replacement = make_meta(rng, 12)
    sm.register(0, replacement)     # overwrite in place
    assert len(sm) == 2
    lo, hi = make_query(rng)
    assert np.array_equal(
        sm.estimate(lo, hi),
        layouts.eval_cost_states([replacement, big], lo, hi))


def test_estimate_costs_subset_and_empty():
    rng = np.random.default_rng(3)
    metas = [make_meta(rng, 8) for _ in range(3)]
    sm = StateMatrix()
    for i, m in enumerate(metas):
        sm.register(10 + i, m)
    lo, hi = make_query(rng)
    subset = sm.estimate_costs([11, 10], lo, hi)
    assert set(subset) == {10, 11}
    assert subset[10] == float(layouts.eval_cost(metas[0], lo, hi))
    assert sm.estimate_costs([], lo, hi) == {}
    assert StateMatrix().estimate(lo, hi).shape == (0,)
    with pytest.raises(KeyError):
        sm.estimate_costs([77], lo, hi)


def test_backend_registry_mirrors_matrix():
    """InMemoryBackend register/deregister keeps dict and plane in sync, and
    numpy estimates equal the reference backend's bit-for-bit."""
    rng = np.random.default_rng(4)
    data = rng.uniform(0, 1, (2000, 6))
    mem = InMemoryBackend(data)                         # StateMatrix plane
    ref = InMemoryBackend(data, compute="reference")    # legacy re-padding
    lays = [layouts.Layout(layout_id=i, name=f"l{i}", technique="synthetic",
                           meta=make_meta(rng, p))
            for i, p in enumerate((8, 8, 20))]
    for b in (mem, ref):
        for lay in lays:
            b.register(lay)
    for _ in range(20):
        lo, hi = make_query(rng)
        q = wl.Query(lo=lo, hi=hi)
        assert mem.estimate_costs([0, 1, 2], q) == ref.estimate_costs(
            [0, 1, 2], q)
    mem.deregister(1)
    assert sorted(mem.state_matrix.state_ids) == [0, 2]
    assert mem.states == [0, 2]


def test_pallas_compute_backend_parity():
    """The kernel-backed plane agrees with numpy on f32-representable data
    (the kernel evaluates in float32)."""
    rng = np.random.default_rng(5)
    c = 6
    data = rng.uniform(0, 1, (2000, c)).astype(np.float32).astype(np.float64)
    sm_np = StateMatrix()
    sm_pl = StateMatrix(compute_backend="pallas")
    for i in range(3):
        order = np.argsort(data[:, i % c], kind="stable")
        assignment = np.empty(len(data), dtype=np.int64)
        assignment[order] = np.arange(len(data)) * 16 // len(data)
        meta = layouts.metadata_from_assignment(data, assignment, 16)
        sm_np.register(i, meta)
        sm_pl.register(i, meta)
    for _ in range(5):
        lo, hi = make_query(rng, c)
        lo = lo.astype(np.float32).astype(np.float64)
        hi = hi.astype(np.float32).astype(np.float64)
        np.testing.assert_allclose(sm_pl.estimate(lo, hi),
                                   sm_np.estimate(lo, hi), atol=1e-12)


def test_pallas_backend_serve_stays_exact():
    """The serve-score fusion memo is numpy-only: under compute="pallas" a
    serve() after estimate_costs must still return the exact float64 cost,
    not the kernel's float32 estimate."""
    rng = np.random.default_rng(6)
    data = rng.uniform(0, 1, (2000, 4))
    backend = InMemoryBackend(data, compute="pallas")
    lay = layouts.Layout(layout_id=0, name="l0", technique="synthetic",
                         meta=make_meta(rng, 8, c=4))
    backend.register(lay)
    backend.activate(0)
    lo, hi = make_query(rng, c=4)
    q = wl.Query(lo=lo, hi=hi)
    before = backend.serve(q)
    backend.estimate_costs([0], q)
    after = backend.serve(q)
    want = float(layouts.eval_cost(lay.serving_meta(), lo, hi))
    assert before == after == want


def test_unknown_compute_backend_rejected():
    with pytest.raises(ValueError):
        StateMatrix(compute_backend="cuda")
    with pytest.raises(ValueError):
        InMemoryBackend(np.zeros((4, 2)), compute="nope")


# ---------------------------------------------------------------------------
# float32 downcast guard on the kernel compute backends
# ---------------------------------------------------------------------------

def test_float32_exact_predicate():
    from repro.engine import compute
    assert compute.float32_exact(np.array([0.5, 1.0, -np.inf, np.inf]))
    assert compute.float32_exact(np.ones(3, np.float32))
    # one ulp above 1.0 in float64 is strictly between float32 neighbours
    assert not compute.float32_exact(np.array([np.nextafter(1.0, 2.0)]))


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
def test_scan_matrix_f32_downcast_warns_and_stays_exact(backend):
    """A bound that is not exactly float32-representable must not be
    silently downcast: the kernel path warns and returns the exact numpy
    answer (regression test for the silent-float32 scan_matrix bug)."""
    from repro.engine import compute
    rng = np.random.default_rng(8)
    P, C, Q = 10, 4, 6
    p_min = rng.uniform(0, 1, (P, C)).astype(np.float32).astype(np.float64)
    p_max = p_min + 0.25
    q_lo = np.zeros((Q, C))
    q_hi = np.ones((Q, C))
    # exactly unrepresentable: sits between p_max's float32 neighbours, so
    # the old downcast flipped overlap verdicts at the boundary
    q_hi[0, 0] = np.nextafter(1.0, 2.0)
    want = compute.scan_matrix(q_lo, q_hi, p_min, p_max, backend="numpy")
    with pytest.warns(RuntimeWarning, match="float32"):
        got = compute.scan_matrix(q_lo, q_hi, p_min, p_max, backend=backend)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
def test_fleet_scan_matrix_f32_downcast_warns_and_stays_exact(backend):
    from repro.engine import compute
    rng = np.random.default_rng(9)
    T, N, C = 3, 8, 4
    mins = rng.uniform(0, 1, (T, N, C)).astype(np.float32).astype(np.float64)
    maxs = mins + 0.25
    q_lo = np.zeros((T, C))
    q_hi = np.ones((T, C))
    mins[1, 3, 2] = np.nextafter(0.5, 1.0)      # not float32-exact
    want = compute.fleet_scan_matrix(q_lo, q_hi, mins, maxs,
                                     backend="numpy")
    with pytest.warns(RuntimeWarning, match="float32"):
        got = compute.fleet_scan_matrix(q_lo, q_hi, mins, maxs,
                                        backend=backend)
    np.testing.assert_array_equal(got, want)


def test_pallas_fused_compute_backend_parity():
    """StateMatrix estimates under the megakernel backend == numpy on
    f32-representable data (same contract as the ``pallas`` backend)."""
    rng = np.random.default_rng(10)
    c = 6
    data = rng.uniform(0, 1, (2000, c)).astype(np.float32).astype(np.float64)
    sm_np = StateMatrix()
    sm_fu = StateMatrix(compute_backend="pallas_fused")
    for i in range(3):
        order = np.argsort(data[:, i % c], kind="stable")
        assignment = np.empty(len(data), dtype=np.int64)
        assignment[order] = np.arange(len(data)) * 16 // len(data)
        meta = layouts.metadata_from_assignment(data, assignment, 16)
        sm_np.register(i, meta)
        sm_fu.register(i, meta)
    for _ in range(5):
        lo, hi = make_query(rng, c)
        lo = lo.astype(np.float32).astype(np.float64)
        hi = hi.astype(np.float32).astype(np.float64)
        np.testing.assert_allclose(sm_fu.estimate(lo, hi),
                                   sm_np.estimate(lo, hi), atol=1e-12)


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
def test_state_matrix_f32_guard_warns_and_stays_exact(backend):
    """A float64 plane under a kernel backend warns and scores through the
    exact numpy pass, bit for bit."""
    rng = np.random.default_rng(11)
    sm_np = StateMatrix()
    sm_k = StateMatrix(compute_backend=backend)
    for i, p in enumerate((8, 8, 12)):
        meta = make_meta(rng, p)
        sm_np.register(i, meta)
        sm_k.register(i, meta)
    lo, hi = make_query(rng)
    with pytest.warns(RuntimeWarning, match="float32"):
        got = sm_k.estimate(lo, hi)
    assert np.array_equal(got, sm_np.estimate(lo, hi))


def test_total_rows_rounds_sample_scaled_counts():
    """Sample-estimated metadata scales counts by a non-integer n/m; a row
    sum that lands a hair under n must not truncate to n - 1, or a full
    scan is priced above 1.0 (the TPC-H SF1 row count hit this)."""
    meta = layouts.PartitionMetadata(mins=np.zeros((2, 1)),
                                     maxs=np.ones((2, 1)),
                                     rows=np.array([2.9999999999, 3.0]))
    assert meta.total_rows == 6
    full = layouts.eval_cost(meta, np.array([-np.inf]), np.array([np.inf]))
    assert 0.0 <= full <= 1.0 + 1e-9
