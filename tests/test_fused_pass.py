"""The fused plane pass (``compute.fused_frames_scan``) as one device
program: the queries ride the kernel's dispatch, the ``> 0.5`` threshold
runs inside the compiled program, and the pass reads back one bool mask.
The decision megakernel runs in interpret mode on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import layouts
from repro.engine import FleetMatrix, StateMatrix, compute
from repro.kernels.decision_fused import decision_fused

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

C, T, S, P = 3, 2, 3, 200


def f32(a):
    return np.asarray(a, np.float32).astype(np.float64)


def plane(rng):
    """A float32-exact ``(C, T, S, P)`` plane whose last state of each
    tenant is a padded slot, ``[+inf, -inf]``, as the packed planes keep
    (only a query unbounded on every column scans it)."""
    lo = f32(rng.uniform(0, 100, size=(C, T, S, P)))
    hi = np.maximum(lo, f32(lo + rng.uniform(0, 30, size=lo.shape)))
    lo[:, :, -1], hi[:, :, -1] = np.inf, -np.inf
    return lo, hi


def frames(rng, b):
    """(b, T, C) query bounds: each column bounded, half-open or unbounded,
    and the first tenant of the first frame unbounded on every column."""
    lo = f32(rng.uniform(0, 100, size=(b, T, C)))
    hi = f32(lo + rng.uniform(0, 60, size=lo.shape))
    kind = rng.integers(0, 4, size=lo.shape)
    lo[kind == 1] = -np.inf
    hi[kind == 2] = np.inf
    lo[kind == 3], hi[kind == 3] = -np.inf, np.inf
    lo[0, 0], hi[0, 0] = -np.inf, np.inf
    return lo, hi


@pytest.mark.parametrize("resident", [False, True],
                         ids=["numpy_plane", "device_plane"])
@pytest.mark.parametrize("b", range(1, 33))
def test_fused_frames_scan_equals_the_exact_overlap(b, resident):
    """Every frame count 1-32 (padded to a power of two inside) gives the
    exact float64 overlap bit for bit, as a numpy bool ``(b, T, S, P)``:
    no padded frame reaches the caller."""
    rng = np.random.default_rng(1900 + b)
    mins, maxs = plane(rng)
    q_lo, q_hi = frames(rng, b)
    want = compute.fleet_masked_overlap(mins, maxs, q_lo, q_hi)
    if resident:
        mins, maxs = (jnp.asarray(a, jnp.float32) for a in (mins, maxs))
    got = compute.fused_frames_scan(q_lo, q_hi, mins, maxs)
    assert isinstance(got, np.ndarray)
    assert got.dtype == np.bool_ and got.shape == (b, T, S, P)
    assert np.array_equal(got, want)
    assert got[0, 0].all()                  # unbounded query: everything


def test_the_mask_is_the_thresholded_scan():
    """``mask=True`` thresholds inside the program; without it the scan is
    the float 0/1 it always was, as ``ops.fused_decision`` returns it."""
    rng = np.random.default_rng(7)
    mins, maxs = (a.astype(np.float32) for a in plane(rng))
    q_lo, q_hi = (a.astype(np.float32) for a in frames(rng, 4))
    scan, _, _ = decision_fused.fused_decision_pallas(q_lo, q_hi, mins, maxs)
    mask, cost, freq = decision_fused.fused_decision_pallas(
        q_lo, q_hi, mins, maxs, mask=True)
    assert cost is None and freq is None
    assert scan.dtype == jnp.float32 and mask.dtype == jnp.bool_
    assert mask.shape == scan.shape == (4, T, S, P)
    scan = np.asarray(scan)
    assert set(np.unique(scan).tolist()) <= {0.0, 1.0}
    assert np.array_equal(np.asarray(mask), scan > 0.5)


# ---------------------------------------------------------------------------
# A fleet pass over the resident plane
# ---------------------------------------------------------------------------

def f32_meta(rng, partitions, columns, rows_per=50):
    data = f32(rng.uniform(0, 100, size=(partitions * rows_per, columns)))
    assignment = np.repeat(np.arange(partitions), rows_per)
    return layouts.metadata_from_assignment(data, assignment, partitions)


def fleet(rng, columns):
    """A fused fleet of tenants a, b with two float32-exact states each."""
    fm = FleetMatrix("pallas_fused", tenant_capacity=3, state_capacity=4)
    for tid in "ab":
        sm = StateMatrix()
        for sid in range(2):
            sm.register(sid, f32_meta(rng, 4, columns))
        fm.attach(tid, sm)
    return fm


def fleet_frames(rng, fm, n, columns):
    out = []
    for _ in range(n):
        frame = []
        for tid in fm.tenant_ids:
            lo, hi = np.full(columns, -np.inf), np.full(columns, np.inf)
            col = rng.integers(columns)
            lo[col], hi[col] = np.sort(f32(rng.uniform(0, 100, size=2)))
            frame.append((tid, lo, hi))
        out.append(frame)
    return out


@pytest.fixture
def compiles():
    """Backend compiles and persistent-cache hits while ``on``."""
    seen = {"compiles": 0, "hits": 0, "on": False}

    def duration(event, secs, **_):
        if seen["on"] and event == BACKEND_COMPILE_EVENT:
            seen["compiles"] += 1

    def hit(event, **_):
        if seen["on"] and event == CACHE_HIT_EVENT:
            seen["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(hit)
    yield seen
    jax.monitoring.unregister_event_duration_listener(duration)
    jax.monitoring.unregister_event_listener(hit)


def test_a_numpy_warm_up_compiles_the_resident_plane_pass(compiles):
    """Warmed as the benchmark warms the window's shapes (numpy float64
    zeros for the queries and the plane), a fleet pass over the resident
    device plane at those shapes compiles nothing.  The plane went up
    before, as the benchmark's warm-up queries put it there."""
    columns, n = 5, 4
    rng = np.random.default_rng(11)
    fm = fleet(rng, columns)
    resident = fm._resident_plane()
    c, t, s, p = fm._minsT.shape
    compiles["on"] = True
    for b in range(1, n + 1):
        q = np.zeros((b, t, c))
        zeros = np.zeros((c, t, s, p))
        compute.fused_frames_scan(q, q, zeros, zeros)
    warmed = compiles["compiles"] + compiles["hits"]
    compiles["compiles"] = compiles["hits"] = 0
    for b in range(1, n + 1):
        fm.estimate_frames(fleet_frames(rng, fm, b, columns))
    compiles["on"] = False
    assert warmed > 0
    # no refresh in the window: the same device arrays are still resident
    assert all(a is r for a, r in zip(fm._resident_plane(), resident))
    assert compiles["compiles"] == 0


@pytest.fixture
def tracer():
    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()


def test_a_resident_pass_is_one_launch_and_counts_its_bytes(
        monkeypatch, tracer):
    """Each pass calls ``decision_fused.fused_decision_pallas`` once, at
    Python level (the benchmark counts launches there), with the queries
    as float32 numpy arrays and the plane as the resident device arrays;
    ``plane.h2d_bytes`` counts the numpy operands, ``plane.d2h_bytes`` the
    mask the launch returned."""
    columns = 3
    rng = np.random.default_rng(13)
    fm = fleet(rng, columns)
    fm.estimate_frames(fleet_frames(rng, fm, 1, columns))   # plane goes up
    launches = []
    kernel = decision_fused.fused_decision_pallas

    def launch(*args, **kwargs):
        out = kernel(*args, **kwargs)
        launches.append((args, kwargs, out[0]))
        return out

    monkeypatch.setattr(decision_fused, "fused_decision_pallas", launch)
    tracer.reset()
    sizes = (1, 3, 8)
    for b in sizes:
        fm.estimate_frames(fleet_frames(rng, fm, b, columns))
    snap = tracer.snapshot()
    counters = snap["counters"]
    assert len(launches) == len(sizes) == counters["plane.passes"]
    for name in ("plane.upload", "plane.kernel", "plane.readback"):
        assert snap["spans"][name]["count"] == len(sizes)
    assert "plane.refreshes" not in counters
    h2d = d2h = 0
    for b, (args, kwargs, mask) in zip(sizes, launches):
        q_lo, q_hi, mins, maxs = args
        assert kwargs == {"mask": True}
        assert all(isinstance(q, np.ndarray) and q.dtype == np.float32
                   for q in (q_lo, q_hi))
        assert q_lo.shape == (1 << (b - 1).bit_length(), fm._tcap, columns)
        assert mins is fm._device_plane[1] and maxs is fm._device_plane[2]
        assert not isinstance(mask, jax.core.Tracer)
        assert mask.dtype == jnp.bool_
        h2d += q_lo.nbytes + q_hi.nbytes
        d2h += mask.nbytes
    assert counters["plane.h2d_bytes"] == h2d
    assert counters["plane.d2h_bytes"] == d2h
