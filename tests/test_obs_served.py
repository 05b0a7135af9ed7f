"""The program's tracer on the served path: a frontend over a small fleet
serves the same results with tracing on and off, and the spans and
counters agree with what the fused plane passes did (the decision
megakernel runs in interpret mode on the CPU)."""
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core import build_default_layout, workload as wl
from repro.engine import (FleetEngine, FleetMatrix, InMemoryBackend,
                          LayoutEngine, ThresholdSwitchPolicy, compute)
from repro.kernels.decision_fused import decision_fused
from repro.serve import FrontendConfig, ServeFrontend

TENANTS = 3
QUERIES = 24


@pytest.fixture(autouse=True)
def tracer_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def f32(a):
    return np.asarray(a, np.float32).astype(np.float64)


def tables(exact: bool):
    rng = np.random.default_rng(5)
    out = {}
    for t in range(TENANTS):
        data = rng.uniform(0, 100, size=(1_500, 4))
        out[f"t{t}"] = f32(data) if exact else data
    return out


def stream():
    rng = np.random.default_rng(6)
    events = []
    for i in range(QUERIES):
        for t in range(TENANTS):
            lo, hi = np.full(4, -np.inf), np.full(4, np.inf)
            col = (i // 6 + t) % 4
            lo[col], hi[col] = np.sort(f32(rng.uniform(0, 100, size=2)))
            events.append(wl.QueryEvent(f"t{t}", wl.Query(lo=lo, hi=hi)))
    return events


def frontend(data, plane="pallas_fused", own=None, batched=True, **cfg):
    """``plane`` scores the fleet's passes, ``own`` (default: the same)
    each tenant's own plane."""
    engines = {}
    for tid, d in data.items():
        space = [build_default_layout(sid, d, 8, sort_col=sid % d.shape[1])
                 for sid in range(3)]
        engines[tid] = LayoutEngine(
            ThresholdSwitchPolicy(space, alpha=4.0, threshold=0.05),
            InMemoryBackend(d, compute=own or plane), delta=2)
    return ServeFrontend(FleetEngine(engines), FrontendConfig(
        batched=batched, compute=plane, breaker_open_frac=None,
        pump_chunk=8, **cfg))


def serve(fe, events):
    for ev in events:
        fe.submit(ev)
        if fe.queue_depth >= fe.config.pump_chunk:
            fe.pump()
    fe.flush()
    return fe.result()


@pytest.fixture(scope="module")
def untraced():
    return serve(frontend(tables(exact=True)), stream())


@pytest.fixture(scope="module")
def traced():
    """A traced run, with every fused pass's operands, every kernel launch
    and the plane version of every fleet pass seen from outside the
    program."""
    shapes, launches, versions = [], [], []
    scan, kernel = compute.fused_frames_scan, \
        decision_fused.fused_decision_pallas
    scanned_all = FleetMatrix._scanned_all

    def frames_scan(q_lo, q_hi, minsT, maxsT):
        shapes.append((q_lo.shape, minsT))
        return scan(q_lo, q_hi, minsT, maxsT)

    def fleet_pass(self, q_lo, q_hi):
        versions.append(self.version)
        return scanned_all(self, q_lo, q_hi)

    def launch(*args, **kwargs):
        launches.append(1)
        return kernel(*args, **kwargs)

    compute.fused_frames_scan = frames_scan
    decision_fused.fused_decision_pallas = launch
    FleetMatrix._scanned_all = fleet_pass
    fe = frontend(tables(exact=True))
    obs.reset()
    obs.enable()
    try:
        result = serve(fe, stream())
    finally:
        obs.disable()
        compute.fused_frames_scan = scan
        decision_fused.fused_decision_pallas = kernel
        FleetMatrix._scanned_all = scanned_all
    snap = obs.snapshot()
    obs.reset()
    return result, snap, shapes, launches, fe, versions


def test_results_are_bit_identical_with_tracing_on_and_off(untraced,
                                                          traced):
    result = traced[0]
    assert sum(len(r.reorg_indices)
               for r in untraced.per_tenant.values()) > 0
    for tid, want in untraced.per_tenant.items():
        got = result.per_tenant[tid]
        assert np.array_equal(got.query_costs, want.query_costs)
        assert np.array_equal(got.state_seq, want.state_seq)
        assert got.reorg_indices == want.reorg_indices


def test_pass_counters_match_the_kernel_launches(traced):
    """The queries go up on every pass; a tenant's own plane goes up with
    its pass, the fleet's resident plane once for each plane version its
    passes scored."""
    _, snap, shapes, launches, _, versions = traced
    counters = snap["counters"]
    assert launches and counters["plane.passes"] == len(launches)
    assert len(shapes) == len(launches)
    assert "plane.fallbacks" not in counters
    h2d = d2h = 0
    resident = {}
    for (b, t, c), plane in shapes:
        c2, t2, s, p = plane.shape
        assert (c, t) == (c2, t2)
        b_pad = 1 << (b - 1).bit_length()
        h2d += 2 * 4 * b_pad * t * c
        if isinstance(plane, np.ndarray):
            h2d += 2 * 4 * c * t * s * p
        else:
            resident[id(plane)] = plane
        d2h += b * t * s * p                    # one bool per partition
    h2d += sum(2 * 4 * plane.size for plane in resident.values())
    assert versions and len(set(versions)) < len(versions)
    assert counters["plane.refreshes"] == len(set(versions)) \
        == len(resident)
    assert counters["plane.h2d_bytes"] == h2d
    assert counters["plane.d2h_bytes"] == d2h


def test_the_plane_spans_nest_under_the_fleet_pass(traced):
    _, snap, _, launches, _, _ = traced
    recs = snap["records"]
    by_id = {r["id"]: r for r in recs if r["id"] is not None}
    spans = snap["spans"]
    for name in ("plane.upload", "plane.kernel", "plane.readback"):
        assert spans[name]["count"] == len(launches)
    fleet = {i for i, r in by_id.items() if r["name"] == "fleet.pass"}
    # The tenants' own planes launch the kernel too, outside a fleet pass.
    in_fleet = [r for r in recs
                if r["name"] == "plane.kernel" and r["parent"] in fleet]
    assert 0 < len(in_fleet) < len(launches)
    for r in recs:
        if not r["name"].startswith("plane."):
            continue
        assert r["request"] is not None         # every pass is in a pump
        if r["name"] == "plane.reduce":
            assert r["parent"] in fleet
        if r["parent"] in fleet:
            parent = by_id[r["parent"]]
            assert by_id[parent["parent"]]["name"] == "frontend.pump"
            assert r["request"] == parent["request"]
    for span in spans.values():
        assert 0 <= span["self_s"] <= span["total_s"]
    assert snap["dropped"] == 0


@pytest.mark.parametrize("batched", [True, False])
def test_every_event_has_one_queue_record_of_its_pump(batched):
    events = stream()
    fe = frontend(tables(exact=True), plane="numpy", batched=batched)
    obs.enable()
    serve(fe, events)
    snap = obs.snapshot()
    pumps = {r["id"]: r for r in snap["records"]
             if r["name"] == "frontend.pump"}
    queue = [r for r in snap["records"] if r["name"] == "frontend.queue"]
    assert len(queue) == len(events) == fe.processed
    assert sorted(p["request"] for p in pumps.values()) == list(
        range(1, len(pumps) + 1))
    taken = {}
    for q in queue:
        pump = pumps[q["parent"]]
        assert q["request"] == pump["request"]
        assert q["end_ns"] == pump["start_ns"] >= q["start_ns"]
        taken[pump["id"]] = taken.get(pump["id"], 0) + 1
    assert max(taken.values()) <= fe.config.pump_chunk
    assert fe.latencies == []               # record_latency is off


def test_record_latency_keeps_a_latency_per_event():
    events = stream()[:12]
    fe = frontend(tables(exact=True), plane="numpy", record_latency=True)
    serve(fe, events)
    assert len(fe.latencies) == len(events)
    assert all(lat >= 0 for lat in fe.latencies)
    assert obs.snapshot()["records"] == []  # the tracer stayed off
    assert frontend(tables(exact=True)).config.record_latency is False


def test_a_plane_that_is_not_float32_exact_counts_a_fallback_per_pass(
        monkeypatch):
    passes = []
    inner = FleetMatrix._scanned_all

    def scanned_all(self, q_lo, q_hi):
        passes.append(1)
        return inner(self, q_lo, q_hi)

    monkeypatch.setattr(FleetMatrix, "_scanned_all", scanned_all)
    # The tenants' own planes stay numpy: only the fleet's passes fall back.
    fe = frontend(tables(exact=False), own="numpy")
    obs.enable()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        serve(fe, stream()[:30])
    counters = obs.snapshot()["counters"]
    assert passes and counters["plane.fallbacks"] == len(passes)
    assert "plane.passes" not in counters
