"""Trace reduction and the kernel's work count, on hand data and on a small
recorded trace."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import kernel_work, trace_reduce as tr  # noqa: E402


def test_union_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tr.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert tr.gaps(tr.clip(busy, 2, 6), 2, 6) == [(3, 5)]


def test_idle_time_goes_to_the_innermost_host_span():
    spans = [(0, 10, "pump"), (2, 6, "decide"), (3, 5, "candidate_build"),
             (7, 9, "plane_pass")]
    segments = tr.innermost(spans)
    assert [s[2] for s in segments] == [
        "pump", "decide", "candidate_build", "decide", "pump", "plane_pass",
        "pump"]
    idle = [(1, 4), (8, 12)]
    got = tr.attribute(idle, segments, 0, 12)
    assert got == {"pump": 2, "decide": 1, "candidate_build": 1,
                   "plane_pass": 1, tr.NO_SPAN: 2}
    assert sum(got.values()) == sum(b - a for a, b in idle)


class _TraceEvent:
    def __init__(self, name, start_ns, end_ns):
        self.name, self.start_ns, self.end_ns = name, start_ns, end_ns
        self.stats = []


class _TraceLine:
    def __init__(self, name, events):
        self.name, self.events = name, [_TraceEvent(*e) for e in events]


class _TracePlane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_idle_time_goes_to_a_program_span_the_run_recorded():
    """A program span nested in ``plane_pass`` names the idle gap under it
    once the run's snapshot names it; a span it does not name is not
    admitted."""
    host = _TracePlane("/host:CPU", [_TraceLine("main", [
        ("window", 0, 100), ("pump", 10, 60), ("plane_pass", 20, 50),
        ("fleet.pass", 22, 48), ("plane.readback", 30, 45),
        ("unknown.span", 70, 80)])])
    device = _TracePlane("/device:TPU:0", [_TraceLine(tr.OPS_LINE, [
        ("decision_fused.1", 25, 30)])])
    planes = [host, device]

    def idle_ns(summary):
        return {k: round(v * 1e9, 6) for k, v in summary.idle_by_host}
    assert idle_ns(tr.reduce_planes(planes)) == {
        tr.NO_SPAN: 50, "pump": 20, "plane_pass": 25}
    got = tr.reduce_planes(planes, spans=["fleet.pass", "plane.readback"])
    assert idle_ns(got) == {tr.NO_SPAN: 50, "pump": 20, "plane_pass": 4,
                            "fleet.pass": 6, "plane.readback": 15}
    assert got.busy_s == pytest.approx(5e-9) and got.kernel_events == 1
    assert got.device_ops == tr.reduce_planes(planes).device_ops


def test_op_names_drop_operands_and_layouts():
    name = ("%decision_fused.1 = f32[1,8,8,1024]{3,2,1,0:T(8,128)} "
            "custom-call(f32[8192]{0:T(1024)S(1)} %copy_bitcast_fusion.1)")
    assert tr.op_name(name) == "decision_fused.1 f32[1,8,8,1024]"
    assert tr.op_name("jit_greater") == "jit_greater"


def test_launch_work_by_hand():
    # 2 queries of one tenant (3 states of 128, 128 and 64 partitions)
    # and 1 query of another (2 states of 128), 5 columns.
    ops, nbytes = kernel_work.launch_work(
        items=3, item_states=2 * 3 + 2, item_partitions=2 * 320 + 256,
        plane_partitions=320 + 256, columns=5)
    assert ops == (2 * 320 + 256) * (3 * 5 + 2)
    assert nbytes == 4 * ((320 + 256) * (2 * 5 + 1) + 3 * 2 * 5 + 8)


def test_ideal_time_is_the_larger_bound():
    ops, nbytes = kernel_work.launch_work(4, 12, 12 * 1024, 12 * 1024, 16)
    t = kernel_work.ideal_seconds(ops, nbytes, "TPU v5 lite")
    assert t == pytest.approx(nbytes / 819e9)       # bytes bound it
    assert t > ops / 197e12
    with pytest.raises(KeyError):
        kernel_work.peaks("cpu")


class _Meta:
    def __init__(self, p):
        self.mins = np.zeros((p, 5))


class _StateMatrix:
    """A tenant's states (by partition count), as the probe reads them."""

    def __init__(self, parts, columns=5):
        self.parts, self.num_columns = parts, columns

    @property
    def state_ids(self):
        return list(range(len(self.parts)))

    def metadata(self, sid):
        return _Meta(self.parts[sid])

    def estimate(self, q_lo, q_hi):
        return _compute.fused_frames_scan(None, None, _Plane((5, 1, 8, 256)))


class _Plane:
    def __init__(self, shape):
        self.shape = shape


class _Fleet:
    """A plane padded to 8 tenants x 8 states x 256 partitions."""

    num_columns = 5

    def __init__(self, tenants):
        self.tenants = tenants

    def __contains__(self, tid):
        return tid in self.tenants

    def estimate_frames(self, frames):
        return _compute.fused_frames_scan(None, None, _Plane((5, 8, 8, 256)))


class _Kernel:
    @staticmethod
    def fused_decision_pallas(*args, **kwargs):
        return None


class _Compute:
    @staticmethod
    def fused_frames_scan(q_lo, q_hi, plane, *args):
        return _Kernel.fused_decision_pallas(q_lo, q_hi, plane)


_compute = _Compute


def test_launch_work_counts_the_states_scored_not_the_padding():
    from chipbench import probes
    matrices = {"a": _StateMatrix([128, 128, 64]), "b": _StateMatrix([256]),
                "c": _StateMatrix([])}
    kl = probes.KernelLaunches(_Compute, _Kernel)
    kl.watch(_Fleet, _StateMatrix, matrices)
    fleet = _Fleet({"a", "b", "c"})
    frames = [[("a", 0, 0), ("b", 0, 0), ("c", 0, 0)], [("a", 0, 0)],
              [("z", 0, 0)]]
    fleet.estimate_frames(frames)           # off: nothing recorded
    assert kl.launches == [] and kl.planes == {(5, 8, 8, 256)}
    kl.on = True
    fleet.estimate_frames(frames)
    matrices["b"].estimate(None, None)
    _Compute.fused_frames_scan(None, None, _Plane((5, 2, 8, 256)))
    assert kl.launches == [
        probes.Work(items=3, item_states=3 + 1 + 3,
                    item_partitions=320 + 256 + 320,
                    plane_partitions=320 + 256, columns=5),
        probes.Work(1, 1, 256, 256, 5),
        None]


def test_reduce_a_recorded_host_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pump"):
                with jax.profiler.TraceAnnotation("plane_pass"):
                    f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    summary = tr.reduce_trace(tr.find_xplane(str(tmp_path)))
    assert summary is not None
    assert 0.015 < summary.window_s < 5.0
    # A host-only trace has no device plane: nothing is busy or named.
    assert summary.devices == 0 and summary.busy_s == 0.0
    assert summary.kernel_events == 0 and summary.idle_by_host == []
    names = " ".join(tr.inventory(tr.find_xplane(str(tmp_path))))
    assert "pump" in names and "window" in names


def test_reduce_a_recorded_tpu_trace(tmp_path):
    """A 10-second window of ``lineitem_sf1_x4.shift`` traced on one v5e
    chip: 39 kernel launches, three 6M-row rewrites on the host."""
    import gzip
    import shutil
    path = tmp_path / "window.xplane.pb"
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "tpu_window.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    s = tr.reduce_trace(str(path))
    assert s.devices == 1
    assert s.window_s == pytest.approx(28.401462675)
    assert s.busy_s == pytest.approx(0.001578269)
    assert s.kernel_events == 39
    assert s.kernel_s == pytest.approx(0.00054367)
    assert s.device_ops[1] == ("decision_fused.1 f32[1,8,8,1024]",
                               pytest.approx(0.000398572))
    idle = dict(s.idle_by_host)
    assert max(idle, key=idle.get) == "materialize"
    assert sum(idle.values()) + s.busy_s == pytest.approx(s.window_s)
