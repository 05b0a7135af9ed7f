"""The program's own tracer (``repro.obs``) as the harness drives it: on
around a traced window only, its snapshot and the window's counts on the
``Run`` the metric readers see, and the readers of its spans and counters.

In a traced run its spans sit inside the harness's spans around the same
calls, and its counts agree with what the harness sees from outside.  A
small cell on the CPU, the kernel in interpret mode.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run as harness  # noqa: E402

SEED = 2**31 + 4242


def drive(seed, trace, tmp):
    """One run of the harness's own path on a small cell: its result, the
    ``Run`` its metrics were read from, and how often it turned the
    program's tracer on."""
    from repro import obs
    cell = harness.load_cell("lineitem_sf1_x4.per_event")
    cell = dataclasses.replace(cell, rate=40.0, config=dict(
        cell.config, partitions=64))
    got = {"enables": 0}
    enable, read_metrics = obs.enable, harness.read_metrics

    def counted_enable():
        got["enables"] += 1
        enable()

    def read(root, entries, run):
        got["run"] = run
        return read_metrics(root, entries, run)

    with pytest.MonkeyPatch.context() as mp:
        # A trace directory of its own: the harness's tests trace in
        # parallel.
        mp.setattr(harness, "TRACE_DIR", str(tmp / "trace"))
        mp.setattr(obs, "enable", counted_enable)
        mp.setattr(harness, "read_metrics", read)
        result = harness.run_cell(cell, seed, seconds=3.0, trace=trace,
                                  rows=3000)
    assert result.correct, result.checks
    return result, got["run"], got["enables"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return drive(SEED, True, tmp_path_factory.mktemp("traced"))


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return drive(SEED + 1, False, tmp_path_factory.mktemp("untraced"))


def test_program_spans_agree_with_the_harness(traced):
    from repro import obs
    result, run, enables = traced
    assert enables == 1
    program = run.program
    spans, counters = program["spans"], program["counters"]
    assert program["dropped"] == 0
    # One admission record per window event, one program pump per pump of
    # the open loop.
    assert spans["frontend.queue"]["count"] == result.attempted == 120
    assert spans["frontend.pump"]["count"] == run.spans.counts["pump"]
    assert spans["frontend.pump"]["total_s"] <= run.spans.seconds["pump"]
    # ``fleet.pass`` is the body of the call the harness spans as
    # ``plane_pass``, and each fused pass is one kernel launch.
    assert spans["fleet.pass"]["count"] == run.spans.counts["plane_pass"]
    assert spans["fleet.pass"]["total_s"] <= run.spans.seconds["plane_pass"]
    assert counters["plane.passes"] == len(run.launches) > 0
    assert "plane.fallbacks" not in counters
    # Self time is what the plane's stage, upload, kernel, readback and
    # reduce spans leave of the pass.
    passes = {r["id"] for r in program["records"]
              if r["name"] == "fleet.pass"}
    children = 1e-9 * sum(r["end_ns"] - r["start_ns"]
                          for r in program["records"]
                          if r["parent"] in passes)
    fleet = spans["fleet.pass"]
    assert 0 < children < fleet["total_s"]
    assert abs(fleet["self_s"] - (fleet["total_s"] - children)) < 1e-6
    # The window over, the tracer is off again.
    assert not obs.enabled()
    # Its readers read the window's spans and counters.
    metrics = result.metrics
    shares = [metrics[f"plane_{k}_share"]["value"]
              for k in ("upload", "kernel", "readback")]
    assert all(v > 0 for v in shares)
    assert metrics["plane_upload_share"]["value"] == pytest.approx(
        100.0 * spans["plane.upload"]["total_s"] / run.window_s)
    assert metrics["plane_refreshes"]["value"] == counters.get(
        "plane.refreshes", 0)


def test_an_untraced_run_never_turns_the_tracer_on(untraced):
    from repro import obs
    result, run, enables = untraced
    assert enables == 0 and run.program is None and not obs.enabled()
    assert run.spans is None
    names = {m["name"] for m in harness.load_cell(
        "lineitem_sf1_x4.per_event").end_to_end}
    assert set(result.metrics) == names


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_run_counts_are_the_result_counts(request, which):
    result, run, _ = request.getfixturevalue(which)
    assert run.counts == result.counts
    assert set(run.counts) == {"candidate_builds", "reorganizations_charged",
                               "swaps_due"}
    assert set(run.counts["swaps_due"]) == {"t0", "t1", "t2", "t3"}


def synthetic_run(program):
    return harness.Run(
        window_s=50.0, setup_s=80.0, latencies_s=np.ones(3),
        queue_waits_s=np.ones(3), lateness_s=np.zeros(3), engine_delta={},
        compiles_in_window=0, device_kind="TPU v5 lite", program=program)


def span(total_s, count=4):
    return {"count": count, "total_s": total_s, "self_s": total_s,
            "durations_s": [total_s / count] * count}


PROGRAM = {"spans": {"fleet.pass": span(0.9), "plane.upload": span(0.25),
                     "plane.kernel": span(0.1), "plane.readback": span(0.4)},
           "counters": {"plane.passes": 4, "plane.refreshes": 2},
           "records": [], "dropped": 0}


@pytest.mark.parametrize("name,expected,absent", [
    ("plane_upload_share", 0.5, "plane.upload"),
    ("plane_kernel_share", 0.2, "plane.kernel"),
    ("plane_readback_share", 0.8, "plane.readback"),
    ("plane_refreshes", 2.0, "fleet.pass"),
])
def test_program_readers(name, expected, absent):
    read = harness.reader(ROOT, name)
    assert read(synthetic_run(None)) is None
    assert read(synthetic_run(PROGRAM)) == pytest.approx(expected)
    without = dict(PROGRAM, spans={k: v for k, v in PROGRAM["spans"].items()
                                   if k != absent})
    assert read(synthetic_run(without)) is None
