"""The program's own tracer (``repro.obs``) inside a traced run of the
harness: its spans sit inside the harness's spans around the same calls,
and its counts agree with what the harness sees from outside.

The harness does not turn the program's tracer on; the test does, around
the window, and reads the run the metrics are read from.  A small cell on
the CPU, the kernel in interpret mode.
"""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run as harness  # noqa: E402

SEED = 2**31 + 4242


def test_program_spans_agree_with_the_harness(monkeypatch, tmp_path):
    from repro import obs
    # A trace directory of its own: the harness's tests trace in parallel.
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    cell = harness.load_cell("lineitem_sf1_x4.per_event")
    cell = dataclasses.replace(cell, rate=40.0, config=dict(
        cell.config, partitions=64))
    got = {}
    serve_window, read_metrics = harness.serve_window, harness.read_metrics

    def traced_window(*args, **kwargs):
        obs.reset()
        obs.enable()
        try:
            return serve_window(*args, **kwargs)
        finally:
            obs.disable()
            got["program"] = obs.snapshot()
            obs.reset()

    def read(root, entries, run):
        got["run"] = run
        return read_metrics(root, entries, run)

    monkeypatch.setattr(harness, "serve_window", traced_window)
    monkeypatch.setattr(harness, "read_metrics", read)
    result = harness.run_cell(cell, SEED, seconds=3.0, trace=True,
                              rows=3000)
    assert result.correct, result.checks
    program, run = got["program"], got["run"]
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
