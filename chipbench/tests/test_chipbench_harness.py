"""The harness: cells found by name from files alone, no result without a
chip, and ``correct`` false under each fault a cell can have.

A deployment, a mix, a cell and a metric are added under a temporary root
(files and entries only); a small run of that cell drives the whole served
path on the CPU, the kernel in interpret mode.
"""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run as harness  # noqa: E402

CELL = "tiny_lineitem.tiny_shift"
SEED = 2**31 + 12345
RUN = dict(seconds=3.0, trace=False, rows=3000)


def digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "chipbench")):
        for name in files:
            if name.endswith((".py", ".json")):
                path = os.path.join(base, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as f:
        out["BENCHMARK.json"] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The repository's benchmark plus one new deployment, mix, cell and
    metric, added as files and entries only."""
    before = digest(ROOT)
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lineitem_sf1_x4.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny_lineitem", rows=3000, partitions=64)
    limits = {"cost_gap": 1e-9, "state_mismatches": 0, "reorg_mismatches": 0,
              "ledger_mismatches": 0, "unserved": 0}
    with open(os.path.join(ROOT, "chipbench", "traffic", "shift.json")) as f:
        mix = json.load(f)
    files = {
        "chipbench/configs/tiny_lineitem.json": json.dumps(cfg),
        "chipbench/traffic/tiny_shift.json": json.dumps(mix),
        f"chipbench/cells/{CELL}.json": json.dumps({
            "rate_per_s": 40.0, "limits": limits}),
        "chipbench/metrics/events_served.py": (
            "def read(run):\n    return float(len(run.latencies_s))\n"),
    }
    for rel, text in files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    bench = copy.deepcopy(bench)
    bench["configs"].append({"name": "tiny_lineitem", "source": "test",
                             "file": "chipbench/configs/tiny_lineitem.json",
                             "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_lineitem",
                               "traffic": "tiny_shift", "chips": 1,
                               "why": "test"})
    for metric in bench["per_layer"]:
        metric["workloads"].append(CELL)
    bench["end_to_end"].append({"name": "events_served", "unit": "events",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    yield root
    assert digest(ROOT) == before


def tiny_cell(root):
    return harness.load_cell(CELL, root=root)


def test_cells_resolve_by_name(tiny_root):
    cell = tiny_cell(tiny_root)
    assert cell.config["partitions"] == 64 and cell.rate == 40.0
    assert len(cell.mix["tenants"]) == cell.config["tenants"]
    names = [m["name"] for m in cell.end_to_end]
    assert "events_served" in names and "latency_p95_ms" in names
    other = harness.load_cell("lineitem_sf1_x4.per_event", root=tiny_root)
    assert "events_served" not in [m["name"] for m in other.end_to_end]
    with pytest.raises(harness.BenchError):
        harness.load_cell("no_such_cell", root=tiny_root)


@pytest.fixture(scope="module")
def sound(tiny_root):
    return harness.run_cell(tiny_cell(tiny_root), SEED, **RUN)


def test_a_sound_run_is_correct(sound):
    assert sound.correct, sound.checks
    assert set(sound.counts) == {"candidate_builds", "reorganizations_charged",
                                 "swaps_due"}
    assert sound.failed == 0 and sound.attempted == 120
    assert sound.metrics["events_served"]["value"] == 120
    assert set(sound.metrics) == {"events_per_s", "latency_p50_ms",
                                  "latency_p95_ms", "setup_s",
                                  "events_served"}
    line = json.loads(sound.line())
    assert list(line)[-1] == "checks"
    assert line["checks"]["cost_gap"] == {"value": 0.0, "limit": 1e-9}


def test_a_traced_run_reads_the_layers(tiny_root):
    result = harness.run_cell(tiny_cell(tiny_root), SEED + 1,
                              **dict(RUN, trace=True))
    assert result.correct, result.checks
    assert {"queue_wait_p95_ms", "decide_share", "window_compiles",
            "plane_pass_share", "plane_upload_share", "plane_kernel_share",
            "plane_readback_share", "plane_refreshes"} <= set(result.metrics)
    # No device on the CPU: the device's metrics read nothing, never 0.
    assert "decision_fused_roofline" not in result.metrics
    assert "device_idle_share" not in result.metrics
    note = next(n for n in result.notes if n.startswith("trace:"))
    assert "launches recorded" in note and " 0 launches" not in note


def flip_first_frame(compute):
    inner = compute.fused_frames_scan

    def altered(*args, **kwargs):
        out = np.array(inner(*args, **kwargs))
        out[0] = ~out[0]
        return out
    return altered


def keep_first_layout(backend_cls):
    inner = backend_cls.activate

    def activate(self, state_id):
        if self.serving_state is None:
            inner(self, state_id)
    return activate


def drop_half(fleet_cls):
    inner = fleet_cls._drain_batched

    def drain(self, events, compute, frames_per_pass):
        inner(self, events[::2], compute, frames_per_pass)
    return drain


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_the_batch"])
def test_a_broken_path_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.engine import FleetEngine, InMemoryBackend, compute
    if fault == "answer_altered":
        monkeypatch.setattr(compute, "fused_frames_scan",
                            flip_first_frame(compute))
    elif fault == "state_unchanged":
        monkeypatch.setattr(InMemoryBackend, "activate",
                            keep_first_layout(InMemoryBackend))
    else:
        monkeypatch.setattr(FleetEngine, "_drain_batched",
                            drop_half(FleetEngine))
    result = harness.run_cell(tiny_cell(tiny_root), SEED, **RUN)
    assert not result.correct
    assert any(v > lim for _, v, lim in result.checks)


def test_the_control_is_not_correct(tiny_root):
    """The reference at bfloat16 zone maps and bounds, in the program's
    place, fails the comparison with the float32 reference."""
    from chipbench import control
    checks = control.readings(tiny_cell(tiny_root), SEED, RUN["seconds"],
                              rows=RUN["rows"], processes=False)
    assert any(v > lim for _, v, lim in checks), checks


def test_no_result_without_a_chip(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "lineitem_sf1_x4.per_event", "--seed", "1",
            "--seconds", "1", "--trace", "0"]
    proc = subprocess.run([sys.executable, "chipbench/run.py"] + args,
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    # A checkout of the benchmark's files alone (no program) fails too.
    bare = os.path.join(tiny_root, "bare")
    shutil.copytree(os.path.join(tiny_root, "chipbench"),
                    os.path.join(bare, "chipbench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    proc = subprocess.run([sys.executable, "chipbench/run.py"] + args,
                          cwd=bare, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_result_numbers_come_from_the_served_stream(sound):
    lat = [sound.metrics[k]["value"]
           for k in ("latency_p50_ms", "latency_p95_ms")]
    assert 0 < lat[0] <= lat[1]
    assert np.isfinite(sound.metrics["events_per_s"]["value"])
