"""Incremental reorganization in the harness: a deployment that says
``"reorg": "incremental"`` gets the program's incremental engines, and the
reference replays each migration step by step with its share of α.

A tiny incremental deployment, mix and cell are added under a temporary
root as files and entries only; a small run of that cell drives the whole
served path on the CPU, the kernel in interpret mode.
"""
import copy
import dataclasses
import json
import os
import shutil
import sys
from functools import reduce

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run as harness  # noqa: E402
from chipbench.tests.test_chipbench_harness import digest  # noqa: E402

CELL = "tiny_lineitem_incr.tiny_shift"
SEED = 2**31 + 16001
RUN = dict(seconds=3.0, trace=False, rows=3000)


@pytest.fixture(scope="module")
def incr_root(tmp_path_factory):
    """The repository's benchmark plus one incremental deployment, a mix
    and a cell, added as files and entries only."""
    before = digest(ROOT)
    root = str(tmp_path_factory.mktemp("bench_incr"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lineitem_sf1_x4.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny_lineitem_incr", rows=3000, partitions=64,
               reorg="incremental", migration_steps=4, reorg_window=64)
    limits = {"cost_gap": 1e-9, "state_mismatches": 0, "reorg_mismatches": 0,
              "ledger_mismatches": 0, "unserved": 0}
    with open(os.path.join(ROOT, "chipbench", "traffic", "shift.json")) as f:
        mix = json.load(f)
    files = {
        "chipbench/configs/tiny_lineitem_incr.json": json.dumps(cfg),
        "chipbench/traffic/tiny_shift.json": json.dumps(mix),
        f"chipbench/cells/{CELL}.json": json.dumps({
            "rate_per_s": 40.0, "limits": limits}),
    }
    for rel, text in files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    bench = copy.deepcopy(bench)
    bench["configs"].append({
        "name": "tiny_lineitem_incr", "source": "test",
        "file": "chipbench/configs/tiny_lineitem_incr.json",
        "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_lineitem_incr",
                               "traffic": "tiny_shift", "chips": 1,
                               "why": "test"})
    for metric in bench["per_layer"]:
        metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    yield root
    assert digest(ROOT) == before


def tiny_cell(root):
    return harness.load_cell(CELL, root=root)


def run_capturing(cell, monkeypatch):
    """One run of ``cell``, and the front end it served through."""
    from chipbench import system
    seen = []
    inner = system.make_frontend

    def make_frontend(engines):
        seen.append(inner(engines))
        return seen[-1]
    monkeypatch.setattr(system, "make_frontend", make_frontend)
    result = harness.run_cell(cell, SEED, **RUN)
    return result, seen[0]


def test_a_sound_incremental_run_is_correct(incr_root, monkeypatch):
    cell = tiny_cell(incr_root)
    result, frontend = run_capturing(cell, monkeypatch)
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted == 120
    assert "migration_steps_charged" in result.counts
    alpha = cell.config["alpha"]
    long_ones = []
    for tid in frontend.result().per_tenant:
        for m in frontend.fleet.tenant(tid).reorg_executor.migrations:
            if m.completed_at > m.begun_at and len(m.charges) >= 2:
                long_ones.append(m)
                total = reduce(lambda acc, c: acc + c[2], m.charges, 0.0)
                assert total == alpha, m.charges
    assert long_ones, "no migration took two steps or more"


def apply_nothing(self, hybrid_meta, newly_done):
    """Serve the source until the migration completes."""


def charge_at_the_end(record_cls):
    inner = record_cls.charge

    def charge(self, index, rows, completing):
        if completing:
            inner(self, index, rows, completing)
        else:
            self.charges.append((index, rows, 0.0))
    return charge


def reversed_moves(executor_module):
    inner = executor_module.plan_migration

    def plan(*args, **kwargs):
        out = inner(*args, **kwargs)
        out.moves = out.moves[::-1]
        return out
    return plan


@pytest.mark.parametrize("fault", ["source_served", "alpha_at_completion",
                                   "moves_reversed"])
def test_a_broken_migration_is_not_correct(incr_root, monkeypatch, fault):
    from repro.engine import InMemoryBackend
    from repro.engine.reorg import executor
    if fault == "source_served":
        monkeypatch.setattr(InMemoryBackend, "apply_migration", apply_nothing)
    elif fault == "alpha_at_completion":
        monkeypatch.setattr(executor.MigrationRecord, "charge",
                            charge_at_the_end(executor.MigrationRecord))
    else:
        monkeypatch.setattr(executor, "plan_migration",
                            reversed_moves(executor))
    result = harness.run_cell(tiny_cell(incr_root), SEED, **RUN)
    assert not result.correct
    assert any(v > lim for _, v, lim in result.checks)


def test_the_incremental_control_is_not_correct(incr_root):
    """The reference at bfloat16 zone maps and bounds, in the program's
    place, fails the comparison with the float32 reference."""
    from chipbench import control
    checks = control.readings(tiny_cell(incr_root), SEED, RUN["seconds"],
                              rows=RUN["rows"], processes=False)
    assert any(v > lim for _, v, lim in checks), checks


def test_one_step_migrations_replay_the_atomic_reference(incr_root):
    """With the whole table granted in one step, every migration completes
    where the atomic swap lands: the same costs, states and
    reorganizations to the bit, and one ledger entry of α a migration."""
    from chipbench import tables
    cell = tiny_cell(incr_root)
    rows = RUN["rows"]
    cfg = cell.config
    tids = [f"t{k}" for k in range(cfg["tenants"])]
    data = [tables.make(cfg["table"], rows, SEED, cfg["tenants"], k)
            for k in range(cfg["tenants"])]
    col_lo = np.min([d.min(axis=0) for d in data], axis=0)
    col_hi = np.max([d.max(axis=0) for d in data], axis=0)
    stream = harness.make_stream(cell, tids, col_lo, col_hi,
                                 RUN["seconds"], SEED)
    one_step = dataclasses.replace(cell, config=dict(cfg, migration_steps=1))
    atomic = dataclasses.replace(cell, config=dict(cfg, reorg="atomic"))
    got = harness.reference_traces(one_step, stream, SEED, rows,
                                   processes=False)
    want = harness.reference_traces(atomic, stream, SEED, rows,
                                    processes=False)
    migrations = 0
    for tid in tids:
        g, w = got[tid], want[tid]
        assert np.array_equal(g.costs, w.costs)
        assert np.array_equal(g.states, w.states)
        assert g.reorgs == w.reorgs
        begun = [i for i, _, _ in g.alpha_ledger]
        assert len(set(begun)) == len(begun)
        assert {i - cfg["delta"] for i in begun} <= set(w.reorgs)
        assert all(c == cfg["alpha"] for _, _, c in g.alpha_ledger)
        migrations += len(begun)
    assert migrations


def test_the_atomic_cell_keeps_its_warmup():
    cell = harness.load_cell("lineitem_sf1_x4.per_event")
    assert cell.config["reorg"] == "atomic"
    assert harness.warmup_per_tenant(cell.config) == 120


def test_hybrid_zone_maps_are_those_of_the_rows_where_they_lie():
    """After each move of a migration, the reference's hybrid zone maps
    equal ``zone_maps`` over the rows' hybrid assignment: a row lies in its
    target partition once that partition's move is done, and in its source
    partition until then."""
    from chipbench import reference, tables
    data = tables.make("tpch_lineitem", 2000, SEED, 1, 0)
    costing = reference.Costing("float32")
    source = reference.arrival_order_layout(data, 16)
    rng = np.random.default_rng(SEED)
    q_lo = data[rng.integers(0, len(data), 32)] - 1.0
    q_hi = q_lo + 50.0
    target = reference.qdtree_layout(1, data, q_lo, q_hi, 16, 0.5, seed=1)
    migrations = reference.Migrations(data, costing, 4.0, 100, 64, source)
    mig = migrations._plan(target, list(zip(q_lo, q_hi)))
    p_s, p_t = 16, target.meta.mins.shape[0]
    a_s, a_t = source.route(data), target.route(data)
    assert len(mig.moves) > 2
    for done in range(len(mig.moves) + 1):
        mig.done = done
        got = mig.hybrid(data)
        assign = np.where(mig.done_at[a_t] < done, p_s + a_t, a_s)
        want = reference.zone_maps(data, assign, p_s + p_t)
        for field in ("mins", "maxs", "rows"):
            assert np.array_equal(getattr(got, field), getattr(want, field))


def test_the_closing_charges_land_on_alpha():
    """Whatever has been charged before, the closing charges bring the
    left-to-right sum to α to the bit, in one charge wherever one float
    does."""
    import math

    from chipbench.reference import closing_charges
    rng = np.random.default_rng(SEED)
    splits = 0
    for alpha, part in zip(rng.uniform(0.1, 100.0, 20000),
                           rng.uniform(0.0, 1.0, 20000)):
        charged = 0.0
        for inc in (alpha * part, alpha * part * part):
            charged = charged + inc
        charges = closing_charges(charged, alpha)
        assert reduce(lambda acc, c: acc + c, charges, charged) == alpha
        if len(charges) > 1:
            splits += 1
            near = alpha - charged
            assert all(charged + inc != alpha for inc in (
                near, math.nextafter(near, math.inf),
                math.nextafter(near, -math.inf)))
    assert splits
