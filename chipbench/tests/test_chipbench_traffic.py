"""The traffic generator: seeded, fixed work per seed, switches placed,
mixes as data for any tenant count, and a mix that names its own module."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import generator  # noqa: E402

TENANTS = ["t0", "t1", "t2", "t3"]
COL_LO = np.zeros(16)
COL_HI = np.linspace(10.0, 1e6, 16)


def mix():
    with open(os.path.join(ROOT, "chipbench", "traffic", "shift.json")) as f:
        return json.load(f)


def stream(seed, rate=16.0, seconds=40.0, tenants=TENANTS, m=None,
           warmup=100):
    return generator.make_stream(m or mix(), tenants, COL_LO, COL_HI,
                                 rate=rate, seconds=seconds,
                                 warmup_per_tenant=warmup, seed=seed)


def flat(s):
    return [(e.tenant, e.template, e.lo.tobytes(), e.hi.tobytes())
            for e in s.warmup + s.window]


def first_template(tenant_index, m=None):
    m = m or mix()
    return m["tenants"][tenant_index % len(m["tenants"])]["segments"][0][
        "template"]


def test_same_seed_same_stream():
    a, b = stream(2**31 + 17), stream(2**31 + 17)
    assert flat(a) == flat(b)
    assert np.array_equal(a.due, b.due)
    assert flat(a) != flat(stream(2**31 + 18))


@pytest.mark.parametrize("rate,seconds", [(16.0, 40.0), (12.5, 20.0),
                                          (40.0, 51.0)])
def test_switch_in_first_third(rate, seconds):
    for seed in (1, 99, 2**32 + 5):
        s = stream(seed, rate, seconds)
        for i, tid in enumerate(TENANTS):
            first = first_template(i)
            new = [d for e, d in zip(s.window, s.due)
                   if e.tenant == tid and e.template != first]
            old = [d for e, d in zip(s.window, s.due)
                   if e.tenant == tid and e.template == first]
            assert new and min(new) < seconds / 3
            assert not old or max(old) < min(new)
        assert all(e.template == first_template(TENANTS.index(e.tenant))
                   for e in s.warmup)


def test_poisson_arrivals_have_the_configured_mean():
    rate, seconds = 16.0, 40.0
    s = stream(3, rate, seconds)
    gaps = np.diff(np.concatenate([[0.0], s.due]))
    assert len(s.due) == int(rate * seconds)
    assert s.due[-1] == pytest.approx(seconds)
    assert gaps.mean() == pytest.approx(1.0 / rate)
    # Exponential: the standard deviation equals the mean, to within the
    # midpoint-quantile discretization.
    assert gaps.std() == pytest.approx(1.0 / rate, rel=0.1)


def test_every_seed_gets_the_same_work():
    a, b = stream(5), stream(6)
    gaps = [np.sort(np.diff(np.concatenate([[0.0], x.due]))) for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    for tid in TENANTS:
        assert (sum(e.tenant == tid for e in a.window)
                == sum(e.tenant == tid for e in b.window))
        assert (sum(e.tenant == tid for e in a.warmup)
                == sum(e.tenant == tid for e in b.warmup) == 100)


def test_bounds_are_float32_and_contain_the_template_range():
    s = stream(11)
    for e in s.warmup + s.window:
        for b in (e.lo, e.hi):
            assert np.array_equal(b, b.astype(np.float32).astype(np.float64))
        tm = mix()["templates"][e.template]
        bounded = np.isfinite(e.lo)
        assert sorted(np.nonzero(bounded)[0]) == sorted(tm["columns"])


def test_a_mix_serves_any_tenant_count():
    tenants = [f"t{k}" for k in range(6)]
    s = stream(7, tenants=tenants)
    for i, tid in enumerate(tenants):
        own = {e.template for e in s.warmup if e.tenant == tid}
        assert own == {first_template(i)}
    assert {e.tenant for e in s.window} == set(tenants)


def test_weights_and_query_indexed_segments():
    m = {"arrivals": "poisson",
         "templates": [{"columns": [0], "selectivities": [0.1]},
                       {"columns": [3, 5], "selectivities": [0.05, 0.2]}],
         "tenants": [{"weight": 3, "segments": [
                         {"template": 0}, {"template": 1, "query": 110}]},
                     {"segments": [{"template": 1}]}]}
    s = stream(2**31 + 3, rate=10.0, seconds=40.0, m=m)
    counts = [sum(e.tenant == tid for e in s.window) for tid in TENANTS]
    assert counts == generator.shares(400, [3, 1, 3, 1]) == [150, 50, 150, 50]
    for tid in ("t0", "t2"):
        seq = [e.template for e in s.tenant_events(tid)]
        assert seq == [0] * 110 + [1] * (len(seq) - 110)
    assert all(e.template == 1 for e in s.tenant_events("t1"))
    assert generator.shares(7, [1, 1, 1]) == [3, 2, 2]


def test_a_mix_may_name_its_own_generator(tmp_path):
    (tmp_path / "fixed_burst.py").write_text(
        "from chipbench import generator\n"
        "import numpy as np\n"
        "def make_stream(mix, tenants, col_lo, col_hi, *, rate, seconds,\n"
        "                warmup_per_tenant, seed):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    tm = mix['templates'][0]\n"
        "    ev = [generator.Event(t, *generator.sample_query(\n"
        "          tm, rng, col_lo, col_hi), 0) for t in tenants]\n"
        "    return generator.Stream(warmup=[], window=ev,\n"
        "                            due=np.zeros(len(ev)))\n")
    m = {"generator": "fixed_burst",
         "templates": [{"columns": [2], "selectivities": [0.5]}]}
    s = generator.make_stream(m, TENANTS, COL_LO, COL_HI, rate=1.0,
                              seconds=1.0, warmup_per_tenant=0, seed=1,
                              traffic_dir=str(tmp_path))
    assert [e.tenant for e in s.window] == TENANTS
    assert not s.due.any()
