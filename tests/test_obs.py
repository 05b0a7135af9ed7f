"""The program's tracer (``repro.obs``): off it costs nothing and records
nothing; on it nests spans, splits self time from child time, files
recorded spans and counters under the current request, and stops at its
buffer's capacity."""
import os
import subprocess
import sys
import tracemalloc
import types

import pytest

from repro import obs

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


@pytest.fixture(autouse=True)
def tracer_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture
def clock(monkeypatch):
    """A clock that advances 10 ns at every read."""
    ticks = iter(range(0, 10**9, 10))
    monkeypatch.setattr(obs, "time",
                        types.SimpleNamespace(perf_counter_ns=lambda: next(
                            ticks)))


def test_off_reads_no_clock_and_records_nothing(monkeypatch):
    def no_clock():
        raise AssertionError("clock read while the tracer is off")
    monkeypatch.setattr(obs, "time",
                        types.SimpleNamespace(perf_counter_ns=no_clock))
    assert not obs.enabled()
    with obs.span("a") as outer, obs.span("b", root=True):
        obs.record("q", 1, 2)
        obs.count("n", 5)
    assert obs.span("a") is obs.span("b") is outer      # one shared no-op
    snap = obs.snapshot()
    assert snap == {"spans": {}, "counters": {}, "records": [],
                    "dropped": 0}


def test_off_allocates_nothing():
    # Everything the calls hand back is held, so an allocation on their
    # path would still be live, and filed under obs.py, at the snapshot.
    held = [None] * 2000

    def loop():
        for i in range(1000):
            with obs.span("a") as a, obs.span("b", root=True) as b:
                obs.count("n")
                obs.record("q", 1, 2)
            held[2 * i], held[2 * i + 1] = a, b
    loop()                                  # warm any lazy state first
    tracemalloc.start()
    try:
        loop()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    mine = snap.filter_traces([tracemalloc.Filter(True, obs.__file__)])
    assert mine.statistics("lineno") == []
    assert all(h is held[0] for h in held)


def test_off_never_imports_jax():
    code = (
        "import sys\n"
        "from repro import obs\n"
        "with obs.span('a'), obs.span('b', root=True):\n"
        "    obs.record('q', 1, 2)\n"
        "    obs.count('n')\n"
        "assert obs.snapshot()['records'] == []\n"
        "assert not any(m == 'jax' or m.startswith('jax.')\n"
        "               for m in sys.modules), 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_nested_spans_carry_parents_and_self_time(clock):
    obs.enable()
    with obs.span("pump", root=True):
        with obs.span("pass"):
            with obs.span("upload"):
                pass
            with obs.span("kernel"):
                pass
        with obs.span("pass"):
            pass
    snap = obs.snapshot()
    recs = {(r["name"], r["id"]): r for r in snap["records"]}
    pump = recs[("pump", 1)]
    assert pump["parent"] is None and pump["request"] == 1
    assert recs[("pass", 2)]["parent"] == 1
    assert recs[("upload", 3)]["parent"] == 2
    assert recs[("kernel", 4)]["parent"] == 2
    assert recs[("pass", 5)]["parent"] == 1
    assert {r["request"] for r in snap["records"]} == {1}
    spans = snap["spans"]
    assert spans["pass"]["count"] == 2
    assert spans["pass"]["durations_s"] == [
        (r["end_ns"] - r["start_ns"]) * 1e-9
        for r in snap["records"] if r["name"] == "pass"]
    for name in spans:
        children = sum(r["end_ns"] - r["start_ns"] for r in snap["records"]
                       if r["parent"] in {p["id"] for p in snap["records"]
                                          if p["name"] == name})
        total = sum(r["end_ns"] - r["start_ns"] for r in snap["records"]
                    if r["name"] == name)
        assert spans[name]["total_s"] == pytest.approx(total * 1e-9)
        assert spans[name]["self_s"] == pytest.approx(
            (total - children) * 1e-9)
    assert spans["upload"]["self_s"] == spans["upload"]["total_s"] > 0
    assert spans["pump"]["self_s"] < spans["pump"]["total_s"]


def test_root_spans_number_the_requests(clock):
    obs.enable()
    for _ in range(3):
        with obs.span("pump", root=True):
            with obs.span("inner"):
                pass
    with obs.span("orphan"):
        pass
    recs = obs.snapshot()["records"]
    assert [(r["name"], r["request"]) for r in recs] == [
        ("inner", 1), ("pump", 1), ("inner", 2), ("pump", 2), ("inner", 3),
        ("pump", 3), ("orphan", None)]


def test_record_and_count(clock):
    obs.enable()
    obs.record("queue", 0, 7)               # outside any request
    with obs.span("pump", root=True) as pump:
        obs.record("queue", 3, pump.start_ns)
        obs.count("bytes", 10)
        obs.count("bytes", 5)
        obs.count("passes")
    snap = obs.snapshot()
    queue = [r for r in snap["records"] if r["name"] == "queue"]
    assert queue[0] == {"name": "queue", "id": None, "parent": None,
                        "request": None, "start_ns": 0, "end_ns": 7}
    assert queue[1]["request"] == 1 and queue[1]["parent"] == pump.id
    assert queue[1]["end_ns"] == pump.start_ns
    assert snap["spans"]["queue"]["count"] == 2
    assert snap["spans"]["queue"]["self_s"] == snap["spans"]["queue"][
        "total_s"]
    # A recorded span lies before the span open at the time: it takes
    # nothing from that span's self time.
    assert snap["spans"]["pump"]["self_s"] == snap["spans"]["pump"]["total_s"]
    assert snap["counters"] == {"bytes": 15, "passes": 1}


def test_reset_clears_everything(clock):
    obs.enable()
    with obs.span("pump", root=True):
        obs.count("n")
        obs.record("queue", 0, 1)
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counters": {}, "records": [],
                              "dropped": 0}
    with obs.span("pump", root=True):
        pass
    (rec,) = obs.snapshot()["records"]
    assert rec["id"] == 1 and rec["request"] == 1
    obs.disable()
    with obs.span("pump", root=True):
        pass
    assert len(obs.snapshot()["records"]) == 1      # kept, nothing added


def test_buffer_stops_at_capacity(clock, monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 3)
    obs.enable()
    for _ in range(5):
        with obs.span("a"):
            pass
    obs.record("q", 0, 1)
    snap = obs.snapshot()
    assert len(snap["records"]) == 3 and snap["dropped"] == 3
    assert snap["spans"]["a"]["count"] == 5             # totals go on
    assert len(snap["spans"]["a"]["durations_s"]) == 3
    assert snap["spans"]["q"]["count"] == 1
    assert snap["spans"]["q"]["durations_s"] == []


def test_on_opens_a_profiler_annotation_per_span(clock, monkeypatch):
    import jax
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    obs.enable()
    with obs.span("pump", root=True), obs.span("pass"):
        obs.record("queue", 0, 1)
    assert opened == ["pump", "pass"]       # none for a recorded span
