"""CPU checks of ``kbtrace``: the device's idle time split by the Knowledge
Bank dispatcher's own spans, on a small trace recorded on a TPU v5e
(``testdata/small_v5e_kb.xplane.pb``: a coalescing KnowledgeBankServer on
the Pallas backend, 2^14 x 128, two client threads each making a lookup,
a lazy_grad and a lookup of 16 ids with a 3 ms sleep after each, inside
``bench.window``), the per-dispatch host times read from the same spans,
and the readers of the four metrics that use them.

  JAX_PLATFORMS=cpu python -m pytest -q bench/test_kbtrace.py
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import kbtrace  # noqa: E402

tr = kbtrace.tr
DATA_KB = os.path.join(HERE, "testdata", "small_v5e_kb.xplane.pb")
DATA = os.path.join(HERE, "testdata", "small_v5e.xplane.pb")
METRICS = os.path.join(HERE, "metrics")
READERS = ["idle_host.point", "idle_starved.point", "dispatcher_ms.point",
           "engine_host_ms.point"]


@pytest.fixture(scope="module")
def kbtrace_data():
    return tr.Trace(DATA_KB), kbtrace.kb_spans(DATA_KB)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    """A reader's context with the trace's ``kb.*`` spans already read, as
    ``kbtrace`` keeps them for a run."""

    def __init__(self, trace, spans):
        self.trace, self.workload = trace, "none"
        self.memo = {"kb_spans": spans}


def test_innermost_and_overlap_by_hand():
    spans = [("a", 0, 10), ("b", 2, 5), ("c", 3, 4), ("d", 6, 8),
             ("e", 12, 13)]
    segs = kbtrace.innermost(spans)
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                    (5, 6, "a"), (6, 8, "d"), (8, 10, "a"), (12, 13, "e")]
    got = kbtrace.overlap_by_name(segs, [(1, 3.5), (7, 12.5)])
    assert got == {"a": 1 + 2, "b": 1, "c": 0.5, "d": 1, "e": 0.5}


def test_kb_spans_are_read_with_their_thread(kbtrace_data):
    trace, spans = kbtrace_data
    names = {n for n, _, _, _ in spans}
    assert {"kb.dispatch.wait", "kb.dispatch.form", "kb.run", "kb.run.args",
            "kb.run.reply", "kb.engine.lookup", "kb.engine.lazy_grad",
            "kb.engine.wait"} <= names
    thread = kbtrace.dispatcher_thread(spans)
    assert {n for n, _, _, t in spans if t == thread} == names
    assert not any(n.startswith("kb.") for n, _, _ in trace.spans)
    assert kbtrace.kb_spans(DATA) == []


def test_idle_by_dispatcher_is_exact_overlap_on_gaps(kbtrace_data):
    """The three longest idle gaps, split by hand: paint each nanosecond
    of a gap with the dispatcher span that opened last among those open
    (spans of one thread nest)."""
    trace, kb = kbtrace_data
    thread = kbtrace.dispatcher_thread(kb)
    spans = sorted([(s, e, n) for n, s, e, t in kb if t == thread],
                   key=lambda x: (x[0], -x[1]))
    busy = trace.busy[sorted(trace.busy)[0]]
    idle = tr.gaps(busy, trace.t0, trace.t1)
    segs = kbtrace.innermost((n, s, e) for s, e, n in spans)
    for lo, hi in sorted(idle, key=lambda g: g[0] - g[1])[:3]:
        lo_ns, n_ns = int(np.ceil(lo * 1e9)), int((hi - lo) * 1e9)
        label = np.full(n_ns, -1)
        for k, (s, e, _) in enumerate(spans):
            a = max(int(round(s * 1e9)) - lo_ns, 0)
            b = min(int(round(e * 1e9)) - lo_ns, n_ns)
            if b > a:
                label[a:b] = k
        want = {}
        for k in np.unique(label[label >= 0]):
            name = spans[k][2]
            want[name] = want.get(name, 0) + (label == k).sum() * 1e-9
        got = kbtrace.overlap_by_name(segs, [(lo, hi)])
        assert set(got) == set(want)
        for name in want:
            assert got[name] == pytest.approx(want[name], abs=1e-8)
    split = kbtrace.idle_by_dispatcher(trace, kb)
    assert sum(split.values()) == pytest.approx(
        trace.window_s - trace.busy_s(), abs=2e-6)


def test_per_run_readings_by_hand(kbtrace_data):
    """The per-dispatch readers against the recording's events read
    straight from the file, in nanoseconds: the dispatcher's line is the
    one with the most ``kb.dispatch.*`` events; spans that start inside
    ``bench.window`` count."""
    from jax.profiler import ProfileData
    trace, spans = kbtrace_data
    lines = [line for p in ProfileData.from_file(DATA_KB).planes
             if p.name.startswith("/host:") for line in p.lines]
    line = max(lines, key=lambda ln: sum(
        e.name.startswith("kb.dispatch.") for e in ln.events))
    lo, hi = round(trace.t0 * 1e9), round(trace.t1 * 1e9)
    ev = [(e.name, e.duration_ns) for e in line.events
          if lo <= e.start_ns < hi]
    runs = sum(1 for n, _ in ev if n == "kb.run")
    assert runs >= 2

    def ns(*names):
        return sum(d for n, d in ev if n in names)

    own = ns("kb.dispatch.form", "kb.run.args", "kb.run.reply")
    host = ns("kb.engine.lookup", "kb.engine.lazy_grad") \
        - ns("kb.engine.wait")
    ctx = _Ctx(*kbtrace_data)
    assert _reader("dispatcher_ms.point")(ctx) == pytest.approx(
        own * 1e-6 / runs, rel=1e-6)
    assert _reader("engine_host_ms.point")(ctx) == pytest.approx(
        host * 1e-6 / runs, rel=1e-6)
    assert 0 < own < ns("kb.run", "kb.dispatch.form")
    assert 0 < host < ns("kb.engine.lookup", "kb.engine.lazy_grad")


def test_idle_split_fits_inside_idle(kbtrace_data):
    ctx = _Ctx(*kbtrace_data)
    idle = _reader("idle.point")(ctx)
    host = _reader("idle_host.point")(ctx)
    starved = _reader("idle_starved.point")(ctx)
    assert host > 0 and starved > 0     # host work; the 3 ms sleeps
    assert host + starved <= idle


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_the_programs_spans(name):
    """A program without the server's spans (the recording of
    ``small_v5e.xplane.pb``) reports none of these metrics."""
    ctx = _Ctx(tr.Trace(DATA), kbtrace.kb_spans(DATA))
    assert _reader(name)(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_untraced(name):
    ctx = _Ctx(None, None)
    ctx.memo = {}
    assert _reader(name)(ctx) is None


def test_traced_run_finds_the_servers_spans(monkeypatch):
    """A traced run of a small point cell through ``run.run_cell`` on the
    CPU: the readers find the run's trace where ``run.py`` records it and
    read the dispatcher's spans from it. The CPU has no device plane, so
    the idle split is left out; the per-dispatch host times are read."""
    import run
    from test_correct import MIXES, SEED, SMALL
    seen = []

    class Context(run.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen.append(self)

    monkeypatch.setattr(run, "Context", Context)
    cell = "kb-sift1m-ycsbb-zipf"
    res = run.run_cell(cell, SEED, 2.0, True, require_tpu=False,
                       cfg_overrides=SMALL, mix_overrides=MIXES[cell])
    assert res["correct"], res["checks"]
    assert not set(READERS[:2]) & set(res["metrics"])   # no device plane
    assert res["metrics"]["dispatcher_ms.point"]["value"] > 0
    assert res["metrics"]["engine_host_ms.point"]["value"] > 0
    spans = seen[0].memo["kb_spans"]
    thread = kbtrace.dispatcher_thread(spans)
    names = {n for n, _, _, t in spans if t == thread}
    assert {"kb.dispatch.wait", "kb.run", "kb.engine.lookup",
            "kb.engine.wait"} <= names
