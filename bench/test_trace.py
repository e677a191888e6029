"""CPU checks of the trace reduction on a small trace recorded on a TPU
v5e (``testdata/small_v5e.xplane.pb``: a ``bench.window`` span around
three rounds of a ``bench.lookup`` span holding an ``engine.lookup`` span
(a 2048 x 2048 fp32 matmul), a 3 ms sleep, an ``engine.lazy_grad`` span
(an elementwise pass) and a 2 ms sleep).

  JAX_PLATFORMS=cpu python -m pytest -q bench/test_trace.py
"""
import importlib.util
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "testdata", "small_v5e.xplane.pb")
_spec = importlib.util.spec_from_file_location("bench_trace",
                                               os.path.join(HERE, "trace.py"))
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(DATA)


@pytest.fixture(scope="module")
def raw():
    """Device op intervals (moved onto the host clock: the k-th program
    cannot start before the host's k-th execute call) and host spans, read
    straight from the file, in nanoseconds."""
    from jax.profiler import ProfileData
    d = ProfileData.from_file(DATA)
    ops, spans, mods, execs = [], [], [], []
    for p in d.planes:
        for line in p.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if p.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
                elif p.name == "/device:TPU:0" and line.name == "XLA Modules":
                    mods.append(e.start_ns)
                elif p.name == "/host:CPU" and e.name.startswith(
                        ("bench.", "engine.")):
                    spans.append((e.name, *iv))
                elif e.name == "PJRT_LoadedExecutable_Execute":
                    execs.append(e.start_ns)
    assert len(mods) == len(execs) == 6         # f and g, three rounds
    shift = max(x - m for x, m in zip(sorted(execs), sorted(mods)))
    return [(s + shift, e + shift) for s, e in ops], spans, shift


def _covered_ns(intervals, lo, hi):
    """Covered nanoseconds of [lo, hi), by marking a bitmap: a method
    independent of the reduction's interval merge."""
    lo, hi = int(np.floor(lo)), int(np.ceil(hi))
    mark = np.zeros(hi - lo, bool)
    for s, e in intervals:
        a, b = max(int(round(s)) - lo, 0), min(int(round(e)) - lo, hi - lo)
        if b > a:
            mark[a:b] = True
    return mark


def test_interval_helpers_by_hand():
    m = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m.tolist() == [[0, 3], [5, 8]]
    assert tr.overlap(m, 2, 6) == 2.0        # [2,3] and [5,6]
    assert tr.gaps(m, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(tr.union([]), 1, 2) == [(1, 2)]


def test_device_clock_moved_onto_the_host_clock(trace, raw):
    # the recorded device clock runs about a millisecond ahead
    assert trace.shift["/device:TPU:0"] == pytest.approx(raw[2] * 1e-9)
    assert 1e-4 < trace.shift["/device:TPU:0"] < 5e-3


def test_window_is_the_window_span(trace, raw):
    w = [s for s in raw[1] if s[0] == "bench.window"][0]
    assert trace.t0 == pytest.approx(w[1] * 1e-9)
    assert trace.window_s == pytest.approx((w[2] - w[1]) * 1e-9)
    assert 0.010 < trace.window_s < 1.0


def test_busy_is_the_union_of_device_ops(trace, raw):
    ops, spans, _ = raw
    w = [s for s in spans if s[0] == "bench.window"][0]
    mark = _covered_ns(ops, w[1], w[2])
    assert trace.busy_s() == pytest.approx(mark.sum() * 1e-9, abs=2e-6)
    idle = 1.0 - trace.busy_s() / trace.window_s
    assert 0.5 < idle < 1.0           # small ops, and sleeps between them


def test_device_time_inside_spans(trace, raw):
    ops, spans, _ = raw
    w = [s for s in spans if s[0] == "bench.window"][0]
    busy = _covered_ns(ops, w[1], w[2])
    for name in ("engine.lookup", "engine.lazy_grad"):
        inside = _covered_ns([(s, e) for n, s, e in spans if n == name],
                             w[1], w[2])
        want = (busy & inside).sum() * 1e-9
        assert trace.span_device_s(name) == pytest.approx(want, abs=2e-6)
    # once aligned, each round's matmul lies inside its engine.lookup span
    # and its elementwise pass inside engine.lazy_grad
    assert trace.span_device_s("engine.lookup") > \
        trace.span_device_s("engine.lazy_grad") > 0


def test_idle_gaps_named_by_open_spans(trace):
    gaps = dict(trace.idle_by_span())
    assert set(gaps) <= {"engine.lookup", "engine.lazy_grad",
                         "bench.lookup", "no span"}
    assert sum(gaps.values()) == pytest.approx(
        trace.window_s - trace.busy_s(), abs=2e-6)
    # the 3 ms sleeps sit inside bench.lookup, after engine.lookup closed
    assert gaps["bench.lookup"] > 0.008


def test_top_ops_are_labelled_and_sorted(trace):
    top = trace.top_ops()
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert all(" = " not in name and len(name) <= 160 for name, _ in top)


def test_op_label_keeps_name_opcode_and_type():
    hlo = ("%copy = f32[1048576,1]{1,0:T(8,128)} copy(f32[1048576,1]"
           "{0,1:T(1,128)} %bitcast.3)")
    assert tr.op_label(hlo) == "%copy copy f32[1048576,1]"
