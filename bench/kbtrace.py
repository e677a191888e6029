"""The Knowledge Bank server's own host spans in a traced run, and the
first chip's idle time split by what the server's dispatcher was doing.

The program writes ``kb.*`` spans (``jax.profiler.TraceAnnotation``) while
the profiler runs: on the dispatcher thread ``kb.dispatch.wait`` (queue
empty), ``kb.dispatch.form``, ``kb.run`` and its children ``kb.run.args``
and ``kb.run.reply``, and, inside a run, the engine's ``kb.engine.<op>``
and ``kb.engine.wait`` (blocked on a result's copy to the host). They lie
on the host's clock, as do the device intervals of ``trace.Trace`` once
it has moved them. ``trace.Trace`` keeps only the benchmark's own spans,
so the ``kb.*`` spans are read here from the same ``.xplane.pb``, which
``run.py`` leaves under ``bench_out/trace/<workload>`` until the metric
readers have run. A program without these spans gives no split, and the
readers that use it leave their metric out. Times are in seconds.
"""
from __future__ import annotations

import importlib.util
import os
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_spec = importlib.util.spec_from_file_location("bench_trace_kb",
                                               os.path.join(HERE, "trace.py"))
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)


def trace_dir(workload: str) -> str:
    """Where ``run.py`` records a traced run of ``workload``."""
    return os.path.join(ROOT, "bench_out", "trace", workload)


def kb_spans(path: str) -> list:
    """The ``kb.*`` host spans of a trace: (name, start, end, thread), the
    thread as (host plane, line number)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n_line, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("kb."):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                (plane.name, n_line)))
    return out


def innermost(spans) -> list:
    """Split the time that properly nested spans of one thread cover into
    segments, each named by the innermost span open in it. ``spans``:
    (name, start, end); returns sorted disjoint (start, end, name)."""
    out, stack, t = [], [], None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((t, top[2], top[0]))
            t = top[2]
        if stack:
            out.append((t, s, stack[-1][0]))
        stack.append((name, s, e))
        t = s
    while stack:
        top = stack.pop()
        out.append((t, top[2], top[0]))
        t = top[2]
    return [seg for seg in out if seg[1] > seg[0]]


def overlap_by_name(segments: list, spans: list) -> dict:
    """Seconds of ``spans`` ((lo, hi), sorted, disjoint) that each name's
    ``segments`` (sorted, disjoint (start, end, name)) cover."""
    tot, i = defaultdict(float), 0
    for lo, hi in spans:
        while i < len(segments) and segments[i][1] <= lo:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < hi:
            s, e, name = segments[j]
            tot[name] += min(e, hi) - max(s, lo)
            j += 1
    return dict(tot)


def dispatcher_thread(spans: list):
    """The thread that wrote the most ``kb.dispatch.*`` spans; None where
    there are none."""
    count = defaultdict(int)
    for name, _, _, thread in spans:
        if name.startswith("kb.dispatch."):
            count[thread] += 1
    return max(count, key=count.get) if count else None


def idle_by_dispatcher(trace, spans: list):
    """Idle device time in ``trace``'s window (first chip), split by the
    innermost ``kb.*`` span open on the dispatcher thread, by exact
    overlap; "no span" where none is open. {name: seconds}; None where
    the trace holds no device or ``spans`` no dispatcher span."""
    thread = dispatcher_thread(spans)
    if not trace.busy or thread is None:
        return None
    segs = innermost((n, s, e) for n, s, e, t in spans if t == thread)
    idle = tr.gaps(trace.busy[sorted(trace.busy)[0]], trace.t0, trace.t1)
    out = overlap_by_name(segs, idle)
    out["no span"] = sum(hi - lo for lo, hi in idle) - sum(out.values())
    return out


def _spans(ctx):
    """The ``kb.*`` spans of a metric reader's traced run, read once per
    run; None in an untraced run."""
    if ctx.trace is None:
        return None
    if "kb_spans" not in ctx.memo:
        ctx.memo["kb_spans"] = kb_spans(
            tr.find_xplane(trace_dir(ctx.workload)))
    return ctx.memo["kb_spans"]


def idle_split(ctx):
    """``idle_by_dispatcher`` for a metric reader's traced run; None in an
    untraced run or where the trace holds no dispatcher span."""
    if "kb_idle" not in ctx.memo:
        spans = _spans(ctx)
        ctx.memo["kb_idle"] = (None if spans is None
                               else idle_by_dispatcher(ctx.trace, spans))
    return ctx.memo["kb_idle"]


def per_run_ms(ctx, names, less=()):
    """Milliseconds per run on the dispatcher thread in a traced run's
    window: the summed length of its spans named in ``names`` less that of
    those named in ``less``, over its ``kb.run`` spans, counting the spans
    that start inside the window. None in an untraced run or where the
    window holds no run."""
    spans = _spans(ctx)
    thread = dispatcher_thread(spans) if spans else None
    if thread is None:
        return None
    t0, t1 = ctx.trace.t0, ctx.trace.t1
    sel = [(n, e - s) for n, s, e, t in spans
           if t == thread and t0 <= s < t1]
    runs = sum(1 for n, _ in sel if n == "kb.run")
    if not runs:
        return None
    return 1e3 * (sum(d for n, d in sel if n in names)
                  - sum(d for n, d in sel if n in less)) / runs
