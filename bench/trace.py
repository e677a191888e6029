"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, idle gaps named by what the
host was doing, the device operations that took the most time, and the
device time that falls inside host spans.

Host spans are ``jax.profiler.TraceAnnotation`` events that the benchmark
itself writes: ``bench.window`` around the measured window, ``bench.*``
around each client request or step, and ``engine.*`` around each call of
the server's engine. Device operations are the events of the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane. Times are in seconds.

The device's clock in the trace can run ahead of the host's by about a
millisecond. Each device plane is shifted by the smallest amount under
which no program (``XLA Modules`` event) starts before the host called
it (the ``PJRT_LoadedExecutable_Execute`` events, in the same order).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_EXECUTE = "PJRT_LoadedExecutable_Execute"


def op_label(hlo: str) -> str:
    """A device op's name as the ``XLA Ops`` line gives it is the whole HLO
    instruction; keep its name, opcode and result type without layouts."""
    name, _, rest = hlo.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9-]*)\(", rest)
    if m is None:
        return hlo[:120]
    typ = re.sub(r"\{[^}]*\}", "", rest[:m.start()])
    parts = typ.strip("()").split(", ")
    if typ.startswith("(") and len(parts) > 2:      # a tuple: ends only
        typ = f"({parts[0]}, ..., {parts[-1]})"
    return f"{name} {m.group(1)} {typ}"[:160]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> np.ndarray:
    """Merge (start, end) pairs into sorted disjoint intervals, (n, 2)."""
    iv = np.asarray(sorted(intervals), np.float64).reshape(-1, 2)
    if iv.size == 0:
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by disjoint sorted intervals."""
    if merged.size == 0 or hi <= lo:
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


def gaps(merged: np.ndarray, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Trace:
    """One trace, read once: device op events per device plane and the
    host spans, all on the profiler's clock, in seconds."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.devices = {}       # plane name -> [(start, end, op name)]
        self.spans = []         # (name, start, end)
        modules, executes = {}, []
        for plane in data.planes:
            if _DEVICE_PLANE.match(plane.name):
                ops = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.extend((e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9,
                                    e.name) for e in line.events)
                    elif line.name == MODULES_LINE:
                        modules[plane.name] = sorted(
                            e.start_ns * 1e-9 for e in line.events)
                self.devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(("bench.", "engine.")):
                            self.spans.append(
                                (e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
                        elif e.name == HOST_EXECUTE:
                            executes.append(e.start_ns * 1e-9)
        executes.sort()
        self.shift = {}
        for name, starts in modules.items():
            n = min(len(starts), len(executes))
            lag = (np.asarray(executes[:n]) - np.asarray(starts[:n])
                   ).max() if n else 0.0
            self.shift[name] = max(0.0, float(lag))
            self.devices[name] = [(s + self.shift[name], e + self.shift[name],
                                   op) for s, e, op in self.devices[name]]
        windows = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = windows[-1][1], windows[-1][2]
        self.busy = {name: union((s, e) for s, e, _ in ops
                                 if e > self.t0 and s < self.t1)
                     for name, ops in self.devices.items()}

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the chips."""
        if not self.busy:
            return 0.0
        return float(np.mean([overlap(m, self.t0, self.t1)
                              for m in self.busy.values()]))

    def idle_pct(self):
        """Share of the window in which no operation ran on the device, in
        percent; None where the trace holds no device."""
        if not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def span_device_s(self, prefix: str) -> float:
        """Device busy seconds inside host spans whose name starts with
        ``prefix``, averaged over the chips."""
        spans = union((s, e) for n, s, e in self.spans
                      if n.startswith(prefix) and n != WINDOW_SPAN
                      and e > self.t0 and s < self.t1)
        if not self.busy:
            return 0.0
        return float(np.mean([sum(overlap(m, max(s, self.t0),
                                          min(e, self.t1))
                                  for s, e in spans)
                              for m in self.busy.values()]))

    def top_ops(self, n: int = 10) -> list:
        """[[op name, device seconds in the window]], largest first, summed
        over the chips."""
        tot = defaultdict(float)
        for ops in self.devices.values():
            for s, e, name in ops:
                d = min(e, self.t1) - max(s, self.t0)
                if d > 0:
                    tot[op_label(name)] += d
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> list:
        """Idle device time in the window (first chip), each gap named by
        the host span open at its middle: the most recently opened
        ``engine.*`` span, else the most recently opened ``bench.*``
        request or step span, else "no span". [[name, seconds]], summed
        per name, largest first."""
        if not self.busy:
            return []
        merged = self.busy[sorted(self.busy)[0]]
        kinds = []
        for prefix in ("engine.", "bench."):
            sel = [(s, e, name) for name, s, e in self.spans
                   if name.startswith(prefix) and name != WINDOW_SPAN]
            kinds.append((np.asarray([s for s, _, _ in sel]),
                          np.asarray([e for _, e, _ in sel]),
                          [name for _, _, name in sel]))
        tot = defaultdict(float)
        for lo, hi in gaps(merged, self.t0, self.t1):
            mid = 0.5 * (lo + hi)
            label = "no span"
            for starts, ends, names in kinds:
                open_ = np.flatnonzero((starts <= mid) & (ends >= mid))
                if open_.size:
                    label = names[open_[np.argmax(starts[open_])]]
                    break
            tot[label] += hi - lo
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
