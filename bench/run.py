"""Benchmark of the CARLS Knowledge Bank and trainer on a TPU.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on. Everything that belongs to a cell is found by name:
``bench/configs/<config>.json`` (the sizes and settings),
``bench/traffic/<traffic>.json`` (the traffic mix; its ``generator`` names
``bench/generators/<generator>.py``) and ``bench/metrics/<metric>.py`` (one
reader per metric). A run loads, warms every shape its traffic uses
(``setup_s``), measures for ``--seconds``, checks what the timed path
produced against a plain reference (``correct``), and prints one JSON
object as its last line of standard output. With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the profiler and the metrics are the cell's per-layer ones.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts programs lowered while ``active``: each is a compile, or a
    load from the persistent cache, that the window would wait for."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.memo = {}


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cfg_overrides: dict = None,
             mix_overrides: dict = None, tamper=None, control: bool = False,
             t_start: float = None) -> dict:
    """One run; returns the result object (``control`` and ``tamper`` are
    for the tests and the control script, never for a benchmark run)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    cfg.update(cfg_overrides or {})
    mix.update(mix_overrides or {})
    import jax
    devs = require_chips(cell["chips"]) if require_tpu else jax.devices()
    from repro.env import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import work
    tracemod = _module(os.path.join(HERE, "trace.py"), "bench_trace")
    # off the chip (tests only) the readers get the v5e's peaks
    peak = work.peaks(devs[0].device_kind if require_tpu else "TPU v5 lite")
    counter = CompileCounter()
    gen = _module(os.path.join(HERE, "generators", mix["generator"] + ".py"),
                  "generator_" + mix["generator"]).Generator(
        cfg, mix, seed, tamper=tamper, control=control)
    gen.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(ROOT, "bench_out", "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = True
    gen.run_window(seconds, tracemod.WINDOW_SPAN)
    counter.active = False
    tr = None
    if trace:
        jax.profiler.stop_trace()
    gen.finish()
    stats = gen.stats()
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0))
                     for d in devs[:cell["chips"]])
    if trace:
        tr = tracemod.Trace(tracemod.find_xplane(trace_dir))
    ctx = Context(stats=stats, trace=tr, setup_s=setup_s, peak=peak,
                  cfg=cfg, mix=mix, workload=workload)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        reader = _module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                         "metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps":
                     tr.idle_by_span()}
        shutil.rmtree(trace_dir, ignore_errors=True)

    gen.free()
    gc.collect()
    t_check = time.perf_counter()
    checks = gen.check()
    check_s = time.perf_counter() - t_check
    passed = all(_passes(v, op, lim) for v, op, lim in checks.values())
    correct = bool(passed and stats["failed"] == 0 and stats["attempted"] > 0)
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = counter.count
    result["info"] = dict(getattr(gen, "info", {}), check_s=check_s)
    if control:
        result["control"] = gen.control
    result["checks"] = {k: {"value": v, "limit": lim, "cmp": op}
                        for k, (v, op, lim) in checks.items()}
    return result


def _passes(value, op: str, limit) -> bool:
    return value <= limit if op == "<=" else value >= limit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=_T_START)
    print(f"bench: {res['window_compiles']} programs compiled or loaded "
          f"inside the window", file=sys.stderr)
    print(f"bench: {json.dumps(res['info'])}", file=sys.stderr)
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['cmp']} "
              f"{c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
