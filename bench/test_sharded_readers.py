"""CPU checks of the sharded cell's readers and of its ``correct``.

The readers run on a small trace recorded on a four-chip TPU v5e host
(``testdata/small_v5e4_sharded.xplane.pb``: a coalescing
KnowledgeBankServer on the sharded backend with no mesh given, 2^16 x 128
over the four chips, two client threads each making a lookup, a lazy_grad
and a lookup of 16 ids with a 3 ms sleep after each, inside
``bench.window``, the engine observed as ``kbserve.observe`` does), with
the engine calls of that window (``small_v5e4_sharded.json``: the counts
that ``kb_point`` gives ``floors``). The check of ``correct`` runs the
cell at a small size on four CPU devices, in a subprocess.

  JAX_PLATFORMS=cpu python -m pytest -q bench/test_sharded_readers.py
"""
import importlib.util
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import kbtrace  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CELL = "kb-sift4m-sharded-ycsbb-zipf"
DATA = os.path.join(HERE, "testdata", "small_v5e4_sharded.xplane.pb")
STATS = os.path.join(HERE, "testdata", "small_v5e4_sharded.json")
READERS = ["lookup_roofline.sharded", "mfu.sharded", "allreduce_ms.sharded",
           "args_ms.sharded", "idle_max.sharded"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded():
    with open(STATS) as f:
        stats = json.load(f)
    return kbtrace.tr.Trace(DATA), kbtrace.kb_spans(DATA), stats


def _ctx(recorded):
    trace, spans, stats = recorded
    ctx = run.Context(stats=stats, trace=trace, setup_s=0.0,
                      peak=work.peaks("TPU v5 lite"), cfg=None, mix=None,
                      workload=CELL)
    ctx.memo["kb_spans"] = spans
    return ctx


def test_recording_spans_four_chips(recorded):
    trace, spans, stats = recorded
    assert len(trace.devices) == 4
    assert {"kb.engine.args", "kb.run"} <= {n for n, _, _, _ in spans}
    assert sum(c["op"] == "lookup" for c in stats["calls"]) == 4


@pytest.mark.parametrize("name", READERS)
def test_reader_is_finite_on_the_recording(recorded, name):
    value = _reader(name).read(_ctx(recorded))
    assert value is not None and math.isfinite(value) and value > 0


@pytest.mark.parametrize("sharded,one_chip", [
    ("lookup_roofline.sharded", "lookup_roofline"),
    ("mfu.sharded", "mfu.point"),
])
def test_sharded_share_is_one_chip_formula_over_four(recorded, sharded,
                                                     one_chip):
    want = _reader(one_chip).read(_ctx(recorded)) / 4
    assert _reader(sharded).read(_ctx(recorded)) == pytest.approx(
        want, rel=1e-12)


def test_allreduce_intervals_pair_start_with_done():
    hlo = "%{n} = f32[8,128]{{1,0}} {op}(%x), channel_id=1"
    ops = [(0.0, 1.0, hlo.format(n="psum.1", op="all-reduce")),
           (2.0, 2.5, hlo.format(n="ars", op="all-reduce-start")),
           (2.6, 3.0, hlo.format(n="fusion.1", op="fusion")),
           (3.5, 4.0, hlo.format(n="ard", op="all-reduce-done")),
           (5.0, 6.0, hlo.format(n="copy.1", op="copy"))]
    assert _reader("allreduce_ms.sharded").intervals(ops) == [(0.0, 1.0),
                                                              (2.0, 4.0)]


def test_readers_silent_without_a_trace(recorded):
    ctx = _ctx(recorded)
    ctx.trace = None
    for name in ("lookup_roofline.sharded", "allreduce_ms.sharded",
                 "args_ms.sharded", "idle_max.sharded"):
        assert _reader(name).read(ctx) is None


# -- correct on four devices ---------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {here!r})
    import numpy as np
    import run

    SMALL = {{"rows": 4096, "dim": 128,
              "mixture": {{"centers": 16, "noise": 0.15}}}}
    MIX = {{"clients": 4, "ids_per_request": 16, "check_share": 0.3}}

    def lost_shard(op, orig):
        # the psum share of shard 0 (rows [0, 1024)) lost from each reply
        if op != "lookup":
            return orig

        def lookup(ids):
            rows = np.array(orig(ids))
            rows[np.asarray(ids) < SMALL["rows"] // 4] = 0.0
            return rows
        return lookup

    out = {{}}
    for name, tamper in (("sound", None), ("lost_shard", lost_shard)):
        res = run.run_cell({cell!r}, 2 ** 31 + 29, 2.0, False,
                           require_tpu=False, cfg_overrides=SMALL,
                           mix_overrides=MIX, tamper=tamper)
        out[name] = {{"correct": res["correct"], "devices":
                     res["device"]["count"], "checks": res["checks"]}}
    print("REPORT " + json.dumps(out))
""").format(here=HERE, cell=CELL)


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("REPORT ")]
    return json.loads(line[-1][len("REPORT "):])


def test_sharded_cell_is_correct_on_four_devices(four_device_runs):
    sound = four_device_runs["sound"]
    assert sound["devices"] == 4
    assert sound["correct"], sound["checks"]


def test_lost_psum_share_is_not_correct(four_device_runs):
    lost = four_device_runs["lost_shard"]
    assert not lost["correct"]
    served = lost["checks"]["served_row_err"]
    assert served["value"] > served["limit"], lost["checks"]
