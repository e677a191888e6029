"""The sharded Knowledge Bank on the normal serving path, with no mesh
given by the caller: ``KnowledgeBankServer(rows, dim, backend="sharded")``
spans every local device, serves coalesced traffic bit-identically to the
dense reference, and ``serve.py``'s sharded branch gets the same mesh.

Everything runs in one subprocess with 4 forced host devices (the main
pytest process keeps its single device); the tests read its report.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, threading
    import jax
    import numpy as np
    import repro.core
    from repro.core import KBEngine, KnowledgeBankServer
    from repro.core.kb_engine import make_backend
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh

    N, D, B = 4096, 16, 16
    THREADS, CALLS = 8, 24
    report = {}

    srv = KnowledgeBankServer(N, D, backend="sharded")
    report["devices"] = len(jax.devices())
    report["shards"] = {
        leaf: sorted([s.device.id, s.data.shape[0]]
                     for s in getattr(srv.engine.state, leaf)
                     .addressable_shards)
        for leaf in ("table", "version", "grad_sum", "grad_cnt",
                     "grad_sqnorm", "norm_ema")}

    eng, log, lock = srv.engine, [], threading.Lock()
    for op in ("lookup", "lazy_grad"):
        def call(*args, _op=op, _orig=getattr(eng, op)):
            out = _orig(*args)
            with lock:
                log.append((_op, [np.array(a) for a in args],
                            None if out is None else np.array(out)))
            return out
        setattr(eng, op, call)

    rng = np.random.default_rng(20261019)
    table0 = rng.standard_normal((N, D)).astype(np.float32)
    srv.update(np.arange(N), table0)
    perm = rng.permutation(N)              # hot ranks spread over shards

    def client(c):
        r = np.random.default_rng([20261019, c])
        for _ in range(CALLS):
            ids = perm[(r.zipf(1.3, B) - 1) % N]
            if r.random() < 0.05:
                g = r.standard_normal((B, D)).astype(np.float32)
                g[r.random(B) < 0.1] *= 10.0
                srv.lazy_grad(ids, g)
            else:
                srv.lookup(ids)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    written = np.unique(np.concatenate(
        [a[0] for op, a, _ in log if op == "lazy_grad"] + [perm[:8]]))
    readback = srv.lookup(written)
    report["coalesced"] = srv.stats()["metrics"]["max_run"] > 1
    report["lazy_grads"] = sum(op == "lazy_grad" for op, _, _ in log)
    table_sharded = srv.table_snapshot()
    srv.close()

    dense = KBEngine(N, D, backend="dense")
    dense.update(np.arange(N), table0)
    served_equal, n_lookups = True, 0
    for op, args, out in log:
        if op == "lookup":
            n_lookups += 1
            served_equal &= np.array_equal(dense.lookup(*args), out)
        else:
            dense.lazy_grad(*args)
    report["lookups"] = n_lookups
    report["served_equal"] = bool(served_equal)
    report["readback_equal"] = bool(np.array_equal(
        readback, log[-1][2]) and np.array_equal(log[-1][2],
                                                 dense.lookup(written)))
    report["table_equal"] = bool(np.array_equal(table_sharded,
                                                dense.table_snapshot()))

    made = []

    class Recording(KnowledgeBankServer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    repro.core.KnowledgeBankServer = Recording
    serve.main(["--kb", "--kb-backend", "sharded", "--kb-entries", "1024",
                "--kb-dim", "16", "--clients", "2", "--gen", "4"])
    meshes = {"serve": made[0].engine.backend.dist.mesh,
              "default": make_backend("sharded").dist.mesh,
              "host": make_host_mesh()}
    report["meshes"] = {k: [list(m.axis_names), list(m.devices.shape),
                            [d.id for d in m.devices.flat]]
                        for k, m in meshes.items()}
    print("REPORT " + json.dumps(report))
""")


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("REPORT ")]
    return json.loads(line[-1][len("REPORT "):])


def test_default_sharded_server_holds_a_quarter_per_device(report):
    assert report["devices"] == 4
    for leaf, shards in report["shards"].items():
        assert [dev for dev, _ in shards] == [0, 1, 2, 3], leaf
        assert all(rows == 4096 // 4 for _, rows in shards), leaf


def test_default_sharded_server_serves_bit_identical_to_dense(report):
    """A seeded mix (8 threads, coalescing on, 95% lookup / 5% lazy_grad,
    zipfian ids, some 10x gradients), replayed in dispatch order on the
    dense backend: every served row, the read-back rows and the final
    table agree bit for bit."""
    assert report["coalesced"]
    assert report["lazy_grads"] > 0 and report["lookups"] > 0
    assert report["served_equal"]
    assert report["readback_equal"]
    assert report["table_equal"]


def test_serve_sharded_branch_uses_the_default_mesh(report):
    m = report["meshes"]
    assert m["serve"] == m["default"] == m["host"]
    assert m["serve"][1] == [1, 4]
    np.testing.assert_array_equal(sorted(m["serve"][2]), [0, 1, 2, 3])
