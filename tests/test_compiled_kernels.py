"""Compiled-kernel lane: every Pallas entry point under ``interpret=False``
on a real accelerator, checked against the same jnp oracles the interpret
lane uses.

On CPU-only runners (the default CI container) every test skips with an
explicit "skipped: no accelerator" marker — run with ``pytest -rs`` so the
skip is visible rather than silent. On a GPU/TPU runner the tri-state auto
mode resolves to compiled and these tests execute for real; they can also
be forced from the CLI lane with ``REPRO_INTERPRET=false``. The backend is
asked inside a fixture, when a test starts, never while the file is
imported.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.env import has_accelerator
from repro.kernels import ops, ref


@pytest.fixture(autouse=True)
def accelerator():
    if not has_accelerator():
        pytest.skip("skipped: no accelerator (jax backend is "
                    f"'{jax.default_backend()}') — the compiled "
                    "interpret=False lane needs a gpu/tpu runner")


def test_nn_search_topk_compiled():
    q = jax.random.normal(jax.random.key(0), (8, 64))
    bank = jax.random.normal(jax.random.key(1), (512, 64))
    s, i = ops.nn_search_topk(q, bank, 8, interpret=False)
    s2, i2 = ref.nn_search_ref(q, bank, 8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), atol=1e-4)


def test_flash_attention_compiled():
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = [jax.random.normal(kk, (1, 2, 256, 64)) for kk in ks]
    o = ops.flash_attention(q, k, v, interpret=False)
    o2 = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=2e-4)


def test_ivf_search_compiled_with_chunk_plan():
    from repro.core.ann_index import build_ivf_index, clustered_bank
    from repro.kernels.nn_search_ivf import ivf_search_jnp, ivf_search_pallas
    table = clustered_bank(2048, 32, 16, seed=3)
    idx = build_ivf_index(table, nlist=16, iters=5)
    q = jnp.asarray(clustered_bank(8, 32, 16, seed=4))
    args = (table, idx.centroids, idx.packed_vecs, idx.packed_ids, q, 8, 4)
    s2, i2 = ivf_search_jnp(*args)
    s, i = ivf_search_pallas(*args, bucket_occ=idx.bucket_occ,
                             interpret=False)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), atol=1e-4)


def test_engine_fused_lookup_compiled():
    from repro.core.kb_engine import KBEngine
    key = jax.random.key(5)
    a = KBEngine(256, 32, backend="dense", key=key)
    b = KBEngine(256, 32, backend="pallas", interpret=False, key=key)
    ids = np.asarray([0, 17, 255, 100, 3])
    np.testing.assert_allclose(a.lookup(ids), b.lookup(ids),
                               rtol=0, atol=1e-5)
