"""System behaviour tests: the paper's claims at test scale.

- async runtime: makers refresh concurrently, staleness is tracked, loss
  decreases (§3, §4.1)
- in-graph trainer: CARLS step cost is ~flat in neighbor count, inline
  baseline is not (checked structurally via FLOP counts, since CPU wall
  times are noisy) (§1 headline claim)
- curriculum makers: label mining recovers noisy labels; graph agreement
  infers missing labels (§4.2)
- graph builder: dynamic neighbors come from the same latent cluster (§3.1)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (KnowledgeBankServer, graph_agreement_labels,
                        feature_store_create, fs_update_labels, kb_create,
                        kb_update, make_carls_train_step, make_embed_fn,
                        make_graph_builder, make_inline_baseline_step,
                        run_async_training)
from repro.data import SyntheticGraphCorpus
from repro.models import build_model
from repro.models.losses import masked_mean_pool
from repro.optim import AdamW, constant_lr
from repro.sharding.partition import DistContext

DIST = DistContext()


def tiny_model(arch="yi-6b", **kw):
    cfg = get_config(arch).reduced().replace(num_layers=2, **kw)
    return cfg, build_model(cfg)


def test_async_training_loss_decreases_and_makers_run():
    cfg, model = tiny_model()
    corpus = SyntheticGraphCorpus(num_nodes=256, vocab_size=cfg.vocab_size,
                                  seq_len=17, num_clusters=4,
                                  neighbors_per_node=4)
    res = run_async_training(model, corpus, steps=30, batch_size=8,
                             num_makers=2, maker_batch=32, ckpt_period=5,
                             lr=3e-3)
    assert res.maker_refreshes > 0
    assert res.mean_staleness >= 0.0
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5])


def test_async_without_makers_has_stale_bank():
    cfg, model = tiny_model()
    corpus = SyntheticGraphCorpus(num_nodes=128, vocab_size=cfg.vocab_size,
                                  seq_len=17, neighbors_per_node=4)
    res = run_async_training(model, corpus, steps=10, batch_size=8,
                             use_makers=False)
    assert res.maker_refreshes == 0


def _count_flops(f, *args):
    return jax.jit(f).lower(*args).compile().cost_analysis()["flops"]


def test_carls_step_flops_flat_in_neighbors_baseline_linear():
    """The paper's headline structural claim, measured in compiled FLOPs:
    CARLS per-step cost is ~constant in K; inline baseline grows linearly."""
    cfg, model = tiny_model()
    opt = AdamW(lr=constant_lr(1e-3))
    corpus = SyntheticGraphCorpus(num_nodes=256, vocab_size=cfg.vocab_size,
                                  seq_len=17, neighbors_per_node=16)
    rng = np.random.default_rng(0)
    b = corpus.batch(rng, 4)
    flops = {}
    for K in (2, 16):
        cfgK = cfg.replace(carls=cfg.carls.__class__(
            **{**cfg.carls.__dict__, "num_neighbors": K, "kb_entries": 256}))
        modelK = build_model(cfgK)
        stepK = make_carls_train_step(modelK, opt, DIST)
        params = modelK.init(jax.random.key(0))
        kb = kb_create(256, cfg.d_model)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        jb["neighbor_ids"] = jnp.asarray(b["neighbor_ids"][:, :K])
        jb["neighbor_weights"] = jnp.asarray(b["neighbor_weights"][:, :K])
        flops[("carls", K)] = _count_flops(stepK, params, opt.init(params),
                                           kb, jb)
        stepB = make_inline_baseline_step(modelK, opt, DIST, num_neighbors=K)
        jb["neighbor_tokens"] = jnp.asarray(
            corpus.neighbor_tokens(b["neighbor_ids"][:, :K]))
        flops[("base", K)] = _count_flops(stepB, params, opt.init(params), jb)
    carls_ratio = flops[("carls", 16)] / flops[("carls", 2)]
    base_ratio = flops[("base", 16)] / flops[("base", 2)]
    assert carls_ratio < 1.15, carls_ratio          # ~flat
    assert base_ratio > 2.0, base_ratio             # grows with K
    assert flops[("base", 16)] > 3 * flops[("carls", 16)]


@pytest.fixture(scope="module")
def trained():
    """A briefly-trained encoder + its node embeddings — the maker tests use
    a real checkpoint, exactly as the paper's makers do ('knowledge makers
    keep the same machine states as model trainers... from the latest
    checkpoints'). 50 LM steps give kNN-8 cluster purity ~0.97."""
    cfg, model = tiny_model()
    corpus = SyntheticGraphCorpus(num_nodes=512, vocab_size=cfg.vocab_size,
                                  seq_len=17, num_clusters=4,
                                  neighbors_per_node=8, labeled_frac=0.3,
                                  label_noise=0.4, seed=1)
    res = run_async_training(model, corpus, steps=50, batch_size=16,
                             use_makers=False, reg_weight=0.0, lr=3e-3,
                             seed=0)
    embed = jax.jit(make_embed_fn(model, DIST))
    ids = np.arange(512)
    emb = np.asarray(embed(res.final_params,
                           jnp.asarray(corpus.node_tokens(ids)[:, :-1])))
    return cfg, model, res.final_params, corpus, emb


def test_label_mining_recovers_noisy_labels(trained):
    """§4.2.1: re-classifying against labeled-set centroids (computed from
    the 40%-noisy labels — majority still wins) beats the noisy labels."""
    cfg, model, params, corpus, emb = trained
    lab = corpus.labeled_ids
    noisy = corpus.noisy_labels[lab]
    cent = np.stack([emb[lab][noisy == c].mean(0) for c in range(4)])
    pred = (emb @ cent.T).argmax(-1)
    acc_mined = (pred == corpus.true_labels).mean()
    acc_noisy = (corpus.noisy_labels == corpus.true_labels).mean()
    assert acc_mined > acc_noisy + 0.15, (acc_mined, acc_noisy)


def test_graph_agreement_infers_missing_labels(trained):
    """§4.2.2: kNN vote over KB embeddings labels unlabeled nodes."""
    cfg, model, params, corpus, emb = trained
    n = corpus.num_nodes
    kb = kb_create(n, cfg.d_model)
    kb = kb_update(kb, jnp.arange(n), jnp.asarray(emb))
    fs = feature_store_create(n, 8)
    lab = corpus.labeled_ids
    fs = fs_update_labels(fs, jnp.asarray(lab),
                          jnp.asarray(corpus.true_labels[lab]),
                          jnp.ones(len(lab)))
    unlabeled = np.setdiff1d(np.arange(n), lab)[:64]
    pred, conf = graph_agreement_labels(
        kb, fs, jnp.asarray(emb[unlabeled]), jnp.asarray(unlabeled),
        k=8, num_classes=4)
    acc = (np.asarray(pred) == corpus.true_labels[unlabeled]).mean()
    assert acc > 0.7, acc


def test_graph_builder_finds_same_cluster_neighbors(trained):
    cfg, model, params, corpus, emb = trained
    n = corpus.num_nodes
    kb = kb_create(n, cfg.d_model)
    kb = kb_update(kb, jnp.arange(n), jnp.asarray(emb))
    fs = feature_store_create(n, 4)
    builder = make_graph_builder(DIST, k=4)
    q = jnp.arange(32)
    fs = builder(kb, fs, q)
    nbrs = np.asarray(fs.nbr_ids[:32])
    same = (corpus.clusters[nbrs] ==
            corpus.clusters[np.asarray(q)][:, None]).mean()
    assert same > 0.8, same
    assert (nbrs != np.asarray(q)[:, None]).all()   # self excluded


def test_kb_server_staleness_accounting():
    srv = KnowledgeBankServer(32, 4)
    srv.update(np.array([1, 2]), np.ones((2, 4)), src_step=5)
    srv.lookup(np.array([1, 2]), trainer_step=9)
    assert srv.mean_staleness == pytest.approx(4.0)
    srv.lookup(np.array([1]), trainer_step=5)
    assert srv.metrics["rows_served"] == 3
