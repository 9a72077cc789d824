"""Serving engine + trainer + checkpoint + data substrate tests
(single device)."""


import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.predictors import ConditionalProbabilityModel
from repro.data.synthetic import (make_routing_trace, measured_skewness,
                                  skewed_distribution, token_batches)
from repro.models.transformer import Runtime, init_model
from repro.optim.adamw import adamw_init
from repro.optim.schedules import cosine_schedule, wsd_schedule
from repro.serve import BatchScheduler, Request, ServeConfig, ServeEngine
from repro.train import checkpoint as ckpt
from repro.train.steps import make_train_step

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# serving engine (single device: dense MoE path, estimator + replan still run)
# --------------------------------------------------------------------------

def test_engine_generate_and_estimator_updates():
    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(KEY, cfg)
    eng = ServeEngine(cfg, params, ServeConfig(strategy="dist_only",
                                               max_len=64))
    gen = token_batches(0, cfg.vocab_size, batch=2, seq_len=16)
    out, tele = eng.generate({"tokens": jnp.asarray(next(gen)["tokens"])},
                             max_new_tokens=4)
    assert out.shape == (2, 4)
    assert eng.batches_seen == 1
    dist = eng.estimator.predict()
    assert dist.shape == (cfg.num_layers, cfg.moe.num_experts)
    np.testing.assert_allclose(dist.sum(1), 1.0, atol=1e-6)


def test_engine_replan_produces_duplicates_for_skewed_estimate():
    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(KEY, cfg)
    eng = ServeEngine(cfg, params, ServeConfig(strategy="dist_only",
                                               dup_slots=1), ep_ranks=4)
    skewed = np.stack([skewed_distribution(cfg.moe.num_experts, 3.0)
                       for _ in range(cfg.num_layers)])
    eng.estimator.update(skewed * 1000)
    plan = eng.replan()
    assert int(np.asarray(plan.n_replicas).max()) >= 2


def test_scheduler_batches_and_finishes():
    sched = BatchScheduler(batch_size=4, seq_len=8)
    for rid in range(6):
        sched.submit(Request(rid, np.arange(5, dtype=np.int32),
                             max_new_tokens=2))
    b1 = sched.next_batch()
    assert b1["tokens"].shape == (4, 8) and len(b1["requests"]) == 4
    sched.finish(b1["requests"], np.zeros((4, 2), np.int32))
    b2 = sched.next_batch()
    assert len(b2["requests"]) == 2          # padded partial batch
    assert b2["tokens"].shape == (4, 8)
    sched.finish(b2["requests"], np.zeros((2, 2), np.int32))
    assert not sched.has_work() and len(sched.completed) == 6


def test_engine_token_to_expert_predictor_integration():
    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(KEY, cfg)
    tr = make_routing_trace(num_sequences=16, seq_len=16,
                            vocab=cfg.vocab_size,
                            num_experts=cfg.moe.num_experts,
                            num_layers=cfg.num_layers, skew=1.5, seed=0)
    pred = ConditionalProbabilityModel(
        cfg.num_layers, cfg.moe.num_experts, cfg.vocab_size
    ).fit(tr.experts, tr.tokens)
    eng = ServeEngine(cfg, params, ServeConfig(strategy="token_to_expert"),
                      predictor=pred)
    p = eng._predict_tokens(tr.tokens[:2])
    assert p.shape == (cfg.num_layers, 2, 16, cfg.moe.top_k)


# --------------------------------------------------------------------------
# trainer substrate
# --------------------------------------------------------------------------

def test_train_driver_loss_goes_down():
    from repro.launch.train import main
    rc = main(["--arch", "qwen1.5-0.5b", "--reduced", "--steps", "20",
               "--batch", "4", "--seq", "32", "--log-every", "50"])
    assert rc == 0


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; unset, the cache goes to
    the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.REPO_ROOT,
                                           "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_schedules():
    cos = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(cos(0)) == 0.0
    assert float(cos(10)) == pytest.approx(1e-3)
    assert float(cos(100)) == pytest.approx(1e-4, rel=0.01)
    wsd = wsd_schedule(1e-3, warmup=10, total=100)
    assert float(wsd(50)) == pytest.approx(1e-3)      # stable phase
    assert float(wsd(99)) < 5e-4                      # decay phase


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("olmo-1b").reduced()
    params = init_model(KEY, cfg)
    opt = adamw_init(params)
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, {"params": params, "opt": opt})
    loaded = ckpt.load(path)
    restored = ckpt.restore_like({"params": params, "opt": opt}, loaded)
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues from a restored state
    step = jax.jit(make_train_step(cfg, Runtime()))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    p2, o2, m = step(restored["params"], restored["opt"], batch)
    assert np.isfinite(float(m["loss"]))


# --------------------------------------------------------------------------
# synthetic data substrate
# --------------------------------------------------------------------------

def test_routing_trace_properties():
    tr = make_routing_trace(num_sequences=64, seq_len=32, vocab=128,
                            num_experts=8, num_layers=2, skew=2.0,
                            predictability=1.0, seed=0)
    assert tr.tokens.shape == (64, 32)
    assert tr.experts.shape == (2, 64, 32)
    # predictability=1.0 -> expert is a pure function of (token, layer)
    for l in range(2):
        m = {}
        for t, e in zip(tr.tokens.reshape(-1), tr.experts[l].reshape(-1)):
            assert m.setdefault(int(t), int(e)) == int(e)
    # marginal skew lands near the target (sampling noise allowed)
    assert measured_skewness(np.bincount(tr.experts[0].reshape(-1),
                                         minlength=8)) > 1.4


def test_token_batches_shapes():
    gen = token_batches(0, vocab=128, batch=4, seq_len=16)
    b = next(gen)
    assert b["tokens"].shape == (4, 16) and b["labels"].shape == (4, 16)
    assert (b["tokens"][:, 1:] == b["labels"][:, :-1]).all()


def test_remat_and_microbatch_equivalence():
    """remat + gradient-accumulation microbatching produce the same loss
    and the same updated params as the plain step (memory-perf knobs must
    not change semantics)."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(KEY, cfg)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "labels": jnp.zeros((4, 16), jnp.int32)}
    outs = {}
    for name, kw in (("plain", {}), ("remat", dict(remat=True)),
                     ("mb4", dict(microbatches=4))):
        step = jax.jit(make_train_step(cfg, Runtime(), lr_fn=lambda s: 1e-3,
                                       **kw))
        p2, _, m = step(params, adamw_init(params), batch)
        outs[name] = (float(m["loss"]), p2)
    for name in ("remat", "mb4"):
        assert outs[name][0] == pytest.approx(outs["plain"][0], abs=1e-5)
        for a, b in zip(jax.tree.leaves(outs["plain"][1]),
                        jax.tree.leaves(outs[name][1])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-3)
