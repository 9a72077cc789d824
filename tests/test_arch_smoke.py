"""Per-architecture smoke tests (assignment requirement).

Every assigned arch instantiates its REDUCED variant (<=2 layers,
d_model<=512, <=4 experts) and runs one forward/train step and one decode
step on a single CPU device, asserting output shapes and finiteness.
The FULL configs are exercised only via the dry-run (ShapeDtypeStruct)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ALL_ARCHS, ASSIGNED_ARCHS, get_config
from repro.models.transformer import Runtime, forward, init_cache, init_model
from repro.optim.adamw import adamw_init
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step

KEY = jax.random.PRNGKey(0)
RT = Runtime()


def _batch(cfg, B=2, S=16):
    b = {"tokens": jnp.arange(B * S, dtype=jnp.int32).reshape(B, S)
         % cfg.vocab_size,
         "labels": jnp.zeros((B, S), jnp.int32)}
    if cfg.input_mode == "mixed" and cfg.num_prefix_embeddings:
        b["prefix_embeds"] = 0.01 * jnp.ones(
            (B, cfg.num_prefix_embeddings, cfg.d_model), jnp.float32)
    if cfg.is_encdec:
        b["frames"] = 0.01 * jnp.ones((B, 8, cfg.encoder.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    params = init_model(KEY, cfg)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, _, stats = forward(params, cfg, batch, RT, mode="train")
    S_out = S + (cfg.num_prefix_embeddings
                 if cfg.input_mode == "mixed" else 0)
    assert logits.shape == (B, S_out, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    if cfg.is_moe:
        # one row per layer of the MoE stack (leading dense layers hold
        # no experts)
        counts = stats["expert_counts"]
        assert counts.shape == (cfg.num_moe_layers, cfg.moe.num_experts)
        # every routed (token, k) pair lands on exactly one expert
        assert float(counts.sum()) == pytest.approx(
            cfg.num_moe_layers * B * S_out * cfg.moe.top_k)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_reduces_loss_no_nans(arch):
    cfg = get_config(arch).reduced()
    params = init_model(KEY, cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, RT, lr_fn=lambda s: 1e-3))
    batch = _batch(cfg, 2, 16)
    losses = []
    for _ in range(3):
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
        assert np.isfinite(float(metrics["grad_norm"]))
    assert losses[-1] < losses[0]        # same batch: loss must drop


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_then_decode(arch):
    cfg = get_config(arch).reduced()
    params = init_model(KEY, cfg)
    B, S = 2, 16
    cache = init_cache(cfg, RT, B, 32)
    batch = _batch(cfg, B, S)
    logits, cache, _ = make_prefill_step(cfg, RT)(params, batch, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    decode = make_decode_step(cfg, RT)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = S + (cfg.num_prefix_embeddings if cfg.input_mode == "mixed" else 0)
    for t in range(3):
        tok, logits, cache, _ = decode(params, tok, cache, pos + t)
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_decode_consistent_with_train_forward():
    """Greedy decode logits == train-mode logits at the same position
    (dense arch, deterministic): validates cache correctness."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(KEY, cfg)
    B, S = 1, 12
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full_logits, _, _ = forward(params, cfg, {"tokens": toks}, RT, mode="train")

    cache = init_cache(cfg, RT, B, S + 4)
    pre, cache, _ = make_prefill_step(cfg, RT)(
        params, {"tokens": toks[:, :S - 1]}, cache)
    np.testing.assert_allclose(np.asarray(pre[:, 0], np.float32),
                               np.asarray(full_logits[:, S - 2], np.float32),
                               atol=2e-2, rtol=2e-2)
    decode = make_decode_step(cfg, RT)
    _, dlogits, cache, _ = decode(params, toks[:, S - 1:S], cache, S - 1)
    np.testing.assert_allclose(np.asarray(dlogits[:, 0], np.float32),
                               np.asarray(full_logits[:, S - 1], np.float32),
                               atol=6e-2, rtol=6e-2)   # bf16 accumulation


@pytest.mark.parametrize("arch", ["rwkv6-7b", "recurrentgemma-2b"])
def test_recurrent_state_decode_windowed(arch):
    """SSM/hybrid archs decode with O(1)/O(window) state (long_500k path)."""
    cfg = get_config(arch).reduced()
    params = init_model(KEY, cfg)
    B = 2
    cache = init_cache(cfg, RT, B, 10_000)
    # state size must not scale with the 10k max_len
    leaves = jax.tree.leaves(cache)
    assert all(10_000 not in l.shape for l in leaves)
    decode = make_decode_step(cfg, RT)
    tok = jnp.zeros((B, 1), jnp.int32)
    tok, logits, cache, _ = decode(params, tok, cache, 9_000)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_config_parameter_counts_match_specs():
    """Analytical num_params is in the right ballpark for the full configs."""
    expect = {        # billions, loose bands (embeddings/heads vary)
        "minicpm-2b": (2.0, 4.0), "stablelm-3b": (2.0, 4.5),
        "rwkv6-7b": (5.5, 9.0), "qwen1.5-0.5b": (0.3, 0.8),
        "llava-next-34b": (30.0, 40.0), "olmo-1b": (0.9, 1.6),
        "deepseek-v2-lite-16b": (12.0, 20.0), "recurrentgemma-2b": (2.0, 3.6),
        "arctic-480b": (400.0, 520.0), "seamless-m4t-medium": (0.7, 1.8),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).num_params() / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo}, {hi}]"


def test_moe_active_params_below_total():
    for arch in ("arctic-480b", "deepseek-v2-lite-16b", "mixtral-8x7b"):
        cfg = get_config(arch)
        assert cfg.active_params() < 0.5 * cfg.num_params()


def test_input_shapes_table():
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].seq_len == 32768
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
