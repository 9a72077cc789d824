"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).
Kernels run interpret=True on CPU — the exact TPU program body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.histogram import histogram
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.ref import histogram_ref, moe_gemm_ref, rg_lru_ref
from repro.kernels.rg_lru import rg_lru_scan

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("S,T,d,F", [
    (1, 8, 128, 256),          # minimal
    (4, 64, 256, 512),         # aligned
    (2, 100, 128, 300),        # ragged T and F (padding path)
    (8, 8, 512, 1024),         # tall weights
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", ["swiglu", "gelu", "relu"])
def test_moe_gemm_matches_ref(S, T, d, F, dtype, activation):
    ks = jax.random.split(KEY, 4)
    x = (jax.random.normal(ks[0], (S, T, d), jnp.float32) * 0.1).astype(dtype)
    wg = (jax.random.normal(ks[1], (S, d, F), jnp.float32) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (S, d, F), jnp.float32) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (S, F, d), jnp.float32) * 0.05).astype(dtype)
    out = moe_gemm(x, wg, wu, wd, activation=activation,
                   interpret=ops._interpret())
    ref = moe_gemm_ref(x, wg, wu, wd, activation)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_moe_gemm_ops_wrapper_matches_dispatch_grouped_ffn():
    from repro.moe.dispatch import grouped_ffn
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (4, 32, 128), jnp.float32) * 0.1
    slot_w = {
        "w_gate": jax.random.normal(ks[1], (4, 128, 256), jnp.float32) * 0.05,
        "w_up": jax.random.normal(ks[2], (4, 128, 256), jnp.float32) * 0.05,
        "w_down": jax.random.normal(ks[3], (4, 256, 128), jnp.float32) * 0.05,
    }
    out = ops.moe_gemm(x, slot_w, "swiglu")
    ref = grouped_ffn(slot_w, x, "swiglu")
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("N,E", [(8, 4), (100, 8), (5000, 64), (17, 128),
                                 (1024, 256)])
def test_histogram_matches_ref(N, E):
    idx = jax.random.randint(KEY, (N,), 0, E)
    out = histogram(idx, E, interpret=ops._interpret())
    ref = histogram_ref(idx, E)
    assert (np.asarray(out) == np.asarray(ref)).all()
    assert int(out.sum()) == N


def test_histogram_ops_wrapper_topk_shape():
    idx = jax.random.randint(KEY, (16, 2), 0, 8)     # (T, K) assignments
    out = ops.expert_histogram(idx, 8)
    assert int(out.sum()) == 32


@pytest.mark.parametrize("B,S,D", [
    (1, 16, 128), (2, 64, 128), (1, 300, 500),       # ragged
    (4, 2000, 256),                                  # multi time-chunk carry
    (2, 1025, 257),                                  # both dims ragged
])
def test_rg_lru_matches_ref(B, S, D):
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, D), jnp.float32, 0.5, 0.99)
    b = jax.random.normal(ks[1], (B, S, D), jnp.float32) * 0.1
    h0 = jax.random.normal(ks[2], (B, D), jnp.float32)
    out, hl = rg_lru_scan(a, b, h0, interpret=ops._interpret())
    ro, rh = rg_lru_ref(a, b, h0)
    np.testing.assert_allclose(out, ro, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(hl, rh, atol=1e-5, rtol=1e-5)


def test_rg_lru_matches_griffin_associative_scan():
    """The kernel and the model's associative_scan agree (same recurrence)."""
    from repro.models.griffin import rg_lru, init_recurrent_block
    from repro.configs.registry import get_config
    cfg = get_config("recurrentgemma-2b").reduced()
    params = init_recurrent_block(KEY, cfg)
    dr = cfg.rnn_width or cfg.d_model
    x = jax.random.normal(KEY, (2, 32, dr), jnp.float32) * 0.1
    h0 = jnp.zeros((2, dr), jnp.float32)
    y_model, h_model = rg_lru(params, x, h0)

    # rebuild (a, b) exactly as the model does, then run the kernel
    from repro.models.layers import dense
    f32 = jnp.float32
    r = jax.nn.sigmoid(dense(params["w_a"], x).astype(f32))
    i = jax.nn.sigmoid(dense(params["w_x"], x).astype(f32))
    log_a = -8.0 * jax.nn.softplus(params["lam"].astype(f32)) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12)) * (i * x.astype(f32))
    b = b.at[:, 0].add(a[:, 0] * h0)
    out, hl = rg_lru_scan(a, b, jnp.zeros_like(h0),
                          interpret=ops._interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(y_model, np.float32),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(h_model), atol=1e-4,
                               rtol=1e-4)
