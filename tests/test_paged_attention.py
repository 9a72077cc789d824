"""Fused Pallas paged-decode attention vs its oracles.

The fused kernel (kernels.paged_attention, interpret=True on CPU — the
exact TPU program body) walks block_tables via scalar-prefetch index
maps and folds each block into a flash-style online-softmax state. The
gather oracle (kernels.ref.paged_decode_ref) runs the SAME block-ordered
op sequence over the materialised (B, M*bs, K, hd) view, so the two are
bit-exact in fp32 — not merely close. A separate naive full-softmax
reference checks both against textbook attention.

Lengths are deliberately ragged across the block-boundary edge cases:
``len % bs == 0`` (new token opens a fresh block) and ``len % bs ==
bs - 1`` (new token fills a block), plus a full-capacity slot.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ref import paged_decode_ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

M = 4  # table width (blocks per slot)


def _ragged_lengths(bs: int) -> list:
    # new token at position len: block-opening (len % bs == 0),
    # block-filling (len % bs == bs-1), interior, and full-capacity
    return [bs - 1, bs, 2 * bs + 3, M * bs - 1]


def _paged_state(bs, G, lengths, dtype, *, K=2, hd=32, seed=0):
    B = len(lengths)
    rng = np.random.default_rng(seed)
    N = 1 + B * M                                     # block 0 = null
    q = jnp.asarray(rng.normal(size=(B, K, G, hd)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(N, bs, K, hd)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(N, bs, K, hd)), dtype)
    # non-trivial physical placement: slots own disjoint shuffled blocks
    perm = rng.permutation(B * M).astype(np.int32)
    tables = jnp.asarray(1 + perm.reshape(B, M))
    return q, k_pool, v_pool, tables, jnp.asarray(lengths, jnp.int32)


def _gather_view(pool, tables):
    B = tables.shape[0]
    return pool[tables].reshape(B, -1, *pool.shape[2:])


def _naive_full(q, k_view, v_view, lengths, window):
    """Textbook per-slot softmax attention over the valid (windowed)
    prefix, fp32 numpy."""
    B, K, G, hd = q.shape
    qf = np.asarray(q, np.float32)
    kf = np.asarray(k_view, np.float32)
    vf = np.asarray(v_view, np.float32)
    out = np.zeros((B, K, G, hd), np.float32)
    for b in range(B):
        cl = int(lengths[b]) + 1
        lo = max(0, cl - window) if window > 0 else 0
        for k in range(K):
            for g in range(G):
                s = kf[b, lo:cl, k] @ qf[b, k, g] / np.sqrt(hd)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, k, g] = p @ vf[b, lo:cl, k]
    return out


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("window", [0, "bs+2"])
def test_fused_matches_gather_bitexact_fp32(bs, G, window):
    window = bs + 2 if window == "bs+2" else 0
    q, kp, vp, tables, lengths = _paged_state(
        bs, G, _ragged_lengths(bs), jnp.float32)
    fused = paged_decode_attention(q, kp, vp, tables, lengths,
                                   window=window, interpret=True)
    ref = paged_decode_ref(q, _gather_view(kp, tables),
                           _gather_view(vp, tables), lengths,
                           window=window, block_size=bs)
    assert np.array_equal(np.asarray(fused), np.asarray(ref)), (
        np.abs(np.asarray(fused) - np.asarray(ref)).max())


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("window", [0, "bs+2"])
def test_fused_matches_gather_bf16(bs, G, window):
    window = bs + 2 if window == "bs+2" else 0
    q, kp, vp, tables, lengths = _paged_state(
        bs, G, _ragged_lengths(bs), jnp.bfloat16)
    fused = paged_decode_attention(q, kp, vp, tables, lengths,
                                   window=window, interpret=True)
    ref = paged_decode_ref(q, _gather_view(kp, tables),
                           _gather_view(vp, tables), lengths,
                           window=window, block_size=bs)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("window", [0, 11])
def test_fused_matches_naive_full_softmax(window):
    bs = 8
    q, kp, vp, tables, lengths = _paged_state(
        bs, 2, _ragged_lengths(bs), jnp.float32)
    fused = paged_decode_attention(q, kp, vp, tables, lengths,
                                   window=window, interpret=True)
    ref = _naive_full(q, _gather_view(kp, tables), _gather_view(vp, tables),
                      np.asarray(lengths), window)
    np.testing.assert_allclose(np.asarray(fused), ref, atol=1e-5, rtol=1e-5)


def test_ops_wrapper_dispatches_to_kernel():
    bs = 8
    q, kp, vp, tables, lengths = _paged_state(
        bs, 2, _ragged_lengths(bs), jnp.float32)
    out = ops.paged_decode_attention(q, kp, vp, tables, lengths, window=0)
    direct = paged_decode_attention(q, kp, vp, tables, lengths,
                                    window=0, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(direct))


# ---------------------------------------------------------------------------
# gqa_decode_paged: impl knob + inactive-slot write suppression
# ---------------------------------------------------------------------------

def _tiny_cfg(**kw):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=128, **kw)


def _decode_paged_once(impl, lengths, dtype=jnp.float32):
    from repro.models.attention import gqa_decode_paged, init_gqa
    cfg = _tiny_cfg(paged_attn_impl=impl)
    params = init_gqa(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    B, bs = len(lengths), 8
    N = 1 + B * M
    pool = {"k": jnp.zeros((N, bs, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((N, bs, cfg.num_kv_heads, cfg.head_dim), dtype)}
    # row of a released slot (lengths == 0) points wholly at null block 0
    tables = np.zeros((B, M), np.int32)
    for b, ln in enumerate(lengths):
        if ln > 0:
            tables[b] = 1 + b * M + np.arange(M)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (B, 1, cfg.d_model)).astype(dtype)
    out, pool = gqa_decode_paged(
        params, cfg, x, pool, jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), window=0)
    return np.asarray(out, np.float32), pool


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_impl_knob_fused_matches_gather(dtype):
    lengths = [3, 8, 0, 17]
    fused, _ = _decode_paged_once("fused", lengths, dtype)
    gather, _ = _decode_paged_once("gather", lengths, dtype)
    if dtype == jnp.float32:
        assert np.array_equal(fused, gather), np.abs(fused - gather).max()
    else:
        np.testing.assert_allclose(fused, gather, atol=1e-2, rtol=1e-2)


def test_inactive_slot_write_suppressed():
    """Released slots (lengths == 0) must not write their projected KV
    into the null block their table rows point at — other slots' masked
    reads DMA that block and its contents must stay inert."""
    lengths = [5, 0, 0, 12]
    _, pool = _decode_paged_once("fused", lengths)
    assert float(jnp.abs(pool["k"][0]).max()) == 0.0
    assert float(jnp.abs(pool["v"][0]).max()) == 0.0
    # the active slots DID write their new token at position lengths[b]
    for b, ln in enumerate(lengths):
        if ln > 0:
            blk, off = 1 + b * M + ln // 8, ln % 8
            assert float(jnp.abs(pool["k"][blk, off]).max()) > 0.0


# ---------------------------------------------------------------------------
# meshed engine smoke: fused path on a real EP mesh, no recompiles
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_meshed_engine_fused_decode_no_recompiles():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, numpy as np
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import ContinuousConfig, ContinuousEngine
        from repro.serve.scheduler import ServeRequest

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                                  paged_attn_impl="fused")
        params = init_model(jax.random.PRNGKey(0), cfg)
        ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=16,
                                max_len=48, strategy="dist_only",
                                predict_interval=4, dup_slots=1,
                                metrics_window=4)
        eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4)
        eng.warmup()
        rng = np.random.default_rng(0)
        for i in range(5):
            eng.submit(ServeRequest(
                rid=i, arrival=0.0,
                tokens=rng.integers(0, cfg.vocab_size, 12).tolist(),
                max_new_tokens=6))
        n = 0
        while eng.has_work() and n < 60:
            eng.step(float(n)); n += 1
        eng.assert_no_recompiles()
        s = eng.metrics.summary()
        print(json.dumps({
            "completed": int(s["completed"]),
            "decode_toks_per_s": float(s.get("decode_toks_per_s", 0.0)),
            "roofline": float(s.get("fused_vs_gather_speedup", 0.0)),
        }))
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=900,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src")))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["completed"] == 5
    assert res["decode_toks_per_s"] > 0.0
    assert res["roofline"] >= 1.0
