"""DeepSeek-V2 served through the continuous engine, against a plain
float32 reference.

The reference (``ref_forward``) is the published DeepSeek-V2 forward
(``DeepseekV2ForCausalLM``, no q LoRA) written out in ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernel,
no cache, no batching, one sequence at a time, on the program's own
(bf16) weights. Its departure from the published model: RoPE pairs the
first and second halves of each rotary head (rotate-half), where the
published checkpoint interleaves pairs; with random weights that is a
permutation of the rope columns of ``w_q`` and ``w_kv_a``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig
from repro.configs.registry import get_config
from repro.kernels import latent_attention as LA
from repro.kernels.ref import latent_decode_ref
from repro.models.layers import rope_frequencies
from repro.models.transformer import init_params
from repro.moe.router import route
from repro.serve import ContinuousConfig, ContinuousEngine, ServeRequest

# a DeepSeek-V2-shaped model at a tiny size: one dense layer, then two
# MoE layers of 8 experts top-3 (gates not renormalised) and one shared
# expert; MLA over a 64-wide latent and 32-wide rope key; YaRN as published
TINY = ModelConfig(
    name="tiny-dsv2", family="moe", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=32, d_ff=96, vocab_size=128, attention="mla",
    yarn=YarnConfig(), first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=64, q_lora_rank=0, rope_head_dim=32,
                  nope_head_dim=32, v_head_dim=32),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                  num_shared_experts=1, norm_topk_prob=False,
                  capacity_factor=8 / 3))      # E / k: dropless


# ------------------------------------------------------------- reference

def _yarn_inv_freq(dim, base, y):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, as the
    published modelling code computes them (numpy, float64)."""
    def corr_dim(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2) / dim))
    return inter * (1 - mask) + extra * mask


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _f32(t):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, inv_freq):
    """x: (S, n, d), rotate-half pairing."""
    ang = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _ref_layer(lp, cfg, x, moe):
    m, y = cfg.mla, cfg.yarn
    S, H = x.shape[0], cfg.num_heads
    r, nope, rope, vd = (m.kv_lora_rank, m.nope_head_dim, m.rope_head_dim,
                         m.v_head_dim)
    inv = jnp.asarray(_yarn_inv_freq(rope, cfg.rope_theta, y), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.float32)
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"])
    q = (h @ a["w_q"]["w"]).reshape(S, H, nope + rope)
    kv = h @ a["w_kv_a"]["w"]
    c = _rms(kv[:, :r], a["kv_norm"]["scale"])
    k_pe = _rope(kv[:, None, r:], pos, inv)
    k_nope = (c @ a["w_uk"]["w"]).reshape(S, H, nope)
    v = (c @ a["w_uv"]["w"]).reshape(S, H, vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    scale = (nope + rope) ** -0.5 * _mscale(y.factor, y.mscale_all_dim) ** 2
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(S, H * vd) @ a["wo"]["w"]
    h = _rms(x, lp["ln2"]["scale"])
    if not moe:
        return x + _swiglu(lp["ffn"], h)
    e = cfg.moe
    probs = jax.nn.softmax(h @ lp["moe"]["router"]["w"], -1)
    top, idx = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    out = _swiglu(lp["moe"]["shared"], h)
    ex = lp["moe"]["experts"]
    for j in range(e.num_experts):
        g = jnp.where(idx == j, top, 0.0).sum(-1)
        out = out + g[:, None] * _swiglu(jax.tree.map(lambda w: w[j], ex), h)
    return x + out


def ref_forward(params, cfg, tokens):
    """Logits (S, V) of one sequence."""
    p = _f32(params)
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][jnp.asarray(tokens)]
        for i in range(cfg.first_k_dense):
            x = _ref_layer(jax.tree.map(lambda w: w[i], p["dense_layers"]),
                           cfg, x, moe=False)
        for i in range(cfg.num_moe_layers):
            x = _ref_layer(jax.tree.map(lambda w: w[i], p["layers"]),
                           cfg, x, moe=True)
        return _rms(x, p["final_norm"]["scale"]) @ p["lm_head"]["w"]


# ----------------------------------------------------------------- tests

@pytest.mark.parametrize("dim", [16, 32, 64])
def test_yarn_frequencies_match_published(dim):
    y = get_config("deepseek-v2-lite-16b").yarn
    got = np.asarray(rope_frequencies(dim, 10000.0, y), np.float64)
    want = _yarn_inv_freq(dim, 10000.0, y)
    np.testing.assert_allclose(got, want, rtol=1e-6)    # float32 powers
    # the fast pairs keep their frequency, the slow ones are slowed 40x
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(want[-1]) and got[-1] < (
        10000.0 ** (-(dim - 2) / dim)) / 39


@pytest.mark.parametrize("norm", [True, False])
def test_router_gates_follow_norm_topk_prob(norm):
    moe = MoEConfig(num_experts=8, top_k=3, d_ff_expert=8,
                    norm_topk_prob=norm)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    w = {"w": jax.random.normal(jax.random.PRNGKey(2), (16, 8))}
    out = route(w, moe, x)
    probs = jax.nn.softmax(x @ w["w"], -1)
    top = np.take_along_axis(np.asarray(probs),
                             np.asarray(out.expert_idx), 1)
    if norm:
        top = top / top.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out.gates), top, rtol=1e-5)
    sums = np.asarray(out.gates).sum(-1)
    assert np.allclose(sums, 1.0) == norm


def _pool_case(seed, B, M, bs, D, lengths):
    """A pool with every slot's pages scattered over it; released slots
    (length 0) point at the null block only."""
    rng = np.random.default_rng(seed)
    N = 1 + sum(l // bs + 1 for l in lengths if l) + 3
    pool = jax.random.normal(jax.random.PRNGKey(seed), (N, bs, D))
    tables = np.zeros((B, M), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        if n:
            for m in range(n // bs + 1):
                tables[b, m] = free.pop()
    return pool.astype(jnp.bfloat16), tables, np.asarray(lengths, np.int32)


@pytest.mark.parametrize("pages", [1, 3, 16])
def test_latent_kernel_matches_oracle(pages):
    """Ragged lengths, a released slot (all null pages), slots ending on a
    page boundary and inside a page, chunks with dead pages past the end.
    Interpret mode runs the kernel's exact op sequence; the oracle repeats
    it over the gathered view, so the two agree bit for bit."""
    B, H, r, bs, M = 6, 4, 64, 8, 10
    D = 128
    pool, tables, lengths = _pool_case(pages, B, M, bs, D,
                                       [0, 1, 7, 8, 40, 79])
    q = jax.random.normal(jax.random.PRNGKey(7), (B, H, D)).astype(
        jnp.bfloat16)
    out = LA.latent_decode_attention(q, pool, tables, lengths, r=r,
                                     scale=0.2, pages=pages, interpret=True)
    view = pool[tables].reshape(B, M * bs, D)
    ref = latent_decode_ref(q, view, lengths, r=r, scale=0.2,
                            chunk=pages * bs)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))
    # and both are attention over positions [0, lengths[b]] in float32, up
    # to the bf16 rounding of the output and of the probabilities fed to
    # the value product (relative 2^-8 each)
    qf, vf = q.astype(jnp.float32), view.astype(jnp.float32)
    s = jnp.einsum("bhd,bsd->bhs", qf, vf) * 0.2
    live = np.arange(M * bs)[None, None, :] <= lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
    exact = jnp.einsum("bhs,bsr->bhr", p, vf[..., :r])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exact), atol=3e-2, rtol=2e-2)


def test_reduced_keeps_leading_dense_layer():
    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.first_k_dense, cfg.num_moe_layers, cfg.d_ff) == (1, 26, 10944)
    small = cfg.reduced()
    assert small.first_k_dense == 1 and small.num_moe_layers >= 1
    assert not small.moe.norm_topk_prob and small.yarn == cfg.yarn


def _serve(cfg, impl):
    """Serve three requests; record the logits of every prefill (with its
    prompt) and of every decode (per active slot: request id, position)."""
    cfg = dataclasses.replace(cfg, paged_attn_impl=impl)
    params = init_params(jax.random.PRNGKey(3), cfg)
    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=4, prefill_len=32, block_size=8, max_len=64))
    eng.warmup()
    seen = []
    prefill, decode = eng._prefill_fn, eng._decode_fn

    def rec_prefill(*a):
        out = prefill(*a)
        n = int(np.asarray(a[5])[0]) + 1
        seen.append(("prefill", np.asarray(a[1]["tokens"])[0, :n],
                     np.asarray(out[1], np.float32)[0, 0]))
        return out

    def rec_decode(*a):
        out = decode(*a)
        lg = np.asarray(out[1], np.float32)
        for s, req in enumerate(eng.scheduler.slots):
            if req is not None and np.asarray(a[4])[s] > 0:
                seen.append(("decode", (req.rid, int(np.asarray(a[4])[s])),
                             lg[s, 0]))
        return out

    eng._prefill_fn, eng._decode_fn = rec_prefill, rec_decode
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(rid=i, tokens=rng.integers(0, cfg.vocab_size, n),
                         max_new_tokens=k)
            for i, (n, k) in enumerate([(20, 9), (13, 12), (27, 7)])]
    eng.run_trace(reqs)
    assert eng.metrics.resched["dropped_tokens"] == 0
    return params, reqs, seen


@pytest.mark.parametrize("impl", ["fused", "gather"])
def test_engine_logits_match_reference(impl):
    """Prefill (expanded MLA) and paged decode (absorbed MLA over the
    latent pool, Pallas kernel or its oracle) against the reference's full
    forward over prompt + served tokens, at every served position."""
    params, reqs, seen = _serve(TINY, impl)
    seqs = {r.rid: np.concatenate([r.tokens, r.generated[:-1]]) for r in reqs}
    full = {rid: np.asarray(ref_forward(params, TINY, s))
            for rid, s in seqs.items()}
    n_dec = 0
    for kind, key, got in seen:
        if kind == "prefill":
            want = np.asarray(ref_forward(params, TINY, key))[-1]
        else:
            rid, pos = key
            want = full[rid][pos]
            n_dec += 1
        # the program runs bf16 weights and activations (the reference the
        # same weights in float32): logits of size 2-3 differ by up to 0.05
        # here, a few bf16 roundings of the residual stream through three
        # layers. Leaving out YaRN's frequency ramp moves them by 0.2, the
        # mscale^2 softmax scale by 1.8, renormalised gates by 1.0
        np.testing.assert_allclose(got, want, atol=0.1, rtol=0)
    assert n_dec == sum(len(r.generated) - 1 for r in reqs)


def test_fused_and_gather_serve_the_same_tokens():
    """The Pallas latent kernel and its oracle serve token for token the
    same requests."""
    _, a, _ = _serve(TINY, "fused")
    _, b, _ = _serve(TINY, "gather")
    assert [r.generated for r in a] == [r.generated for r in b]


def test_linear_cache_decode_matches_reference():
    """Synchronous serving (``make_prefill_step`` then ``make_decode_step``
    over a linear latent cache, the absorbed form in plain jnp) against
    the reference at every decoded position."""
    from repro.models.transformer import Runtime, init_cache
    from repro.train.steps import make_decode_step, make_prefill_step
    params = init_params(jax.random.PRNGKey(4), TINY)
    rt = Runtime()
    toks = np.random.default_rng(1).integers(0, TINY.vocab_size, 24)
    P = 16
    cache = init_cache(TINY, rt, 1, 32)
    logits, cache, _ = make_prefill_step(TINY, rt)(
        params, {"tokens": jnp.asarray(toks[None, :P])}, cache)
    got = [np.asarray(logits, np.float32)[0, 0]]
    decode = jax.jit(make_decode_step(TINY, rt))
    for t in range(P, len(toks)):
        _, lg, cache, _ = decode(params, jnp.asarray(toks[None, t:t + 1]),
                                 cache, t)
        got.append(np.asarray(lg, np.float32)[0, 0])
    want = np.asarray(ref_forward(params, TINY, toks))[P - 1:]
    # bf16 program against the float32 reference, as in the engine test
    np.testing.assert_allclose(np.stack(got), want, atol=0.1, rtol=0)
