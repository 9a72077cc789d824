"""Equivalence suite: sort-based dispatch vs the one-hot oracle.

Three layers of coverage:

* packer level — ``_pack_sort`` must reproduce ``_pack_onehot`` bit for
  bit (send buffer, capacity mask, destinations, per-slot counts, drop
  count), including the first-come drop rule under tight capacity;
* router level — the fused Pallas softmax/top-k/histogram kernel
  (``interpret=True`` on CPU) must match the dense reference router;
* model level — a multi-device EP forward with ``dispatch_impl="sort"``
  must produce the same logits and ``MoEStats`` as ``"onehot"`` across
  top_k ∈ {1, 2}, loose/tight capacity factors, and the Token-to-Expert
  predicted-assignment mode (run in one subprocess, see
  tests/test_distributed.py for the pattern).
"""


import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.moe.dispatch import _pack_onehot, _pack_sort
from repro.moe.router import route
from tests.test_distributed import run_sub

PACK_FIELDS = ("send", "in_cap", "dest", "counts", "dropped")


@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("cap", [1, 8, 64])
@pytest.mark.parametrize("num_classes", [2, 16, 33])
def test_pack_sort_matches_onehot(top_k, cap, num_classes):
    rng = np.random.default_rng(top_k * 1000 + cap * 10 + num_classes)
    T, d = 96, 8
    N = T * top_k
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    token_of = jnp.arange(N, dtype=jnp.int32) // top_k
    # skewed assignment so some slots overflow the capacity
    gslot = jnp.asarray(rng.integers(0, num_classes, N) ** 2 % num_classes,
                        jnp.int32)
    for valid_frac in (1.0, 0.7):
        valid = jnp.asarray(rng.random(N) < valid_frac)
        ref = _pack_onehot(x, token_of, gslot, valid,
                           num_classes=num_classes, cap=cap)
        got = _pack_sort(x, token_of, gslot, valid,
                         num_classes=num_classes, cap=cap)
        for r, g, name in zip(ref, got, PACK_FIELDS):
            assert np.array_equal(np.asarray(r), np.asarray(g)), name


def test_pack_sort_kernel_histogram_matches_jnp():
    """`_pack_sort` with the Pallas histogram kernel (interpret=True on
    CPU) equals the pure-jnp scatter-add histogram path."""
    rng = np.random.default_rng(3)
    T, K, S, cap, d = 64, 2, 16, 8, 4
    N = T * K
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    token_of = jnp.arange(N, dtype=jnp.int32) // K
    gslot = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    valid = jnp.asarray(rng.random(N) < 0.8)
    ref = _pack_sort(x, token_of, gslot, valid, num_classes=S, cap=cap,
                     use_kernel=False)
    got = _pack_sort(x, token_of, gslot, valid, num_classes=S, cap=cap,
                     use_kernel=True)
    for r, g, name in zip(ref, got, PACK_FIELDS):
        assert np.array_equal(np.asarray(r), np.asarray(g)), name


def test_pack_sort_drop_rule_is_first_come():
    """Capacity 1 with every token on one slot: only the FIRST token in
    token order survives — the drop rule both packers must share."""
    T, d, S = 16, 4, 4
    x = jnp.asarray(np.arange(T * d, dtype=np.float32).reshape(T, d))
    token_of = jnp.arange(T, dtype=jnp.int32)
    gslot = jnp.zeros((T,), jnp.int32)
    valid = jnp.ones((T,), bool)
    for pack in (_pack_onehot, _pack_sort):
        send, in_cap, _, counts, dropped = pack(
            x, token_of, gslot, valid, num_classes=S, cap=1)
        assert np.array_equal(np.asarray(in_cap),
                              [True] + [False] * (T - 1)), pack.__name__
        assert np.array_equal(np.asarray(send[0]), np.asarray(x[0]))
        assert int(dropped) == T - 1
        assert np.asarray(counts).tolist() == [1, 0, 0, 0]


@pytest.mark.parametrize("top_k", [1, 2])
def test_fused_router_matches_reference(top_k):
    rng = np.random.default_rng(top_k)
    d, E, T = 32, 8, 200
    moe = MoEConfig(num_experts=E, top_k=top_k, d_ff_expert=64)
    params = {"w": jnp.asarray(rng.normal(size=(d, E)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    ref = route(params, moe, x)
    got = route(params, moe, x, impl="fused")
    assert np.array_equal(np.asarray(ref.expert_idx), np.asarray(got.expert_idx))
    np.testing.assert_allclose(np.asarray(ref.gates), np.asarray(got.gates),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.probs), np.asarray(got.probs),
                               atol=1e-6)
    np.testing.assert_allclose(float(ref.aux_loss), float(got.aux_loss),
                               rtol=1e-5)
    np.testing.assert_allclose(float(ref.z_loss), float(got.z_loss),
                               rtol=1e-5)


def test_fused_router_histogram_counts_assignments():
    """The kernel's histogram side-output equals the scatter-add of its
    own top-k assignments (the Distribution-Only predictor's input)."""
    from repro.kernels import ops as kernel_ops
    rng = np.random.default_rng(7)
    T, E, K = 300, 16, 2
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    idx, _, _, _, counts = kernel_ops.fused_topk_route(logits, K)
    ref = np.zeros((E,), np.int64)
    np.add.at(ref, np.asarray(idx).reshape(-1), 1)
    assert np.array_equal(ref, np.asarray(counts))
    assert int(counts.sum()) == T * K


@pytest.mark.slow
def test_ep_forward_sort_matches_onehot_multidevice():
    """Full EP forward equivalence on a (2, 4) mesh across top_k,
    capacity factors (loose AND tight — identical drop decisions), and
    predicted-assignment mode with deliberately wrong predictions."""
    res = run_sub("""
        import dataclasses, itertools
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model

        base = get_config("mixtral-8x7b").reduced()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        out = {}
        for top_k, cap_f, predicted in itertools.product(
                (1, 2), (1.0, 8.0), (False, True)):
            cfg0 = dataclasses.replace(base, moe=dataclasses.replace(
                base.moe, top_k=top_k, capacity_factor=cap_f))
            params = init_model(jax.random.PRNGKey(0), cfg0)
            B, S = 4, 32
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (B, S), 0, cfg0.vocab_size)}
            pred = (jnp.zeros((cfg0.num_layers, B, S, top_k), jnp.int32)
                    if predicted else None)
            runs = {}
            for impl in ("onehot", "sort"):
                cfg = dataclasses.replace(cfg0, moe=dataclasses.replace(
                    cfg0.moe, dispatch_impl=impl))
                logits, _, stats = jax.jit(
                    lambda p, b, pr, c=cfg: forward(
                        p, c, b, rt, mode="train", predicted_idx=pr)
                )(params, batch, pred)
                runs[impl] = (logits, stats)
            la, sa = runs["onehot"]; lb, sb = runs["sort"]
            key = f"k{top_k}_c{cap_f}_p{int(predicted)}"
            out[key] = {
                "logits_diff": float(jnp.abs(
                    la.astype(jnp.float32) - lb.astype(jnp.float32)).max()),
                "counts_eq": bool(jnp.array_equal(sa["expert_counts"],
                                                  sb["expert_counts"])),
                "slots_eq": bool(jnp.array_equal(sa["slot_counts"],
                                                 sb["slot_counts"])),
                "dropped_a": int(np.asarray(sa["dropped"]).sum()),
                "dropped_b": int(np.asarray(sb["dropped"]).sum()),
            }
        print(json.dumps(out))
    """, timeout=1800)
    for key, r in res.items():
        assert r["counts_eq"], key
        assert r["slots_eq"], key
        assert r["dropped_a"] == r["dropped_b"], key
        assert r["logits_diff"] < 1e-5, (key, r["logits_diff"])
    # tight capacity on a skewed router must actually drop something,
    # otherwise the drop-rule legs of the suite test nothing
    assert any(r["dropped_a"] > 0 for k, r in res.items()
               if "_c1.0_" in k), res


@pytest.mark.slow
def test_ep_decode_sort_matches_onehot_multidevice():
    """Replicated-token decode dispatch: both impls agree bit-for-bit."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, init_cache, init_model
        from repro.train.steps import make_decode_step

        base = get_config("mixtral-8x7b").reduced()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        B = 4
        tok = jnp.ones((B, 1), jnp.int32)
        out = {}
        for impl in ("onehot", "sort"):
            cfg = dataclasses.replace(base, moe=dataclasses.replace(
                base.moe, dispatch_impl=impl))
            params = init_model(jax.random.PRNGKey(0), cfg)
            cache = init_cache(cfg, rt, B, 32)
            with mesh:
                _, logits, _, stats = jax.jit(
                    lambda p, t, c, cfg=cfg: make_decode_step(cfg, rt)(
                        p, t, c, 5))(params, tok, cache)
            out[impl] = {
                "logits": np.asarray(logits).astype(np.float64).sum().item(),
                "max": float(jnp.abs(logits).max()),
                "slots": np.asarray(stats["slot_counts"]).tolist(),
                "dropped": int(np.asarray(stats["dropped"]).sum()),
            }
        print(json.dumps({
            "sum_diff": abs(out["onehot"]["logits"] - out["sort"]["logits"]),
            "slots_eq": out["onehot"]["slots"] == out["sort"]["slots"],
            "dropped_eq": out["onehot"]["dropped"] == out["sort"]["dropped"],
        }))
    """)
    assert res["slots_eq"]
    assert res["dropped_eq"]
    assert res["sum_diff"] < 1e-4
