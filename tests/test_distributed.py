"""Multi-device tests (EP dispatch, duplication, serving loop, sharding).

These need >1 device, so each test runs in a SUBPROCESS with
xla_force_host_platform_device_count=8 — the main pytest process keeps the
single-device view required by the smoke tests."""

import json
import os
import subprocess

import pytest
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


pytestmark = pytest.mark.slow  # every test here boots a subprocess mesh


def run_sub(body: str, timeout=900) -> dict:
    """Run `body` under 8 fake devices; it must print a JSON dict."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src")))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ep_dispatch_matches_dense_reference():
    """EP shard_map dispatch == single-device dense MoE forward (same
    params, capacity high enough that nothing drops)."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model

        cfg = get_config("mixtral-8x7b").reduced()
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = init_model(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (4, 32), 0, cfg.vocab_size)}
        dense_logits, _, _ = forward(params, cfg, batch, Runtime(),
                                     mode="train")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        ep_logits, _, stats = jax.jit(
            lambda p, b: forward(p, cfg, b, rt, mode="train"))(params, batch)
        diff = float(jnp.abs(dense_logits.astype(jnp.float32)
                             - ep_logits.astype(jnp.float32)).max())
        print(json.dumps({"diff": diff}))
    """)
    assert res["diff"] < 0.1             # bf16 path differences only


def test_duplication_improves_measured_balance():
    res = run_sub("""
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import ServeEngine, ServeConfig
        from repro.data.synthetic import token_batches

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        out = {}
        for strat in ("none", "dist_only"):
            eng = ServeEngine(cfg, params,
                              ServeConfig(strategy=strat, dup_slots=1),
                              mesh=mesh, ep_ranks=4)
            gen = token_batches(0, cfg.vocab_size, batch=4, seq_len=32)
            for i in range(4):
                _, _, stats = eng.prefill(
                    {"tokens": jnp.asarray(next(gen)["tokens"])})
            rl = eng.rank_loads(np.asarray(stats["slot_counts"]))
            out[strat] = float((rl.max(1) / rl.mean(1)).mean())
        print(json.dumps(out))
    """)
    assert res["dist_only"] < res["none"] - 0.05


def test_t2e_predicted_dispatch_correctness():
    """Predicted pre-routing with correction == unpredicted dispatch
    outputs (same tokens end at the same experts regardless of route)."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model

        cfg = get_config("mixtral-8x7b").reduced()
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = init_model(jax.random.PRNGKey(0), cfg)
        B, S = 4, 32
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (B, S), 0, cfg.vocab_size)}
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        ref_logits, _, _ = jax.jit(
            lambda p, b: forward(p, cfg, b, rt, mode="train"))(params, batch)
        # deliberately WRONG predictions: correction round must fix them
        pred = jnp.zeros((cfg.num_layers, B, S, cfg.moe.top_k), jnp.int32)
        lg, _, _ = jax.jit(
            lambda p, b, pr: forward(p, cfg, b, rt, mode="train",
                                     predicted_idx=pr))(params, batch, pred)
        diff = float(jnp.abs(ref_logits.astype(jnp.float32)
                             - lg.astype(jnp.float32)).max())
        print(json.dumps({"diff": diff}))
    """)
    # all-wrong predictions stress the correction path; capacity 8x keeps
    # drops at zero so outputs must match
    assert res["diff"] < 0.1


def test_param_specs_shard_and_gather_consistency():
    """Sharded + fsdp params produce the same forward as replicated."""
    res = run_sub("""
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model
        from repro.sharding import make_shardings, param_specs

        cfg = get_config("olmo-1b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.zeros((4, 16), jnp.int32)}
        ref, _, _ = forward(params, cfg, batch, Runtime(), mode="train")

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        specs = param_specs(params, fsdp_axes=("data",), fsdp_size=2,
                            mesh=mesh)
        sharded = jax.device_put(params, make_shardings(mesh, specs))
        rt = Runtime(mesh=mesh)
        with mesh:
            out, _, _ = jax.jit(
                lambda p, b: forward(p, cfg, b, rt, mode="train"))(
                    sharded, batch)
        diff = float(jnp.abs(ref.astype(jnp.float32)
                             - out.astype(jnp.float32)).max())
        print(json.dumps({"diff": diff}))
    """)
    assert res["diff"] < 5e-2          # bf16 matmul partitioning noise


def test_dev_mesh_decode_moe():
    """EP decode path (replicated tokens + psum combine) matches dense."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model, \
            init_cache
        from repro.train.steps import make_decode_step

        cfg = get_config("deepseek-v2-lite-16b").reduced()
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = init_model(jax.random.PRNGKey(0), cfg)
        B = 4
        tok = jnp.ones((B, 1), jnp.int32)

        dense_cache = init_cache(cfg, Runtime(), B, 32)
        _, dl, _, _ = make_decode_step(cfg, Runtime())(
            params, tok, dense_cache, 5)

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        ep_cache = init_cache(cfg, rt, B, 32)
        with mesh:
            _, el, _, _ = jax.jit(
                lambda p, t, c: make_decode_step(cfg, rt)(p, t, c, 5))(
                    params, tok, ep_cache)
        diff = float(jnp.abs(dl.astype(jnp.float32)
                             - el.astype(jnp.float32)).max())
        print(json.dumps({"diff": diff}))
    """)
    assert res["diff"] < 0.1


def test_expert_tp_decode_matches_dense():
    """2D expert sharding (EP x f-TP, EXPERIMENTS.md Perf cycle 2): decode
    outputs match the dense reference; weights stay resident."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import Runtime, forward, init_model, \
            init_cache
        from repro.train.steps import make_decode_step

        cfg = get_config("mixtral-8x7b").reduced()
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = init_model(jax.random.PRNGKey(0), cfg)
        B = 4
        tok = jnp.ones((B, 1), jnp.int32)
        _, dl, _, _ = make_decode_step(cfg, Runtime())(
            params, tok, init_cache(cfg, Runtime(), B, 32), 5)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4, decode_expert_tp=True)
        cache = init_cache(cfg, rt, B, 32)
        with mesh:
            _, el, _, stats = jax.jit(
                lambda p, t, c: make_decode_step(cfg, rt)(p, t, c, 5))(
                    params, tok, cache)
        diff = float(jnp.abs(dl.astype(jnp.float32)
                             - el.astype(jnp.float32)).max())
        counts = float(np.asarray(stats["expert_counts"]).sum())
        print(json.dumps({"diff": diff, "counts": counts,
                          "expect": cfg.num_layers * B * cfg.moe.top_k}))
    """)
    assert res["diff"] < 5e-2
    assert res["counts"] == res["expect"]


def test_in_graph_replan_balances():
    """Fused predict->plan->dispatch (duplicate_experts_jax inside the
    prefill step) balances as well as the host-side planner.

    The pass threshold is DERIVED per run: round-robin over the active
    plan's replica sets has an achievable imbalance for the observed
    expert histogram (`plan_rank_loads`), and the measured slot loads may
    only exceed it by the round-robin discretization + capacity-drop
    margin. A fixed magic constant here was flaky — borderline runs
    measured ~1.42 against an asserted 1.35 (see CHANGES.md, PR 1)."""
    res = run_sub("""
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import ServeEngine, ServeConfig
        from repro.serve.metrics import imbalance, plan_rank_loads
        from repro.data.synthetic import token_batches

        np.random.seed(0)                       # routing inputs: one stream
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        out = {}
        for in_graph in (False, True):
            eng = ServeEngine(cfg, params,
                              ServeConfig(strategy="dist_only", dup_slots=1,
                                          in_graph_replan=in_graph),
                              mesh=mesh, ep_ranks=4)
            gen = token_batches(0, cfg.vocab_size, batch=4, seq_len=32)
            for i in range(4):
                plan_used = eng._current_plan()   # active DURING the batch
                _, _, stats = eng.prefill(
                    {"tokens": jnp.asarray(next(gen)["tokens"])})
            rl = eng.rank_loads(np.asarray(stats["slot_counts"]))
            counts = np.asarray(stats["expert_counts"], np.float64)
            ach = imbalance(plan_rank_loads(
                counts, plan_used, eng.ep_ranks,
                eng.moe_cfg.duplication_slots))
            key = "graph" if in_graph else "host"
            out[key] = float((rl.max(1) / rl.mean(1)).mean())
            out[key + "_achievable"] = float(ach)
        print(json.dumps(out))
    """)
    for mode in ("graph", "host"):
        # achievable + round-robin discretization / drop slack
        assert res[mode] < res[mode + "_achievable"] * 1.1 + 0.1, res
        assert res[mode] < 1.6                 # none-strategy level: unbalanced
    assert abs(res["graph"] - res["host"]) < 0.25
