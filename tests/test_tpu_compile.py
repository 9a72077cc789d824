"""Compile-only rehearsals at Mixtral-8x7B widths for a described TPU v5e.

Each test lowers and compiles a main-path program with the TPU compiler
for chips that are described, not attached (``jax.experimental.
topologies``). What the chip's compiler refuses — a tile past the scoped
VMEM limit, a Mosaic kernel left outside ``shard_map`` under a mesh —
fails here without a chip. Nothing runs, so nothing here is a result or
a time.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and the worker that runs
this file keeps it until it exits.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.kernels import ops

# Mixtral-8x7B published widths
D_MODEL, D_FF, EXPERTS, TOP_K = 4096, 14336, 8, 2
KV_HEADS, GROUP, HEAD_DIM, WINDOW = 8, 4, 128, 4096
SLOTS, BLOCK, MAX_LEN = 8, 16, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the Pallas kernels through Mosaic, not the interpreter."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def test_paged_decode_kernel_compiles(one_chip, mosaic):
    n_blocks = 1 + SLOTS * (MAX_LEN // BLOCK)
    q = _sds((SLOTS, KV_HEADS, GROUP, HEAD_DIM), jnp.bfloat16, one_chip)
    pool = _sds((n_blocks, BLOCK, KV_HEADS, HEAD_DIM), jnp.bfloat16,
                one_chip)
    tables = _sds((SLOTS, MAX_LEN // BLOCK), jnp.int32, one_chip)
    lengths = _sds((SLOTS,), jnp.int32, one_chip)
    _, text = _compile(partial(ops.paged_decode_attention, window=WINDOW),
                       q, pool, pool, tables, lengths)
    assert "tpu_custom_call" in text


def test_moe_gemm_compiles_at_mixtral_widths(one_chip, mosaic):
    """Default tiles need ~30 MB of VMEM at d 4096; the kernel must ask
    for it (the 16 MB default scoped limit refuses the compile)."""
    slots, tokens = 2, 256
    x = _sds((slots, tokens, D_MODEL), jnp.bfloat16, one_chip)
    w_in = _sds((slots, D_MODEL, D_FF), jnp.bfloat16, one_chip)
    w_out = _sds((slots, D_FF, D_MODEL), jnp.bfloat16, one_chip)
    compiled, text = _compile(
        lambda x, g, u, d: ops.moe_gemm(
            x, {"w_gate": g, "w_up": u, "w_down": d}, "swiglu"),
        x, w_in, w_in, w_out)
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_fused_topk_router_compiles(one_chip, mosaic):
    logits = _sds((4096, EXPERTS), jnp.float32, one_chip)
    _, text = _compile(partial(ops.fused_topk_route, top_k=TOP_K), logits)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("classes", [EXPERTS, 4 * (EXPERTS // 4 + 1) + 1])
def test_histogram_compiles(one_chip, mosaic, classes):
    """Expert counts (E classes) and the dispatch packer's slot offsets
    (EP=4 with one replica slot per rank, plus the overflow class)."""
    idx = _sds((8192,), jnp.int32, one_chip)
    _, text = _compile(partial(ops.histogram_offsets, num_classes=classes),
                       idx)
    assert "tpu_custom_call" in text


def _decode_step(topo, data, model, *, dup_slots=0):
    """Compile a 2-layer full-width paged decode step on a (data, model)
    Auto mesh with EP over ``model``. ``dup_slots`` > 0 adds the replica
    store with overlapped migration on, as the engine serves by default:
    live store, back buffer, per-layer ready mask and target plan."""
    from repro.core.placement import identity_plan, stack_plans
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import Runtime, init_model
    from repro.runtime.store import store_sharding
    from repro.serve.kvcache import pool_sharding
    from repro.sharding import make_shardings, param_specs
    from repro.train.steps import make_paged_decode_step

    base = get_config("mixtral-8x7b")
    cfg = dataclasses.replace(base, num_layers=2, moe=dataclasses.replace(
        base.moe, duplication_slots=dup_slots,
        overlap_migration=dup_slots > 0))
    L = cfg.num_layers
    mesh = make_dev_mesh(data, model, devices=topo.devices[:data * model])
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda k: init_model(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), shapes,
        make_shardings(mesh, param_specs(shapes, mesh=mesh)))
    plan = stack_plans([identity_plan(EXPERTS, model, dup_slots,
                                      cfg.moe.max_copies) for _ in range(L)])
    plan = jax.tree.map(lambda a: _sds(a.shape, a.dtype, rep), plan)
    n_blocks = 1 + SLOTS * (MAX_LEN // BLOCK)
    pool = {k: _sds((L, n_blocks, BLOCK, KV_HEADS, HEAD_DIM),
                    jnp.bfloat16, pool_sharding(cfg, mesh))
            for k in ("k", "v")}
    rt = Runtime(mesh=mesh, ep=True, ep_ranks=model,
                 use_duplication=dup_slots > 0, window_override=MAX_LEN)
    args = [params, _sds((SLOTS, 1), jnp.int32, rep), pool,
            _sds((SLOTS, MAX_LEN // BLOCK), jnp.int32, rep),
            _sds((SLOTS,), jnp.int32, rep), plan,
            _sds((SLOTS, 1), jnp.float32, rep)]
    if dup_slots:
        n_store = model * (EXPERTS // model + dup_slots)
        store = {k: _sds((L, n_store) + a.shape[2:], a.dtype,
                         store_sharding(mesh, a.ndim))
                 for k, a in shapes["layers"]["moe"]["experts"].items()}
        args += [store, dict(store), _sds((L,), jnp.bool_, rep), plan]
    with mesh:
        compiled = jax.jit(make_paged_decode_step(cfg, rt)).lower(
            *args).compile()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    return compiled.as_text(), mem, per_chip


def test_ep4_paged_decode_step_compiles(topo, mosaic):
    """A 2-layer full-width decode step on a 1x4 Auto mesh, EP=4. The
    fused paged-decode kernel is a Mosaic call, which the partitioner
    refuses unless it runs inside ``shard_map``; each chip's share of the
    weights, pool and temporaries must fit its 16 GB."""
    text, _, per_chip = _decode_step(topo, 1, 4)
    assert "tpu_custom_call" in text
    assert 0 < per_chip < 16e9


def test_data_sharded_paged_decode_step_compiles(topo, mosaic):
    """On a 2x2 (data=2, model=2) mesh the kernel's ``shard_map`` splits
    the slots over data like the decode activations, so each chip attends
    for its own 4 of the 8 slots on its 4 of the 8 KV heads — never for
    every slot after an all-gather."""
    text, _, per_chip = _decode_step(topo, 2, 2)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and f"bf16[{SLOTS // 2},{KV_HEADS // 2},{GROUP},{HEAD_DIM}]"
             in ln]
    assert calls, "paged-decode kernel does not take per-shard slots"
    assert not any(f"[{SLOTS},{KV_HEADS // 2},{GROUP},{HEAD_DIM}]" in ln
                   for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert 0 < per_chip < 16e9


def test_ep4_overlap_store_decode_step_compiles(topo, mosaic):
    """The default duplication path at EP=4: one replica slot per chip,
    the resident store and its back buffer as arguments, overlapped
    migration's per-layer ``lax.cond`` around the dispatch. The cond must
    not materialize a store-sized temporary."""
    text, mem, per_chip = _decode_step(topo, 1, 4, dup_slots=1)
    assert "tpu_custom_call" in text
    assert 0 < per_chip < 16e9
    store_layer_bytes = 3 * D_MODEL * D_FF * 2 * (EXPERTS // 4 + 1)
    assert mem.temp_size_in_bytes < store_layer_bytes


# DeepSeek-V2-Lite published widths: 16 heads over a 512 + 64 latent row
# (padded to 640 lanes), the cell's 80 slots and 2,048 positions
DS_HEADS, DS_ROW, DS_LATENT, DS_SLOTS, DS_MAX_LEN = 16, 640, 512, 80, 2048


def test_latent_decode_kernel_compiles(one_chip, mosaic):
    """The paged latent kernel's multi-page DMAs move whole 128-lane
    tiles of a row (a 576-wide row would need an unaligned slice)."""
    n_blocks = 1 + DS_SLOTS * (DS_MAX_LEN // BLOCK)
    q = _sds((DS_SLOTS, DS_HEADS, DS_ROW), jnp.bfloat16, one_chip)
    pool = _sds((n_blocks, BLOCK, DS_ROW), jnp.bfloat16, one_chip)
    tables = _sds((DS_SLOTS, DS_MAX_LEN // BLOCK), jnp.int32, one_chip)
    lengths = _sds((DS_SLOTS,), jnp.int32, one_chip)
    _, text = _compile(partial(ops.latent_decode_attention, r=DS_LATENT,
                               scale=0.1147), q, pool, tables, lengths)
    assert "tpu_custom_call" in text


def test_deepseek_v2_decode_step_compiles(topo, mosaic):
    """The dense layer and one MoE layer of DeepSeek-V2-Lite at published
    widths, served as the cell serves them (one chip, dropless capacity,
    80 slots over the latent pool): the latent kernel runs in its
    ``shard_map``, and weights, pool and temporaries fit the chip."""
    from repro.core.placement import identity_plan, stack_plans
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import Runtime, init_model
    from repro.serve.kvcache import init_block_pool, pool_sharding
    from repro.sharding import make_shardings, param_specs
    from repro.train.steps import make_paged_decode_step

    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(base, num_layers=2, moe=dataclasses.replace(
        base.moe, capacity_factor=64 / 6))
    mesh = make_dev_mesh(1, 1, devices=topo.devices[:1])
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda k: init_model(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), shapes,
        make_shardings(mesh, param_specs(shapes, mesh=mesh)))
    plan = stack_plans([identity_plan(64, 1, 0, cfg.moe.max_copies)])
    plan = jax.tree.map(lambda a: _sds(a.shape, a.dtype, rep), plan)
    n_blocks = 1 + DS_SLOTS * (DS_MAX_LEN // BLOCK)
    pool = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, pool_sharding(cfg, mesh)),
        jax.eval_shape(lambda: init_block_pool(cfg, n_blocks, BLOCK)))
    rt = Runtime(mesh=mesh, ep=True, ep_ranks=1, window_override=DS_MAX_LEN)
    with mesh:
        compiled = jax.jit(make_paged_decode_step(cfg, rt)).lower(
            params, _sds((DS_SLOTS, 1), jnp.int32, rep), pool,
            _sds((DS_SLOTS, DS_MAX_LEN // BLOCK), jnp.int32, rep),
            _sds((DS_SLOTS,), jnp.int32, rep), plan,
            _sds((DS_SLOTS, 1), jnp.float32, rep)).compile()
    text = compiled.as_text()
    assert sum("tpu_custom_call" in ln and f"{DS_LATENT}]" in ln
               for ln in text.splitlines()) >= 1
    mem = compiled.memory_analysis()
    assert 0 < (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < 16e9
