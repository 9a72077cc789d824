"""Chip smoke test: serve Mixtral-8x7B at its published width on a TPU.

Drives the continuous-batching serving path the way a user does —
``ContinuousEngine`` warmup -> submit -> step — with seeded random bf16
weights at Mixtral-8x7B's published widths (d_model 4096, GQA 32/8 x 128,
8 experts top-2 at d_ff 14336, vocab 32000), depth cut to fit the chips.

  python chip_smoke.py             # one chip (default)
  python chip_smoke.py --chips 4   # four chips: the expert-parallel phase

One chip: a 1x1 mesh with one EP rank, so MoE runs through the EP
dispatch (``repro.moe.dispatch``), not the all-experts reference. Serves 8
requests, then checks the fused paged-decode kernel against its jnp
oracle and one full-width MoE layer through EP dispatch against the dense
reference, both on the chip.

Four chips: a 1x4 mesh, EP=4 (2 home experts per chip per layer) with one
replica slot per chip and the resident replica store. Serves skewed
prompts under strategy ``dist_only`` with synchronous migration (re-plans
commit migrations), under ``dist_only`` with overlapped migration (the
engine's default) and under ``none`` (identity plan); the generated
tokens must be equal. One MoE layer under the committed plan, reading
the store's replica weights, is checked against the dense reference.

Exits non-zero, printing no result line, when JAX finds no TPU or any
check fails. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Depth cuts at full width. One chip (16 GB HBM): 4 layers are 12.1 GB of
# bf16 weights, leaving room for the KV pool and activations. Four chips:
# per chip and layer, 0.70 GB of home experts + 1.06 GB of replica store
# (a second copy of the home experts plus the replica slot) + 1.06 GB for
# the store's migration double buffer, so 4 layers take 11.6 GB per chip
# with the embedding, head and migration temporaries (compile-time
# memory_analysis for a described v5e 2x2).
LAYERS_ONE_CHIP = 4
LAYERS_FOUR_CHIPS = 4
NUM_REQUESTS = 8
NEW_TOKENS = 32
SEED = 0
# bf16 agreement limits: max |got - want| <= ATOL + RTOL * max |want|
KERNEL_ATOL, KERNEL_RTOL = 2e-2, 2e-2
MOE_ATOL, MOE_RTOL = 2e-2, 2e-2


def smoke_config(layers: int, ep_ranks: int, dup_slots: int):
    """mixtral-8x7b at published widths, ``layers`` deep. Mixtral routes
    dropless, so the capacity factor is set where no slot can overflow:
    a rank's tokens fill one slot at most once each (top-k experts are
    distinct), which ``ep_ranks * n_slots / top_k`` guarantees."""
    from repro.configs.registry import get_config
    base = get_config("mixtral-8x7b")
    m = base.moe
    n_slots = m.num_experts // ep_ranks + dup_slots
    cf = float(ep_ranks * n_slots) / m.top_k
    return dataclasses.replace(
        base, num_layers=layers,
        moe=dataclasses.replace(m, capacity_factor=cf))


def make_requests(vocab: int, *, skewed: bool, seed: int = SEED):
    """8 seeded requests with 128-512-token prompts. ``skewed`` draws each
    prompt from a Zipf distribution over 32 token ids, so routing
    concentrates on few experts and re-plans have load to balance."""
    import numpy as np
    from repro.serve.scheduler import ServeRequest
    rng = np.random.default_rng(seed)
    hot = rng.choice(vocab, size=32, replace=False)
    p = 1.0 / np.arange(1, 33) ** 1.5
    reqs = []
    for rid in range(NUM_REQUESTS):
        n = int(rng.integers(128, 513))
        toks = (rng.choice(hot, size=n, p=p / p.sum()) if skewed
                else rng.integers(0, vocab, size=n))
        reqs.append(ServeRequest(rid=rid, tokens=toks.tolist(),
                                 max_new_tokens=NEW_TOKENS, arrival=0.0))
    return reqs


def serve(engine, reqs):
    """Submit every request, step on a fixed virtual clock until drained.
    Returns ({rid: generated tokens}, wall seconds)."""
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    now, iters = 0.0, 0
    while engine.has_work():
        engine.step(now)
        now += 1.0
        iters += 1
        if iters > 50 * NEW_TOKENS:
            raise RuntimeError(f"engine did not drain in {iters} steps")
    return {r.rid: list(r.generated) for r in reqs}, time.perf_counter() - t0


def tree_bytes(tree) -> int:
    import jax
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def peak_bytes(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def within(got, want, atol: float, rtol: float):
    """(ok, max abs error, max |want|) of two arrays compared in f32."""
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    err = float(np.abs(g - w).max())
    scale = float(np.abs(w).max())
    ok = bool(np.isfinite(g).all()) and err <= atol + rtol * scale
    return ok, err, scale


def engine_config(strategy: str, dup_slots: int, **kw):
    from repro.serve import ContinuousConfig
    return ContinuousConfig(max_slots=8, block_size=16, prefill_len=512,
                            max_len=1024, strategy=strategy,
                            dup_slots=dup_slots, predict_interval=4, **kw)


def check_served(name, toks, reqs, checks):
    served = sum(len(t) for t in toks.values())
    ok = all(len(toks[r.rid]) == r.max_new_tokens for r in reqs)
    checks.append((f"{name}: every request completed with its tokens", ok,
                   f"{served} tokens over {len(reqs)} requests"))
    return served


def check_paged_kernel(engine, cfg, checks):
    """Fused paged-decode kernel vs ``kernels.ref.paged_decode_ref`` at the
    engine's pool shapes, with a random permutation of blocks as tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from repro.kernels import ops
    from repro.kernels.ref import paged_decode_ref

    N, bs, K, hd = engine.pool["k"].shape[1:]
    B, M = engine.ccfg.max_slots, engine.ccfg.max_len // bs
    G = cfg.num_heads // K
    rng = np.random.default_rng(SEED)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(ks[0], (B, K, G, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (N, bs, K, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (N, bs, K, hd), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))[:B * M]
                         .reshape(B, M), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, B * M * bs // B, B), jnp.int32)
    fused_fn = jax.jit(partial(ops.paged_decode_attention,
                               window=cfg.sliding_window))
    mosaic = "tpu_custom_call" in fused_fn.lower(
        q, kp, vp, tables, lengths).as_text()
    fused = fused_fn(q, kp, vp, tables, lengths)
    ref = jax.jit(lambda q, kp, vp, t, ln: paged_decode_ref(
        q, kp[t].reshape(B, M * bs, K, hd), vp[t].reshape(B, M * bs, K, hd),
        ln, window=cfg.sliding_window, block_size=bs))(
            q, kp, vp, tables, lengths)
    ok, err, scale = within(fused, ref, KERNEL_ATOL, KERNEL_RTOL)
    checks.append(("fused paged-decode kernel (Mosaic) == paged_decode_ref",
                   ok and mosaic,
                   f"B {B} K {K} G {G} hd {hd} bs {bs} blocks {N} "
                   f"window {cfg.sliding_window}: max err {err:.3e} vs "
                   f"max |ref| {scale:.3e} (limit {KERNEL_ATOL} + "
                   f"{KERNEL_RTOL} x max |ref|), tpu_custom_call={mosaic}"))


def check_moe_layer(engine, name, checks, *, layer: int = 0, plan=None,
                    store=None, tokens: int = 512):
    """One layer's MoE block through the engine's EP dispatch vs the dense
    all-experts reference, on one prefill bucket of random activations.
    ``plan``/``store``: a stacked placement plan and the replica store's
    slot weights to dispatch under (default: identity plan, home
    experts)."""
    import jax
    import jax.numpy as jnp
    from repro.models.moe import moe_ffn_dense
    from repro.models.transformer import moe_apply

    cfg, rt = engine.cfg, engine.rt
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (1, tokens, cfg.d_model), jnp.bfloat16)

    def pick(tree):
        # sliced inside the jitted programs: a sliced copy of one layer's
        # experts (2.8 GB) would not fit beside the weights
        return jax.tree.map(lambda a: a[layer], tree)

    with engine.mesh:
        y_ep, _, _, _, _, dropped, _ = jax.jit(
            lambda p, x, plan, store: moe_apply(
                pick(p["layers"]), cfg, x, rt,
                None if plan is None else pick(plan), None, decode=False,
                slot_w_l=None if store is None else pick(store)))(
                    engine.params, x, plan, store)
        y_ref = jax.jit(lambda p, x: moe_ffn_dense(
            pick(p["layers"])["moe"], cfg, x)[0])(engine.params, x)
    ok, err, scale = within(y_ep, y_ref, MOE_ATOL, MOE_RTOL)
    dropped = int(dropped)
    checks.append((f"{name}: MoE layer {layer} via EP dispatch == "
                   f"moe_ffn_dense, 0 dropped", ok and dropped == 0,
                   f"{tokens} tokens, E {cfg.moe.num_experts} top-"
                   f"{cfg.moe.top_k} d_ff {cfg.moe.d_ff_expert}: max err "
                   f"{err:.3e} vs max |ref| {scale:.3e} (limit {MOE_ATOL} "
                   f"+ {MOE_RTOL} x max |ref|), dropped {dropped}"))


def one_chip(checks):
    import jax
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import init_params
    from repro.serve import ContinuousEngine

    cfg = smoke_config(LAYERS_ONE_CHIP, ep_ranks=1, dup_slots=0)
    mesh = make_dev_mesh(1, 1, devices=jax.devices()[:1])
    params = init_params(jax.random.PRNGKey(SEED), cfg, mesh=mesh)
    print(f"one chip: mixtral-8x7b d_model {cfg.d_model}, {cfg.num_layers} "
          f"of 32 layers, weights {tree_bytes(params) / 1e9:.3f} GB bf16")
    engine = ContinuousEngine(cfg, params, engine_config("none", 0),
                              mesh=mesh, ep_ranks=1)
    t0 = time.perf_counter()
    engine.warmup()
    print(f"one chip: warmup (compile) {time.perf_counter() - t0:.1f} s")
    reqs = make_requests(cfg.vocab_size, skewed=False)
    toks, wall = serve(engine, reqs)
    served = check_served("one chip", toks, reqs, checks)
    print(f"one chip: served {served} tokens in {wall:.2f} s wall "
          f"(includes host scheduling; not a benchmark)")
    engine.assert_no_recompiles()
    checks.append(("one chip: no recompiles after warmup", True,
                   str(engine.compile_counts())))
    print(f"one chip: peak_bytes_in_use after serving "
          f"{peak_bytes(jax.devices()[:1])[0] / 1e9:.3f} GB")
    check_paged_kernel(engine, cfg, checks)
    check_moe_layer(engine, "one chip", checks)
    print(f"one chip: peak_bytes_in_use after the checks "
          f"{peak_bytes(jax.devices()[:1])[0] / 1e9:.3f} GB")


def four_chips(checks):
    import jax
    import numpy as np
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import init_params
    from repro.serve import ContinuousEngine

    devices = jax.devices()[:4]
    cfg = smoke_config(LAYERS_FOUR_CHIPS, ep_ranks=4, dup_slots=1)
    mesh = make_dev_mesh(1, 4, devices=devices)
    params = init_params(jax.random.PRNGKey(SEED), cfg, mesh=mesh)
    print(f"four chips: mixtral-8x7b d_model {cfg.d_model}, "
          f"{cfg.num_layers} of 32 layers, EP=4, 1 replica slot per chip, "
          f"weights {tree_bytes(params) / 1e9:.3f} GB bf16 in all")
    reqs = make_requests(cfg.vocab_size, skewed=True)
    # (label, strategy, overlapped migration). migrate_chunk 1: one slot
    # entry (352 MB at these widths) per fill step keeps the step's
    # broadcast temporaries small. "sync": each accepted re-plan drains
    # and commits before the next step, so the run commits migrations
    # deterministically. "overlap" is the engine's default: fills ride
    # under the steps and each layer switches to the back buffer as its
    # fill lands; the fill is budgeted from wall-clock step times and may
    # be restarted by the next re-plan before it commits.
    legs = (("dist_only sync", "dist_only", False),
            ("dist_only overlap", "dist_only", True),
            ("none", "none", True))
    runs = {}
    for label, strategy, overlap in legs:
        name = f"four chips [{label}]"
        engine = ContinuousEngine(
            cfg, params, engine_config(strategy, 1, migrate_chunk=1,
                                       overlap_migration=overlap),
            mesh=mesh, ep_ranks=4)
        t0 = time.perf_counter()
        engine.warmup()
        print(f"{name}: warmup (compile) {time.perf_counter() - t0:.1f} s, "
              f"store {engine._store.hbm_bytes_per_rank / 1e9:.3f} GB "
              f"per chip")
        toks, wall = serve(
            engine, [dataclasses.replace(r, generated=[]) for r in reqs])
        engine.assert_no_recompiles()
        mig = dict(engine.metrics.migration)
        dropped = engine.metrics.resched["dropped_tokens"]
        served = check_served(name, toks, reqs, checks)
        print(f"{name}: served {served} tokens in {wall:.2f} s wall, "
              f"re-plans {mig['replans']:.0f}, pre-begun "
              f"{mig['prebegun']:.0f}, cancelled {mig['cancelled']:.0f}, "
              f"migrations committed {mig['commits']:.0f}, bytes moved "
              f"{mig['bytes_moved']:.0f}, dropped tokens {dropped:.0f}")
        if label == "dist_only sync":
            # the cross-chip exchange against the dense reference, under
            # the committed plan on the layer with the most replicas
            plan = engine._plan_stack
            extra = (np.asarray(plan.n_replicas) - 1).sum(axis=1)
            layer = int(extra.argmax())
            print(f"{name}: replicas in use per layer {extra.tolist()}")
            check_moe_layer(engine, name, checks, layer=layer, plan=plan,
                            store=engine._store.weights)
            checks.append((f"{name}: checked layer serves >= 1 replica",
                           int(extra[layer]) >= 1,
                           f"layer {layer}: {int(extra[layer])}"))
        runs[label] = (toks, mig, dropped)
        del engine
        gc.collect()
    id_toks = runs["none"][0]
    for label in ("dist_only sync", "dist_only overlap"):
        toks, mig, _ = runs[label]
        same = sum(toks[r] == id_toks[r] for r in toks)
        checks.append((f"four chips [{label}]: serves the identity plan's "
                       f"tokens", same == len(reqs),
                       f"{same}/{len(reqs)} requests token-identical"))
    sync_mig, ovl_mig = runs["dist_only sync"][1], runs["dist_only overlap"][1]
    checks.append(("four chips [dist_only sync]: >= 1 migration committed",
                   sync_mig["commits"] >= 1 and sync_mig["bytes_moved"] > 0,
                   f"commits {sync_mig['commits']:.0f}, bytes moved "
                   f"{sync_mig['bytes_moved']:.0f}"))
    checks.append(("four chips [dist_only overlap]: >= 1 fill chunk landed",
                   ovl_mig["bytes_moved"] > 0,
                   f"bytes moved {ovl_mig['bytes_moved']:.0f}, commits "
                   f"{ovl_mig['commits']:.0f}"))
    drops = [runs[label][2] for label, _, _ in legs]
    checks.append(("four chips: no dropped tokens", not any(drops),
                   " / ".join(f"{d:.0f}" for d in drops)))
    peaks = peak_bytes(devices)
    print("four chips: peak_bytes_in_use per chip (GB) "
          + " ".join(f"{p / 1e9:.3f}" for p in peaks))
    checks.append(("four chips: every chip holds weights, peaks within 20%",
                   min(peaks) > 0 and min(peaks) >= 0.8 * max(peaks),
                   f"min {min(peaks) / 1e9:.3f} GB, max "
                   f"{max(peaks) / 1e9:.3f} GB"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-chip serving phase; 4: the four-chip "
                         "expert-parallel phase and its comparison only")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}, "
          f"jax {jax.__version__}")

    checks = []
    (four_chips if args.chips == 4 else one_chip)(checks)
    for name, ok, detail in checks:
        print(f"check {'PASS' if ok else 'FAIL'}: {name} -- {detail}")
    if not checks or not all(ok for _, ok, _ in checks):
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
