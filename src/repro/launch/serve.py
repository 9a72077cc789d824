"""Serving driver: batched requests through the ServeEngine with
prediction-guided expert duplication.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --reduced --strategy dist_only --requests 32 --batch 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.predictors import ConditionalProbabilityModel
from repro.data.synthetic import make_routing_trace, token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_dev_mesh
from repro.models.transformer import init_params
from repro.serve import BatchScheduler, Request, ServeConfig, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--strategy", default="dist_only",
                    choices=["none", "dist_only", "token_to_expert"])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--dup-slots", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-mesh", type=int, default=0)
    ap.add_argument("--model-mesh", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run "
                         "(open in Perfetto / chrome://tracing)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mesh, ep_ranks = None, 1
    if args.data_mesh and args.model_mesh:
        mesh = make_dev_mesh(args.data_mesh, args.model_mesh)
        ep_ranks = args.model_mesh

    params = init_params(jax.random.PRNGKey(args.seed), cfg, mesh=mesh)

    predictor = None
    if args.strategy == "token_to_expert" and cfg.is_moe:
        trace = make_routing_trace(
            num_sequences=64, seq_len=args.seq, vocab=cfg.vocab_size,
            num_experts=cfg.moe.num_experts, num_layers=cfg.num_layers,
            skew=1.5, seed=args.seed)
        predictor = ConditionalProbabilityModel(
            cfg.num_layers, cfg.moe.num_experts, cfg.vocab_size
        ).fit(trace.experts, trace.tokens)

    tracer = None
    if args.trace_out:
        from repro.obs import SpanTracer
        tracer = SpanTracer(process_name="repro-launch-serve")
    engine = ServeEngine(cfg, params,
                         ServeConfig(strategy=args.strategy,
                                     dup_slots=args.dup_slots,
                                     max_len=args.seq + args.new_tokens),
                         mesh=mesh, ep_ranks=ep_ranks, predictor=predictor,
                         tracer=tracer)

    sched = BatchScheduler(args.batch, args.seq)
    gen = token_batches(args.seed, cfg.vocab_size, 1, args.seq)
    for rid in range(args.requests):
        toks = next(gen)["tokens"][0]
        sched.submit(Request(rid, toks, max_new_tokens=args.new_tokens))

    t0 = time.perf_counter()
    batches = 0
    while sched.has_work():
        batch = sched.next_batch()
        out, tele = engine.generate({"tokens": jnp.asarray(batch["tokens"])},
                                    max_new_tokens=args.new_tokens)
        sched.finish(batch["requests"], np.asarray(out))
        batches += 1
        if cfg.is_moe and tele:
            print(f"batch {batches}: measured routing skew={tele['skew']:.2f}")
    dt = time.perf_counter() - t0
    done = len(sched.completed)
    print(f"served {done} requests in {batches} batches, {dt:.1f}s "
          f"({done * args.new_tokens / dt:.1f} tok/s)")
    if tracer is not None:
        tracer.export(args.trace_out,
                      extra={"pred_accuracy": engine.accuracy.to_obj()
                             if engine.accuracy else []})
        print(f"trace written to {args.trace_out}")
    return 0 if done == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
