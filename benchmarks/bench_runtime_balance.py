"""Runtime validation (beyond the simulator): measured per-rank token
loads in the REAL EP dispatch, no-prediction vs Distribution-Only, on an
8-fake-device mesh. The simulator's load factors (skew -> 1+eps) must show
up in actual slot counts. Runs as a subprocess (device-count isolation).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
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
    eng = ServeEngine(cfg, params, ServeConfig(strategy=strat, dup_slots=1),
                      mesh=mesh, ep_ranks=4)
    smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    batch, seq, iters = (4, 32, 3) if smoke else (8, 64, 5)
    gen = token_batches(0, cfg.vocab_size, batch=batch, seq_len=seq)
    for i in range(iters):
        _, _, stats = eng.prefill({"tokens": jnp.asarray(next(gen)["tokens"])})
    rl = eng.rank_loads(np.asarray(stats["slot_counts"]))
    out[strat] = {
        "bottleneck_over_mean": float((rl.max(1) / rl.mean(1)).mean()),
        "routing_skew": eng.history[-1]["skew"],
    }
print(json.dumps(out))
"""


def run(verbose: bool = True):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_BODY)],
        capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
                 JAX_PLATFORMS="cpu"))
    if res.returncode != 0:
        raise RuntimeError(res.stderr[-2000:])
    data = json.loads(res.stdout.strip().splitlines()[-1])
    if verbose:
        for k, v in data.items():
            print(f"{k:10s}: measured rank bottleneck/mean = "
                  f"{v['bottleneck_over_mean']:.3f} "
                  f"(routing skew {v['routing_skew']:.2f})")
        print("(duplication moves the bottleneck toward 1.0 = balanced)")
    derived = (data["none"]["bottleneck_over_mean"]
               - data["dist_only"]["bottleneck_over_mean"])
    rows = [dict(strategy=k, **v) for k, v in data.items()]
    return rows, derived


if __name__ == "__main__":
    run()
