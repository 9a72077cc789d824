"""The DeepSeek-V2 architecture module (``bench/arch/deepseek_v2.py``):
its configuration file holds the published config, its serving tree is
the program's parameter layout, its counts match hand arithmetic for the
served cut, and the check that decides ``correct`` holds at a tiny size
(Pallas interpreted): the program passes, the fp8 control and each
planted fault fail."""

import json
import os

import jax
import numpy as np
import pytest

import counts as C
import faults
import run as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 31 + 977
SECONDS = 3.0

with open(os.path.join(R.BENCH, "configs", "deepseek-v2-lite-1c.json")) as f:
    CONF = json.load(f)
A = R.load_arch(CONF)


def _load(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def test_config_file_is_the_published_config_but_for_its_cut():
    published = A.PUBLISHED[CONF["source"]]
    assert set(CONF["reduced"]) == {"num_hidden_layers"}
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert CONF[key] == value, key
    # stage 1 of 3: the dense layer and at least four MoE layers
    assert CONF["num_hidden_layers"] == 27 // 3
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] >= 4
    # dropless: every expert can take every token of a step
    e = CONF["engine"]
    assert e["capacity_factor"] * CONF["num_experts_per_tok"] \
        >= CONF["n_routed_experts"]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("moe_layer_freq", 2),
    ("routed_scaling_factor", 16.0)])
def test_dims_refuse_what_the_program_does_not_serve(key, value):
    with pytest.raises(SystemExit, match="the program serves"):
        A.dims({**CONF, key: value})


def test_serving_tree_is_the_programs_layout():
    """Every leaf where ``init_params`` puts it, at its shape, in bf16."""
    from repro.models.transformer import init_params
    conf = _load("tiny-dsv2-1c")
    D = A.dims(conf)
    cfg = A.model_config(conf, conf["engine"])
    ours = jax.eval_shape(lambda r: A.serving_tree(r, D),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_expert_and_fixed_bytes_of_the_cut():
    D = A.dims(CONF)
    # gate + up + down, 2048 x 1408, bf16
    assert A.expert_bytes(D) == 3 * 2048 * 1408 * 2 == 17_301_504
    # q 2048 x 16 x 192, kv_a 2048 x 576, latent norm 512, kv_b 512 x 16 x
    # 256, output 16 x 128 x 2048
    attn = (6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304) * 2
    assert A.attn_weight_bytes(D) == attn == 27_526_144
    # an MoE layer outside its routed experts: attention, two norms,
    # router 2048 x 64, shared experts 3 x 2048 x 2816
    moe = attn + (2 * 2048 + 2048 * 64 + 3 * 2048 * 2816) * 2
    # the dense layer: attention, two norms, MLP 3 x 2048 x 10944
    dense = attn + (2 * 2048 + 3 * 2048 * 10944) * 2
    head = (2048 * 102400 + 2048) * 2
    assert A.fixed_bytes(D) == dense + 8 * moe + head == 1_080_644_608
    # the whole stage: 10.36 GB of weights
    total = (A.fixed_bytes(D) + 8 * 64 * A.expert_bytes(D)
             + 102400 * 2048 * 2)
    assert abs(total / 1e9 - 10.36) < 0.01


def test_latent_rows_read_at_context():
    D = A.dims(CONF)
    # 512 + 64 columns of bf16 in each of 9 layers
    assert A.kv_bytes_per_token(D) == 576 * 2 * 9 == 10_368
    # a decode at context 1000 reads 63 blocks of 16 rows
    assert A.kv_read_bytes(D, 1000, 16) == 63 * 16 * 10_368
    assert A.kv_read_bytes(D, 1008, 16) == A.kv_read_bytes(D, 993, 16)


def test_decode_flops_are_the_absorbed_form():
    D = A.dims(CONF)
    proj = 2 * (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512
                + 16 * 512 * 128 + 16 * 128 * 2048)
    attn = 2 * 16 * (512 + 64 + 512)                 # per attended position
    moe = 2 * 2048 * 64 + 6 * 3 * 2 * 2048 * 1408 + 3 * 2 * 2048 * 2816
    dense = 3 * 2 * 2048 * 10944
    body = 9 * proj + dense + 8 * moe + C.head_flops(D)
    assert A.decode_flops(D, 1) == body + 9 * attn
    assert A.decode_flops(D, 1000) - A.decode_flops(D, 1) == 9 * attn * 999


SPEC = {"configs": [{"name": "tiny-dsv2-1c",
                     "file": "bench/tests/data/tiny-dsv2-1c.json"}],
        "workloads": [{"name": "tiny.cell", "config": "tiny-dsv2-1c",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": [], "per_layer": []}


def _run(hook=None, control=False):
    return R.run("tiny.cell", SEED, SECONDS, False, require_tpu=False,
                 spec=SPEC, mix=_load("tiny-closed"), engine_hook=hook,
                 control=control)


def _failed_gap_checks(res):
    conf = _load("tiny-dsv2-1c")
    return [n for n in conf["limits"] if n in R.gap_stats(np.zeros(1))
            and res["checks"][n]["value"] > res["checks"][n]["limit"]]


def test_program_agrees_with_the_reference():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["served"]["checked_slots"] == 8
    assert res["checks"]["served_tokens"]["value"] >= 100
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["dropped_tokens"]["value"] == 0


def test_fp8_control_is_not_correct():
    res = _run(control=True)
    assert not res["correct"]
    assert _failed_gap_checks(res), res["checks"]
    assert res["readings"]["program_mismatch_share"] \
        < res["readings"]["mismatch_share"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    res = _run(hook=faults.FAULTS[fault])
    assert not res["correct"]
    assert _failed_gap_checks(res), res["checks"]
