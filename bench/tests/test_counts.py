"""FLOP and byte counts of the Mixtral architecture module
(``bench/arch/mixtral.py``) against hand arithmetic for the served cut."""

import json
import os

import counts as C
import run as R

with open(os.path.join(R.BENCH, "configs", "mixtral-8x7b-1c.json")) as f:
    CONF = json.load(f)
M = R.load_arch(CONF)


def mixtral():
    return M.dims(CONF)


def test_expert_bytes_per_layer():
    D = mixtral()
    # 8 experts x (gate + up + down) x 4096 x 14336 x 2 bytes
    assert D["E"] * M.expert_bytes(D) == 2_818_572_288


def test_weight_bytes_of_the_cut():
    D = mixtral()
    # q, o: 4096 x 4096; k, v: 4096 x 1024; bf16
    assert M.attn_weight_bytes(D) == 83_886_080
    total = (D["L"] * (D["E"] * M.expert_bytes(D) + M.layer_dense_bytes(D))
             + C.head_bytes(D) + D["V"] * D["d"] * 2)
    assert abs(total / 1e9 - 12.13) < 0.01          # 12.13 GB of weights


def test_fixed_bytes_are_the_layers_outside_their_experts_and_the_head():
    D = mixtral()
    assert M.fixed_bytes(D) == D["L"] * M.layer_dense_bytes(D) \
        + C.head_bytes(D)
    # 4 x (attention 83,886,080 + router 4096 x 8 x 2 + two norms
    # 2 x 4096 x 2) + head (4096 x 32000 + 4096) x 2
    assert M.fixed_bytes(D) == 4 * (83_886_080 + 65_536 + 16_384) \
        + 262_152_192 == 598_024_192


def test_top2_flops_per_token():
    D = mixtral()
    per_layer = (2 * (2 * 4096 * 4096 + 2 * 4096 * 1024)    # projections
                 + 2 * 4096 * 8                              # router
                 + 2 * 3 * 2 * 4096 * 14336)                 # two experts
    head = 2 * 4096 * 32000
    assert M.token_flops(D) == 4 * per_layer + head == 3_416_522_752


def test_attention_and_kv_at_context():
    D = mixtral()
    assert M.kv_bytes_per_token(D) == 16 * 1024        # 16 KiB
    # a decode at context 1000 reads 63 blocks of 16 positions
    assert M.kv_read_bytes(D, 1000, 16) == 63 * 16 * 16 * 1024
    qk_pv = 4 * 2 * 2 * 32 * 128 * 1000
    assert M.decode_flops(D, 1000) == 3_416_522_752 + qk_pv
    p = 512
    body = M.token_flops(D) - 2 * 4096 * 32000
    assert M.prefill_flops(D, p) == (p * body + 2 * 4096 * 32000
                                     + 4 * 2 * 2 * 32 * 128
                                     * p * (p + 1) // 2)
