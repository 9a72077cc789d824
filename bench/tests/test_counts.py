"""FLOP and byte counts against hand arithmetic for the served cut."""

import json
import os

import counts
import weights as W

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mixtral():
    with open(os.path.join(BENCH, "configs", "mixtral-8x7b-1c.json")) as f:
        return W.dims(json.load(f))


def test_expert_bytes_per_layer():
    D = mixtral()
    # 8 experts x (gate + up + down) x 4096 x 14336 x 2 bytes
    assert D["E"] * counts.expert_bytes(D) == 2_818_572_288


def test_weight_bytes_of_the_cut():
    D = mixtral()
    # q, o: 4096 x 4096; k, v: 4096 x 1024; bf16
    assert counts.attn_weight_bytes(D) == 83_886_080
    total = (D["L"] * (D["E"] * counts.expert_bytes(D)
                       + counts.layer_dense_bytes(D))
             + counts.head_bytes(D) + D["V"] * D["d"] * 2)
    assert abs(total / 1e9 - 12.13) < 0.01          # 12.13 GB of weights


def test_top2_flops_per_token():
    D = mixtral()
    per_layer = (2 * (2 * 4096 * 4096 + 2 * 4096 * 1024)    # projections
                 + 2 * 4096 * 8                              # router
                 + 2 * 3 * 2 * 4096 * 14336)                 # two experts
    head = 2 * 4096 * 32000
    assert counts.token_flops(D) == 4 * per_layer + head == 3_416_522_752


def test_attention_and_kv_at_context():
    D = mixtral()
    assert counts.kv_bytes_per_token(D) == 16 * 1024        # 16 KiB
    # a decode at context 1000 reads 63 blocks of 16 positions
    assert counts.kv_read_bytes(D, 1000, 16) == 63 * 16 * 16 * 1024
    qk_pv = 4 * 2 * 2 * 32 * 128 * 1000
    assert counts.decode_flops(D, 1000) == 3_416_522_752 + qk_pv
    p = 512
    body = counts.token_flops(D) - 2 * 4096 * 32000
    assert counts.prefill_flops(D, p) == (p * body + 2 * 4096 * 32000
                                          + 4 * 2 * 2 * 32 * 128
                                          * p * (p + 1) // 2)
