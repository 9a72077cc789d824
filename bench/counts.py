"""Operations and bytes the model's work requires, from its shapes.

Counts are of useful work only: a prompt's real tokens (no bucket
padding), the top-k experts a token is routed to (no capacity padding),
attention over the context each token actually has. Bytes are bf16
weights and KV entries. ``D`` is ``weights.dims(conf)``.
"""

from __future__ import annotations

BF16 = 2


def expert_bytes(D) -> int:
    """One expert's SwiGLU weights (gate, up, down)."""
    return 3 * D["d"] * D["F"] * BF16


def attn_weight_bytes(D) -> int:
    """One layer's q, k, v and output projections."""
    d, H, K, hd = D["d"], D["H"], D["K"], D["hd"]
    return (2 * d * H * hd + 2 * d * K * hd) * BF16


def layer_dense_bytes(D) -> int:
    """One layer's weights outside its experts: attention, router, norms."""
    return attn_weight_bytes(D) + (D["d"] * D["E"] + 2 * D["d"]) * BF16


def head_bytes(D) -> int:
    """LM head and final norm."""
    return (D["d"] * D["V"] + D["d"]) * BF16


def kv_bytes_per_token(D) -> int:
    """K and V of one position in every layer."""
    return 2 * D["K"] * D["hd"] * BF16 * D["L"]


def kv_read_bytes(D, context: int, block: int) -> int:
    """KV a paged decode must read for one sequence: its live blocks."""
    blocks = -(-context // block)
    return blocks * block * kv_bytes_per_token(D)


def body_flops(D) -> int:
    """Per token and over all layers, everything but attention scores:
    projections, router and the top-k experts."""
    d, H, K, hd, F = D["d"], D["H"], D["K"], D["hd"], D["F"]
    proj = 2 * (2 * d * H * hd + 2 * d * K * hd)
    router = 2 * d * D["E"]
    experts = D["top_k"] * 3 * 2 * d * F
    return D["L"] * (proj + router + experts)


def head_flops(D) -> int:
    return 2 * D["d"] * D["V"]


def token_flops(D) -> int:
    """A decoded token, but for attention scores: body and LM head."""
    return body_flops(D) + head_flops(D)


def attn_flops(D, attended: int) -> int:
    """QK and PV products over ``attended`` (query, key) pairs per layer."""
    return D["L"] * 2 * 2 * D["H"] * D["hd"] * attended


def prefill_flops(D, prompt: int) -> int:
    """One prompt: every real token through the body, causal attention,
    the LM head at the last position only."""
    return (prompt * body_flops(D) + head_flops(D)
            + attn_flops(D, prompt * (prompt + 1) // 2))


def decode_flops(D, context: int) -> int:
    """One decoded token attending over ``context`` positions (itself
    included)."""
    return token_flops(D) + attn_flops(D, context)
