"""Operations and bytes that every architecture's counts share.

Counts are of useful work only: a prompt's real tokens (no bucket
padding), the experts a token is routed to (no capacity padding),
attention over the context each token actually has. Bytes are of bf16
weights and cache entries. Each architecture module
(``bench/arch/<model_type>.py``) gives its own ``expert_bytes``,
``fixed_bytes``, ``kv_read_bytes``, ``prefill_flops`` and
``decode_flops`` from these; ``D`` is its ``dims(conf)``.
"""

from __future__ import annotations

BF16 = 2


def head_bytes(D) -> int:
    """LM head and final norm."""
    return (D["d"] * D["V"] + D["d"]) * BF16


def head_flops(D) -> int:
    """The LM head at one position."""
    return 2 * D["d"] * D["V"]


def paged_positions(context: int, block: int) -> int:
    """Positions a paged read of ``context`` covers: its live blocks."""
    return -(-context // block) * block


def causal_pairs(prompt: int) -> int:
    """(query, key) pairs of causal attention over a prompt."""
    return prompt * (prompt + 1) // 2
