"""Config system for the repro framework.

Every architecture in the assigned pool is expressed as a ``ModelConfig``.
Configs are plain frozen dataclasses (hashable -> usable as jit static args).

``reduced()`` derives the CPU-smoke variant (<=2 layers, d_model<=512,
<=4 experts) of the same family, used by tests; full configs are only ever
lowered via ShapeDtypeStruct in the dry-run.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0          # always-on experts (deepseek-style)
    # routed gates: the top-k softmax probabilities, renormalised to sum to
    # one over the k (Mixtral) or left as they are (DeepSeek-V2)
    norm_topk_prob: bool = True
    dense_residual: bool = False         # arctic: dense FFN in parallel with MoE
    d_ff_dense: int = 0                  # width of the dense residual branch
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    router_z_loss: float = 1e-3
    # Paper technique knobs -------------------------------------------------
    max_copies: int = 4                  # Algorithm 1 C_max
    duplication_slots: int = 0           # extra expert slots per EP rank (0 = E/ranks)
    # Dispatch hot path -----------------------------------------------------
    # "sort": argsort + cumsum-offset send-buffer packing (fast path);
    # "onehot": (N, S) one-hot cumsum + scatter (reference oracle).
    # Both produce bit-identical send buffers, stats and drop decisions.
    dispatch_impl: str = "sort"
    # Replica weight movement -----------------------------------------------
    # "store": engines keep persistent per-rank slot-weight buffers
    # (repro.runtime.ReplicaStore) and move weights only when the plan
    # changes; "gather": per-step all_gather replica pool (bit-exact
    # oracle, and the fallback whenever no store is threaded in).
    replica_impl: str = "store"
    # Overlapped (async-prefetch) migration: plan-diff fills are staged
    # per layer and issued during the forward pass instead of between
    # engine steps — forward() selects old-plan slots per layer until
    # that layer's fill commits (repro.runtime.LayerStagedExecutor).
    # False restores the synchronous drain-at-replan path.
    overlap_migration: bool = True
    # Token rescheduling (repro.schedule) ------------------------------------
    # Capacity fraction of the rescue round: tokens that overflow their
    # round-1 slot are re-dispatched to an alternate copy through a second,
    # smaller all-to-all with per-slot capacity
    # ``max(8, cap * resched_cap_frac)``. Only active when a reschedule
    # quota tensor is threaded into dispatch (lever = reschedule/both).
    resched_cap_frac: float = 0.5
    # Per-rank HBM budget (GB) for the replica store (which holds a second
    # copy of the home experts plus the replica slots). 0 = unlimited;
    # otherwise engines clamp duplication_slots down until the store fits
    # (core.placement.clamp_dup_slots) so the prefetcher cannot
    # over-replicate past device memory.
    store_hbm_budget_gb: float = 0.0


@dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0                 # 0 = full-rank Q projection
    rope_head_dim: int = 64              # decoupled RoPE dims per head
    v_head_dim: int = 128
    nope_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling``, type "yarn")."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (seamless-m4t) architectures."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    max_source_len: int = 4096


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                          # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                    # 0 -> d_model // num_heads
    # attention ------------------------------------------------------------
    attention: str = "gqa"               # gqa | mla | none (ssm) | mixed (hybrid)
    qkv_bias: bool = False
    sliding_window: int = 0              # 0 = full attention
    rope_theta: float = 10000.0
    yarn: Optional[YarnConfig] = None    # YaRN-scaled rotary frequencies
    # Paged decode attention ------------------------------------------------
    # "fused": single Pallas pass walks block_tables and computes GQA
    # attention with an online-softmax accumulator straight from the shared
    # KV pool (interpret=True on CPU/test meshes); "gather": materialize
    # the (B, M*bs, K, hd) logical view first (bit-exact oracle — same
    # blockwise op sequence, so fp32 matches the kernel exactly).
    paged_attn_impl: str = "fused"
    # norms / activations ----------------------------------------------------
    norm: str = "rmsnorm"                # rmsnorm | nonparametric (olmo)
    activation: str = "swiglu"           # swiglu | gelu | relu | relu2 (rwkv)
    tie_embeddings: bool = False
    # leading layers whose FFN is dense (width ``d_ff``) ahead of the MoE
    # stack (DeepSeek-V2's ``first_k_dense_replace``); their parameters
    # and caches sit apart from the uniform stack they precede
    first_k_dense: int = 0
    # family extensions ------------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    encoder: Optional[EncoderConfig] = None
    # hybrid (recurrentgemma): block pattern repeated over layers
    block_pattern: Tuple[str, ...] = ()  # e.g. ("recurrent","recurrent","local")
    rnn_width: int = 0                   # RG-LRU recurrence width (griffin: ~4/3 d)
    local_window: int = 2048             # local-attention window (hybrid)
    # modality frontends (stubs): tokens | patches (vlm) | frames (audio)
    input_mode: str = "tokens"
    num_prefix_embeddings: int = 0       # patch/frame embeddings prepended
    # training --------------------------------------------------------------
    lr_schedule: str = "cosine"          # cosine | wsd (minicpm)
    # citation for the config ------------------------------------------------
    source: str = ""

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ------------------------------------------------------------------ utils
    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    @property
    def num_moe_layers(self) -> int:
        """Layers of the uniform (scanned) stack after the leading dense
        ones: the leading dim of expert counts, plans and stacked
        layer params."""
        return self.num_layers - self.first_k_dense

    @property
    def is_encdec(self) -> bool:
        return self.encoder is not None

    @property
    def subquadratic(self) -> bool:
        """True if the arch supports O(window)/O(1)-state decode natively."""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def num_params(self) -> int:
        """Analytical parameter count (embedding + blocks + head)."""
        d, L = self.d_model, self.num_layers
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        # attention
        hd = self.head_dim
        if self.attention == "mla" and self.mla is not None:
            m = self.mla
            per_layer += d * m.kv_lora_rank                       # kv down
            per_layer += m.kv_lora_rank * self.num_heads * (m.nope_head_dim + m.v_head_dim)
            per_layer += d * m.rope_head_dim                      # shared k_rope
            qd = m.q_lora_rank or d
            if m.q_lora_rank:
                per_layer += d * m.q_lora_rank
            per_layer += qd * self.num_heads * (m.nope_head_dim + m.rope_head_dim)
            per_layer += self.num_heads * m.v_head_dim * d        # out proj
        elif self.attention in ("gqa", "mixed"):
            per_layer += d * self.num_heads * hd                  # Q
            per_layer += 2 * d * self.num_kv_heads * hd           # K,V
            per_layer += self.num_heads * hd * d                  # O
        elif self.attention == "none" and self.family == "ssm":
            per_layer += 6 * d * d // 2                           # rwkv6 time-mix approx
        # ffn
        if self.moe is not None:
            e = self.moe
            ff_mult = 3 if self.activation == "swiglu" else 2
            per_layer += e.num_experts * ff_mult * d * e.d_ff_expert
            per_layer += e.num_shared_experts * ff_mult * d * e.d_ff_expert
            if e.dense_residual:
                per_layer += ff_mult * d * (e.d_ff_dense or self.d_ff)
            per_layer += d * e.num_experts                        # router
        else:
            ff_mult = 3 if self.activation == "swiglu" else 2
            per_layer += ff_mult * d * self.d_ff
        total = emb + L * per_layer
        if self.moe is not None and self.first_k_dense:
            # leading layers: a dense FFN where the others hold experts
            e = self.moe
            moe_ffn = ((e.num_experts + e.num_shared_experts) * ff_mult * d
                       * e.d_ff_expert + d * e.num_experts)
            if e.dense_residual:
                moe_ffn += ff_mult * d * (e.d_ff_dense or self.d_ff)
            total += self.first_k_dense * (ff_mult * d * self.d_ff - moe_ffn)
        if self.encoder is not None:
            enc = self.encoder
            enc_layer = 4 * enc.d_model * enc.num_heads * (enc.d_model // enc.num_heads)
            enc_layer += ff_mult * enc.d_model * enc.d_ff
            total += enc.num_layers * enc_layer
        return total

    def active_params(self) -> int:
        """Active (per-token) parameter count — MoE counts only top_k experts."""
        if self.moe is None:
            return self.num_params()
        e = self.moe
        ff_mult = 3 if self.activation == "swiglu" else 2
        inactive = (e.num_experts - e.top_k) * ff_mult * self.d_model * e.d_ff_expert
        return self.num_params() - self.num_moe_layers * inactive

    def reduced(self) -> "ModelConfig":
        """CPU-smoke variant: same family/features, tiny dims."""
        changes = dict(
            # a leading dense layer stays, ahead of one layer of the stack
            num_layers=min(self.num_layers, 2),
            first_k_dense=min(self.first_k_dense, 1),
            d_model=min(self.d_model, 256),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, 2),
            head_dim=64,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 1024),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            local_window=min(self.local_window, 32),
            rnn_width=min(self.rnn_width, 256) if self.rnn_width else 0,
            num_prefix_embeddings=min(self.num_prefix_embeddings, 8),
            name=self.name + "-smoke",
        )
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=min(self.moe.d_ff_expert, 256),
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                d_ff_dense=min(self.moe.d_ff_dense, 256) if self.moe.d_ff_dense else 0,
            )
        if self.mla is not None:
            changes["mla"] = dataclasses.replace(
                self.mla, kv_lora_rank=64, rope_head_dim=32,
                nope_head_dim=32, v_head_dim=32)
        if self.encoder is not None:
            changes["encoder"] = dataclasses.replace(
                self.encoder, num_layers=2, d_model=256, num_heads=4,
                num_kv_heads=2, d_ff=512, max_source_len=64)
        if self.block_pattern:
            changes["num_layers"] = len(self.block_pattern)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str        # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}
