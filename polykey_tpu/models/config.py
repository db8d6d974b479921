"""Model architecture configs + registry.

Covers the three served families from BASELINE.json's measurement configs
(Llama-3-8B, Mixtral-8x7B, Gemma-2-27B) plus scaled-down variants of each for
CPU tests and single-chip experiments. Hyperparameters follow the public
model cards / HF config.json values.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    activation: str = "silu"            # "gelu_tanh" for gemma
    # Gemma-2 specifics
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None      # even layers use the window
    query_pre_attn_scalar: Optional[float] = None
    use_post_norms: bool = False              # post-attn/post-mlp RMSNorms
    scale_embeddings: bool = False            # multiply embeds by sqrt(hidden)
    # A looped stack (the homogeneous block only): the SAME `num_layers`
    # layers run `loop_steps` times a token, the final norm closing every
    # pass and feeding the next; pass u of layer l owns cache layer
    # u · num_layers + l. An exit gate (one Linear(hidden, 1) with bias,
    # sigmoid) on each pass's normed output gives λ_u; a position leaves at
    # the first pass whose cumulative exit probability (λ_u Π_{j<u}(1 − λ_j),
    # the last pass taking the rest) reaches `early_exit_threshold`, else
    # at the last, and the head reads that pass's output. Every pass always
    # runs: later tokens need its K/V. 1: one pass, no gate.
    loop_steps: int = 1
    early_exit_threshold: float = 1.0
    # MoE (Mixtral) specifics
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # Capacity-bucketed sparse dispatch (ops/moe.py: moe_mlp_dispatch) instead
    # of the einsum-dense formulation. On for real MoE sizes — dense pays
    # num_experts/top_k x the dispatch FLOPs; off for tiny test configs,
    # where dispatch's token-drop-on-overflow would perturb exactness checks.
    moe_dispatch: bool = False
    # Hybrid stacks (models/hybrid.py): one character an entry, and an
    # entry is an operator OR a feed-forward part alone, under one norm —
    # "M" a Mamba-2 mixer, "C" a gated short convolution, "L" a gated
    # delta-rule linear attention, "*" attention, "A" latent attention,
    # "E" an expert layer, "D" a dense gated MLP. A published layer that
    # holds an operator AND a feed-forward part under two norms is two
    # entries ("CD", "*E"), and `num_layers` counts entries.
    # Empty: the homogeneous attention+MLP block above, scanned.
    layer_pattern: str = ""
    use_rope: bool = True                     # False: no position embedding
    # "*": an RMSNorm on q and on k before the position embedding. Its
    # span is `qk_norm_span`: "head", over each head's head_dim (one gain
    # of head_dim, shared by the heads); "projection", ONE norm over the
    # whole projection before it is split into heads (the mean square over
    # all heads · head_dim columns, one gain a column).
    qk_norm: bool = False
    qk_norm_span: str = "head"
    # "*": the leading share of each head's dims that the rotary embedding
    # turns (rotate-half inside it); the rest pass.
    partial_rotary_factor: float = 1.0
    # "*": W_q yields a gate beside each head's query ([.., heads, 2 D],
    # query then gate); the context is multiplied by sigmoid(gate) before
    # W_o.
    attn_output_gate: bool = False
    # Every RMSNorm of a pattern but the delta body's gated one: the gain
    # is `norm_offset + w` (1.0: the zero-centred norm, w starts at 0).
    norm_offset: float = 0.0
    # The norms of an entry. `pre_norm`: the body reads Norm(x), else x as
    # it comes. `sandwich_norm`: an RMSNorm with a gain of its own on the
    # body's OUTPUT before the residual add. Pre alone (the default):
    # x ← x + f(Norm(x)); both: x ← x + Norm_post(f(Norm(x))); post alone
    # (`pre_norm` off, `sandwich_norm` on): x ← x + Norm_post(f(x)). An
    # entry with neither is refused.
    pre_norm: bool = True
    sandwich_norm: bool = False
    # "A" (latent attention, MLA): the query through a rank-`q_lora_rank`
    # bottleneck with a norm, heads of `qk_nope_head_dim` + 
    # `qk_rope_head_dim` (the rotary embedding turns the second part);
    # what a token leaves in the cache is ONE row a layer, its normed
    # rank-`kv_lora_rank` latent beside ONE rotary key of
    # `qk_rope_head_dim` shared by all heads; a head's key is its own
    # expansion of the latent beside that key, its value an expansion of
    # `v_head_dim`.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # "M": H heads x P dims, state [H, P, N] per sequence, G groups share
    # B and C, a causal depthwise conv of `conv_kernel` taps over x|B|C.
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 0
    conv_kernel: int = 0
    ssm_chunk: int = 128                      # prefill's chunked form
    # "L": `delta_key_heads` q and k heads of `delta_key_dim`, each read by
    # `delta_value_heads` ÷ `delta_key_heads` value heads of
    # `delta_value_dim`; a matrix S [key dim, value dim] a value head and
    # sequence, stored `delta_heads_per_row` heads side by side
    # (engine/kv_cache.py SlotState); a causal depthwise conv of
    # `conv_kernel` taps over q|k|v; β = `delta_beta_scale` · sigmoid(b):
    # 1, β in (0, 1); 2, β in (0, 2) — the transition e^g (I − β k̃k̃ᵀ)
    # then has a NEGATIVE eigenvalue along k̃ where β > 1; prefill in
    # chunks of `delta_chunk`.
    delta_key_heads: int = 0
    delta_value_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_beta_scale: float = 1.0
    delta_chunk: int = 64
    # "C": [B | C | u] = W_in h, a causal depthwise conv of `conv_kernel`
    # taps over B ⊙ u (no bias, no activation), W_out (C ⊙ conv): what a
    # sequence carries is the conv's last K−1 columns, nothing else.
    # "D": act(h W_gate) ⊙ h W_up through W_down at this width.
    dense_intermediate_size: int = 0
    # "E": a router over `n_routed_experts` — `router_scoring` "sigmoid":
    # the top `num_experts_per_tok` by score + bias; "softmax": over all
    # of them, the top by score, no bias — each chosen expert weighed by
    # its score over the sum of the chosen (+ `router_norm_eps`), experts
    # of `intermediate_size`. Two forms (ops/moe.py `moe_held`):
    # `moe_latent_size` > 0, un-gated relu² experts inside a latent with
    # one shared expert on the full hidden; 0, gated experts
    # (act(h W_gate,e) ⊙ h W_up,e) W_down,e on the full hidden, beside
    # them a gated shared expert of `moe_shared_intermediate` where that
    # is set, weighed by sigmoid(w_s · h) where `shared_expert_gate` says
    # so. The chip holds experts [first_expert, first_expert +
    # experts_held) and computes their part of the sum.
    n_routed_experts: int = 0
    experts_held: int = 0
    first_expert: int = 0
    moe_latent_size: int = 0
    moe_shared_intermediate: int = 0
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 0.0
    router_scoring: str = "sigmoid"
    shared_expert_gate: bool = False

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def stateful(self) -> bool:
        """Holds per-slot state beside the K/V pages (kv_cache.SlotState)."""
        return bool(self.state_held)

    @property
    def state_held(self) -> str:
        """What a slot holds beside its pages, in words; "" for nothing."""
        if "M" in self.layer_pattern:
            return "Mamba-2 h and conv columns"
        if "L" in self.layer_pattern:
            return "delta-rule S and conv columns"
        if "C" in self.layer_pattern:
            return "short-conv columns"
        return ""

    @property
    def kv_layers(self) -> int:
        """Layers that own a layer of the page pool: every pass of a
        looped stack has a cache of its own."""
        if self.layer_pattern:
            return (self.layer_pattern.count("*")
                    + self.layer_pattern.count("A"))
        return self.num_layers * self.loop_steps

    @property
    def latent_kv(self) -> bool:
        """The pool holds latent rows, not K and V ("A" layers)."""
        return "A" in self.layer_pattern

    @property
    def kv_parts(self) -> int:
        """Entries a page of the pool holds: K and V, or one latent row a
        token."""
        return 1 if self.latent_kv else 2

    @property
    def latent_width(self) -> int:
        """A latent row as published: the latent beside the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_row_width(self) -> int:
        """Columns of a row of a page's part: the K (or V) heads folded, or
        a latent row padded with zero columns to whole 128-lane tiles (the
        TPU lays a narrower row out that wide in HBM anyway, and the
        kernels' DMAs take whole tiles)."""
        if self.latent_kv:
            return -(-self.latent_width // 128) * 128
        return self.num_kv_heads * self.head_dim

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x | B | C."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def delta_conv_dim(self) -> int:
        """Channels the delta body's conv runs over: q | k | v."""
        return (2 * self.delta_key_heads * self.delta_key_dim
                + self.delta_value_heads * self.delta_value_dim)

    @property
    def delta_heads_per_row(self) -> int:
        """Value heads whose S lie side by side in one row of the stored
        delta-rule state, [value heads ÷ this, key dim, this · value dim]:
        the fewest that make the row whole 128-lane tiles (a value dim of
        192 alone is laid out 256 wide on the TPU, in HBM and in VMEM: a
        third more bytes moved than held; two heads are 384, three tiles),
        where that many divide the heads; else 1, the heads apart."""
        heads, dim = self.delta_value_heads, self.delta_value_dim
        for n in range(1, heads + 1):
            if heads % n == 0 and n * dim % 128 == 0:
                return n
        return 1

    @property
    def rotary_dim(self) -> int:
        """The leading dims of a head that the rotary embedding turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    def __post_init__(self):
        if self.loop_steps < 1:
            raise ValueError(
                f"loop_steps {self.loop_steps} must be >= 1 (the stack's "
                "passes a token)"
            )
        if self.loop_steps > 1 and self.layer_pattern:
            raise ValueError(
                f"loop_steps {self.loop_steps} with layer_pattern "
                f"{self.layer_pattern!r}: the unrolled walk of a pattern "
                "(models/hybrid.py run_stack) has no loop over the stack; "
                "only the homogeneous scanned block runs its layers again"
            )
        if self.loop_steps > 1 and self.sliding_window is not None:
            raise ValueError(
                f"loop_steps {self.loop_steps} with sliding_window: the "
                "window interleaving goes by the cache layer's index, "
                "which a later pass offsets"
            )
        if not self.layer_pattern:
            return
        if len(self.layer_pattern) != self.num_layers or \
                set(self.layer_pattern) - set("MCL*AED"):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r} must be num_layers="
                f"{self.num_layers} characters of 'M', 'C', 'L', '*', 'A', "
                f"'E', 'D'"
            )
        if "A" in self.layer_pattern and "*" in self.layer_pattern:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r} mixes 'A' and '*': "
                "the page pool has one geometry, latent rows or K and V"
            )
        if not (self.pre_norm or self.sandwich_norm):
            raise ValueError(
                "an entry needs a norm: pre_norm, sandwich_norm (the norm "
                "on the body's output) or both"
            )
        if self.qk_norm_span not in ("head", "projection"):
            raise ValueError(
                f"qk_norm_span {self.qk_norm_span!r} must be 'head' or "
                "'projection'"
            )
        if self.qk_norm_span == "projection" and (
                not self.qk_norm or self.attn_output_gate):
            raise ValueError(
                "qk_norm_span 'projection' is a q/k norm over W_q's whole "
                "output: it needs qk_norm, and cannot be taken where "
                "attn_output_gate interleaves a gate with each head's query"
            )
        if self.delta_beta_scale not in (1.0, 2.0):
            raise ValueError(
                f"delta_beta_scale {self.delta_beta_scale} must be 1 (β in "
                "(0, 1)) or 2 (β in (0, 2): negative eigenvalues allowed)"
            )
        if "E" in self.layer_pattern and not (
            0 < self.experts_held
            and self.first_expert + self.experts_held <= self.n_routed_experts
        ):
            raise ValueError(
                f"experts held [{self.first_expert}, "
                f"{self.first_expert + self.experts_held}) must lie inside "
                f"the router's {self.n_routed_experts}"
            )

    @property
    def q_scale(self) -> float:
        if self.query_pre_attn_scalar is not None:
            return self.query_pre_attn_scalar**-0.5
        if self.latent_kv:
            return (self.qk_nope_head_dim + self.qk_rope_head_dim)**-0.5
        return self.head_dim**-0.5

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        embed = self.vocab_size * self.hidden_size
        if self.layer_pattern:
            h = self.hidden_size
            if self.moe_latent_size:
                experts = (2 * h * self.moe_latent_size
                           + 2 * h * self.moe_shared_intermediate
                           + self.experts_held * 2 * self.moe_latent_size
                           * self.intermediate_size)
            else:
                experts = (self.experts_held * 3 * h * self.intermediate_size
                           + 3 * h * self.moe_shared_intermediate
                           + h * self.shared_expert_gate)
            values = self.delta_value_heads * self.delta_value_dim
            kinds = {
                "M": h * (2 * self.mamba_inner
                          + 2 * self.ssm_groups * self.ssm_state_size
                          + self.mamba_num_heads)
                + self.mamba_inner * h + self.conv_dim * self.conv_kernel,
                "C": 4 * h * h + h * self.conv_kernel,
                "L": h * (self.delta_conv_dim + values
                          + 2 * self.delta_value_heads)
                + values * h + self.delta_conv_dim * self.conv_kernel,
                "*": h * self.head_dim * (
                    (3 if self.attn_output_gate else 2) * self.num_heads
                    + 2 * self.num_kv_heads),
                "A": h * (self.q_lora_rank + self.latent_width)
                + self.num_heads * (
                    self.q_lora_rank
                    * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                    + self.kv_lora_rank
                    * (self.qk_nope_head_dim + self.v_head_dim)
                    + self.v_head_dim * h),
                "E": h * self.n_routed_experts + experts,
                "D": 3 * h * self.dense_intermediate_size,
            }
            tables = 1 if self.tie_embeddings else 2
            return tables * embed + sum(kinds[k] for k in self.layer_pattern)
        attn = self.hidden_size * self.head_dim * (
            self.num_heads * 2 + self.num_kv_heads * 2
        )
        if self.is_moe:
            mlp = 3 * self.hidden_size * self.intermediate_size * self.num_experts
            mlp += self.hidden_size * self.num_experts  # router
        else:
            mlp = 3 * self.hidden_size * self.intermediate_size
        norms = self.hidden_size * (4 if self.use_post_norms else 2)
        block = attn + mlp + norms
        head = 0 if self.tie_embeddings else embed
        # A looped stack holds its layers ONCE; the exit gate's w and b.
        gate = self.hidden_size + 1 if self.loop_steps > 1 else 0
        return embed + self.num_layers * block + self.hidden_size + head + gate

    def num_active_params(self) -> int:
        """Parameters touched per token: for MoE, only the router plus the
        top-k routed experts count (roofline math — per-token FLOPs scale
        with active params, not total); of a pattern's held experts, the
        most a token can choose."""
        if self.layer_pattern:
            width = self.moe_latent_size or self.hidden_size
            matrices = 2 if self.moe_latent_size else 3
            idle = max(self.experts_held - self.num_experts_per_tok, 0)
            return self.num_params() - (
                self.layer_pattern.count("E") * idle * matrices * width
                * self.intermediate_size)
        if not self.is_moe:
            return self.num_params()
        return self.num_params() - (
            self.num_layers * 3 * self.hidden_size * self.intermediate_size
            * (self.num_experts - self.num_experts_per_tok))


LLAMA3_8B = ModelConfig(
    name="llama-3-8b",
    vocab_size=128_256,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=8192,
    rope_theta=500_000.0,
)

LLAMA3_70B = ModelConfig(
    name="llama-3-70b",
    vocab_size=128_256,
    hidden_size=8192,
    intermediate_size=28_672,
    num_layers=80,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=8192,
    rope_theta=500_000.0,
)

LLAMA32_1B = ModelConfig(
    name="llama-3.2-1b",
    vocab_size=128_256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    max_seq_len=8192,
    rope_theta=500_000.0,
    tie_embeddings=True,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32_000,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=8192,
    rope_theta=1_000_000.0,
    num_experts=8,
    num_experts_per_tok=2,
    moe_dispatch=True,
)

GEMMA2_27B = ModelConfig(
    name="gemma-2-27b",
    vocab_size=256_128,
    hidden_size=4608,
    intermediate_size=36_864,
    num_layers=46,
    num_heads=32,
    num_kv_heads=16,
    head_dim=128,
    max_seq_len=8192,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_embeddings=True,
    activation="gelu_tanh",
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    sliding_window=4096,
    query_pre_attn_scalar=144.0,  # hidden_size / num_heads
    use_post_norms=True,
    scale_embeddings=True,
)

GEMMA2_2B = ModelConfig(
    # The family's small member (HF gemma-2-2b config.json values) — the
    # natural speculative DRAFT for gemma-2-9b/27b (same 256k vocab).
    name="gemma-2-2b",
    vocab_size=256_128,
    hidden_size=2304,
    intermediate_size=9216,
    num_layers=26,
    num_heads=8,
    num_kv_heads=4,
    head_dim=256,
    max_seq_len=8192,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_embeddings=True,
    activation="gelu_tanh",
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    sliding_window=4096,
    query_pre_attn_scalar=256.0,
    use_post_norms=True,
    scale_embeddings=True,
)

GEMMA2_9B = ModelConfig(
    name="gemma-2-9b",
    vocab_size=256_128,
    hidden_size=3584,
    intermediate_size=14_336,
    num_layers=42,
    num_heads=16,
    num_kv_heads=8,
    head_dim=256,
    max_seq_len=8192,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_embeddings=True,
    activation="gelu_tanh",
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    sliding_window=4096,
    query_pre_attn_scalar=256.0,
    use_post_norms=True,
    scale_embeddings=True,
)

# Scaled-down variants: same architectural features, CPU-testable sizes.
TINY_LLAMA = ModelConfig(
    name="tiny-llama",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_seq_len=128,
    rope_theta=10_000.0,
)

TINY_MIXTRAL = replace(
    TINY_LLAMA,
    name="tiny-mixtral",
    num_experts=4,
    num_experts_per_tok=2,
)

TINY_GEMMA = replace(
    TINY_LLAMA,
    name="tiny-gemma",
    tie_embeddings=True,
    activation="gelu_tanh",
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    sliding_window=16,
    query_pre_attn_scalar=16.0,
    use_post_norms=True,
    scale_embeddings=True,
)

# A hybrid stack at toy size: every kind of layer, two windows of state
# chunks per 16-token bucket, half the routed experts held.
TINY_HYBRID = ModelConfig(
    name="tiny-hybrid",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=32,
    num_layers=5,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_seq_len=512,
    activation="relu2",
    layer_pattern="MEM*E",
    use_rope=False,
    mamba_num_heads=8,
    mamba_head_dim=16,
    ssm_state_size=16,
    ssm_groups=2,
    conv_kernel=4,
    ssm_chunk=8,
    n_routed_experts=16,
    experts_held=8,
    num_experts_per_tok=4,
    moe_latent_size=32,
    moe_shared_intermediate=48,
    routed_scaling_factor=2.5,
)

# An operator + feed-forward pattern at toy size: both leading dense
# entries, then two periods of conv, conv, attention, conv over gated
# experts on the full hidden, all held; conv-only state, q/k norms, RoPE,
# one tied matrix.
TINY_LFM2 = ModelConfig(
    name="tiny-lfm2",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=32,
    num_layers=20,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_seq_len=512,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    layer_pattern="CDCD*ECECECE*ECECECE",
    qk_norm=True,
    conv_kernel=3,
    dense_intermediate_size=96,
    n_routed_experts=16,
    experts_held=16,
    num_experts_per_tok=4,
    router_norm_eps=1e-6,
)

# A gated delta-rule / gated-attention pattern at toy size: two periods of
# three linear-attention layers and one attending layer, each over softmax-
# routed gated experts with a gated shared expert; 2 key heads under 4
# value heads (so the repeat is exercised), key and value widths that
# differ, rotary on a quarter of the head, zero-centred norms, all 16
# experts held.
TINY_QWEN3_NEXT = ModelConfig(
    name="tiny-qwen3-next",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=32,
    num_layers=16,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_seq_len=512,
    rope_theta=10_000_000.0,
    rms_norm_eps=1e-6,
    layer_pattern="LELELE*E" * 2,
    qk_norm=True,
    partial_rotary_factor=0.25,
    attn_output_gate=True,
    norm_offset=1.0,
    delta_key_heads=2,
    delta_value_heads=4,
    delta_key_dim=8,
    delta_value_dim=16,
    delta_chunk=8,
    conv_kernel=4,
    n_routed_experts=16,
    experts_held=16,
    num_experts_per_tok=4,
    moe_shared_intermediate=32,
    router_scoring="softmax",
    shared_expert_gate=True,
)

# A latent-attention (MLA) pattern at toy size: a leading dense layer, then
# latent attention over sigmoid-routed gated experts with a plain shared
# expert, half the routed experts held, a post-norm on every body.
TINY_PANGU = ModelConfig(
    name="tiny-pangu",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=32,
    num_layers=6,
    num_heads=4,
    num_kv_heads=1,
    head_dim=24,
    max_seq_len=512,
    rope_theta=25_600_000.0,
    layer_pattern="ADAEAE",
    sandwich_norm=True,
    q_lora_rank=48,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    dense_intermediate_size=96,
    n_routed_experts=16,
    experts_held=8,
    num_experts_per_tok=4,
    moe_shared_intermediate=32,
    routed_scaling_factor=2.5,
)

# A gated delta-rule / NoPE attention pattern at toy size: two periods of
# three linear-attention layers and one attending layer, each over a dense
# gated MLP; every body post-normed and none pre-normed; β in (0, 2); as
# many key heads as value heads, of widths that differ and are no power of
# two (two heads' 192-wide S side by side in a 384-wide row of the stored
# state); full multi-head attention without a position embedding, q and k
# normed over the whole projection.
TINY_OLMO_HYBRID = ModelConfig(
    name="tiny-olmo-hybrid",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=96,
    num_layers=16,
    num_heads=4,
    num_kv_heads=4,
    head_dim=16,
    max_seq_len=512,
    rms_norm_eps=1e-6,
    layer_pattern="LDLDLD*D" * 2,
    use_rope=False,
    qk_norm=True,
    qk_norm_span="projection",
    pre_norm=False,
    sandwich_norm=True,
    delta_key_heads=6,
    delta_value_heads=6,
    delta_key_dim=24,
    delta_value_dim=192,
    delta_beta_scale=2.0,
    delta_chunk=8,
    conv_kernel=4,
    dense_intermediate_size=96,
)

# A looped stack at toy size: three sandwich-normed multi-head layers run
# twice a token with the same weights (six cache layers), an exit gate.
TINY_OURO = ModelConfig(
    name="tiny-ouro",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=3,
    num_heads=4,
    num_kv_heads=4,
    head_dim=16,
    max_seq_len=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    use_post_norms=True,
    loop_steps=2,
)

# A mid-size llama for single-chip benchmarking without 8B's 16 GiB of bf16
# weights (v5e has 16 GiB HBM; 8B serves in int8 — see engine docs).
LLAMA_1B_BENCH = replace(LLAMA32_1B, name="llama-1b-bench")

# Mixtral ARCHITECTURE (8 experts, top-2, dispatch routing) scaled to
# ~4.7 B params so the int8 tree (~4.7 GiB) + KV fits one v5e chip:
# hardware evidence for measurement config 4's mechanism (MoE routing +
# grouped expert matmuls) without 8x7B's 47 B params, which need tp>=4.
MIXTRAL_BENCH = replace(
    MIXTRAL_8X7B,
    name="mixtral-bench",
    hidden_size=2048,
    intermediate_size=5632,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
)

MODEL_REGISTRY = {
    cfg.name: cfg
    for cfg in (
        LLAMA3_8B,
        LLAMA3_70B,
        LLAMA32_1B,
        MIXTRAL_8X7B,
        GEMMA2_27B,
        GEMMA2_9B,
        GEMMA2_2B,
        TINY_LLAMA,
        TINY_MIXTRAL,
        TINY_GEMMA,
        TINY_HYBRID,
        TINY_LFM2,
        TINY_QWEN3_NEXT,
        TINY_PANGU,
        TINY_OLMO_HYBRID,
        TINY_OURO,
        LLAMA_1B_BENCH,
        MIXTRAL_BENCH,
    )
}


def get_config(name: str) -> ModelConfig:
    try:
        return MODEL_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}"
        ) from None
