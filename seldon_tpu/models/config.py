"""Model configuration for the decoder models this repo serves.

Configs are static dataclasses so every shape is known at trace time —
XLA requirement (no dynamic shapes under jit). One ModelConfig covers
the homogeneous stack (attention + SwiGLU or all-expert MoE in every
layer: the llama / mistral / mixtral presets) and the PATTERNED stack
(`layer_types` set: a per-layer operator kind, short-conv or attention,
leading dense feed-forward layers before the sparse ones, an expert
width of its own, a sigmoid router, QK-norm; attention and a Mamba-2
mixer side by side in one layer, with fixed scalar multipliers on the
projections; or layers that are ONE residual block each: a Mamba-2
mixer, an attention or a sparse feed-forward alone, with a shared expert
and a share of the routed experts held here). Presets: `tiny*` (CPU
tests), `bench-1b` (one v5e chip in bf16), `llama3-8b` / `llama3-70b`
(geometry only; the benchmark's configurations are registered from
benchmark/configs/ by its launcher).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Operator kinds of a patterned stack (the published `layer_types` names).
OP_CONV = "conv"
OP_ATTN = "full_attention"
# Attention over the last `sliding_window` positions only (the query's own
# among them), with a head count, a rotary table and a KV kind of its own:
# a ring of `sliding_window` rows a slot (transformer.cache_spec).
OP_SWA = "sliding_attention"
# Attention and a Mamba-2 mixer reading the same normed input in parallel,
# their outputs summed into the residual, then the dense feed-forward: the
# one kind whose layer holds KV AND an SSM state.
OP_ATTN_MAMBA = "attention_mamba"
# Layers that are one residual block alone (no feed-forward of their
# own): a Mamba-2 mixer, an attention, a sparse feed-forward.
OP_MAMBA = "mamba"
OP_ATTN_ONLY = "attention"
OP_MOE = "moe"
FUSED_OPS = (OP_CONV, OP_ATTN, OP_ATTN_MAMBA, OP_SWA)  # operator + feed-forward in one layer
SINGLE_OPS = (OP_MAMBA, OP_ATTN_ONLY, OP_MOE)
KV_OPS = (OP_ATTN, OP_ATTN_ONLY, OP_ATTN_MAMBA)  # the operators that hold KV
WINDOW_OPS = (OP_SWA,)  # and those whose KV is a ring as long as the window
SSM_OPS = (OP_MAMBA, OP_ATTN_MAMBA)  # and those that hold an SSM state


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (0 experts = dense). Expert-parallel ('ep') only engages when >0.
    n_experts: int = 0
    n_experts_per_token: int = 2
    eos_token_id: int = 128001
    pad_token_id: int = 0
    # "xla" = einsum attention (GSPMD-shardable, default); "flash" = pallas
    # blockwise kernel on the full-sequence path (single-device / tp=1 —
    # pallas ops don't auto-partition under GSPMD); "ring" = exact
    # sequence-parallel attention over the 'sp' mesh axis (long context).
    attn_impl: str = "xla"
    # "bf16" (compute dtype) or "int8": per-(token, head) symmetric
    # quantization of KV slots — halves the cache read per decode step,
    # the serving bottleneck at high slot counts.
    kv_cache_dtype: str = "bf16"
    # "bf16" or "int8": weight-only quantization (per-output-channel
    # scales, models/quantize.py) — halves weight HBM reads and the
    # footprint (llama3-8b on one 16GB v5e chip needs this). Applied by
    # loaders via quantize_params; compute stays bf16.
    weight_dtype: str = "bf16"
    # "bf16" or "int8": MATMUL ACTIVATION dtype (W8A8). With int8 weights,
    # dynamic per-token activation quantization feeds s8 x s8 -> s32
    # matmuls — the v5e MXU runs those at double rate, which matters
    # because decode is COMPUTE-bound past the slot knee (round-5
    # profile, docs/benchmarking.md). Applies to the dense projections
    # (qkv/o, SwiGLU); lm_head/embeddings stay bf16 for logit quality.
    # No-op unless weight_dtype is int8.
    act_dtype: str = "bf16"
    # RoPE frequency scaling (long-context checkpoints). Flat scalar
    # fields rather than a dict so the frozen config stays hashable.
    # rope_scaling_type: None (no scaling), "linear" (inv_freq / factor),
    # "llama3" (HF _compute_llama3_parameters: wavelengths past the
    # original context window are divided by `factor`, with a smooth
    # ramp between the low/high frequency knees) or "yarn" (below, the
    # patterned stack). Llama-3.1/3.2
    # checkpoints declare rope_type=llama3 — ignoring it would produce
    # subtly wrong logits at every position.
    rope_scaling_type: Optional[str] = None
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_position: int = 8192
    # --- patterned stack (empty layer_types = homogeneous, as before) ---
    # Operator of each layer, "conv" (gated short convolution with a
    # fixed-size state per slot), "full_attention" (GQA with KV) or
    # "attention_mamba" (GQA and a Mamba-2 mixer in parallel: KV and an
    # SSM state in the same layer).
    # A list is accepted and stored as a tuple (hashable); asdict() and
    # a JSON round trip give the list back, which is what /metadata
    # serves and the benchmark's harness compares.
    layer_types: Tuple[str, ...] = ()
    # Leading layers whose feed-forward is the dense SwiGLU (width d_ff)
    # even when n_experts > 0; the layers after them are sparse.
    n_dense_layers: int = 0
    # Width of one expert's SwiGLU (0 = d_ff).
    d_ff_expert: int = 0
    # Router of the sparse block: "softmax" (top-k of the logits, softmax
    # over those k: Mixtral) or "sigmoid" (scores = sigmoid(logits),
    # selection on score [+ expert_bias], weights = the unbiased scores
    # at the selected, optionally renormalised, times router_scale).
    router: str = "softmax"
    router_bias: bool = False
    router_norm_topk: bool = True
    router_scale: float = 1.0
    # RMSNorm over head_dim on q and k, before RoPE.
    qk_norm: bool = False
    # Taps of the depthwise causal short convolution; the decode state
    # is the last conv_kernel - 1 inputs per slot and conv layer (a
    # Mamba-2 mixer's convolution over [x | B | C] reads it too).
    conv_kernel: int = 3
    # Width of one attention head (0 = d_model // n_heads, filled in on
    # construction; a model whose heads are wider than that states it).
    head_dim: int = 0
    # Rotary position embedding on q and k (False: none is applied).
    rotary: bool = True
    # --- single-block layers (layer_types of "mamba" / "attention" / "moe") ---
    # Mamba-2 mixer: heads x head width = its inner width (not a multiple
    # of d_model), B and C in ssm_groups groups of ssm_state values; the
    # decode state per slot and layer is [ssm_heads, ssm_head_dim,
    # ssm_state] float32. Prefill is the chunked (SSD) scan at ssm_chunk.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_chunk: int = 128
    # One expert's block: "swiglu" (gate, up, down) or "relu2"
    # (down(relu(up x) ** 2): no gate).
    ff_act: str = "swiglu"
    # Width of the shared expert every token goes through beside the
    # routed ones (0 = none).
    d_ff_shared: int = 0
    # The share of the routed experts this program holds: experts
    # [expert_first, expert_first + n_experts_held) of the n_experts the
    # router scores (0 = all). Assignments to the others go nowhere.
    n_experts_held: int = 0
    expert_first: int = 0
    # Added to the sum of the selected scores before renormalising.
    router_norm_eps: float = 1e-6
    # --- fixed scalar multipliers (muP; 1 / empty = none is applied) ---
    # On the embedding's rows and on the logits; on the attention's input
    # and output and on its keys (before the rotary embedding); on the
    # mixer's input and output; on the five segments of the mixer's input
    # projection, (z, x, B, C, dt); on the dense SwiGLU's gate (inside
    # the activation) and on its output.
    embed_mult: float = 1.0
    logits_mult: float = 1.0
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    ssm_mults: Tuple[float, ...] = ()
    mlp_gate_mult: float = 1.0
    mlp_down_mult: float = 1.0
    # --- attention by kind ("sliding_attention" beside "full_attention") ---
    # A sliding_attention layer's query sees key j from position i when
    # j <= i and i - j < sliding_window; its KV is a ring of that many
    # rows a slot. Its query heads (0 = n_heads; the KV heads are the
    # stack's) and its plain rotary base over the whole head
    # (0 = rope_theta); n_heads, rope_theta, rotary_share and the "yarn"
    # scaling are the full_attention layers'.
    sliding_window: int = 0
    n_heads_window: int = 0
    rope_theta_window: float = 0.0
    # Share of a full_attention head's dims that rotate (the first
    # rotary_share * head_dim, half-split pairing over those; the rest
    # pass through).
    rotary_share: float = 1.0
    # rope_scaling_type "yarn" over the rotated dims: frequencies below
    # the ramp [beta_fast, beta_slow] (rotations within
    # rope_scaling_original_max_position) are divided by
    # rope_scaling_factor, cos and sin are multiplied by
    # rope_attention_factor (0 = 0.1 ln(factor) + 1).
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_attention_factor: float = 0.0
    # sigmoid(h Wa), one value a head from the layer's normed input, on
    # each head's attention output before the output projection.
    attn_gate: bool = False
    # --- generation by diffusion over blocks (0 = autoregressive) ---
    # A slot's decode step is a PASS over the gen_block positions at
    # pos .. pos + gen_block - 1, which attend to the cache and to each
    # other in both directions (prefill is block-causal: position i sees
    # j when j // gen_block <= i // gen_block). An undecided position's
    # input is the embedding of mask_token_id; a denoising pass decides
    # gen_block // denoise_steps of them, the leftmost ("sequential") or
    # the most confident, and every one above denoise_threshold when at
    # least that many are ("low_confidence"; None = no threshold); once
    # all are decided a commit pass writes the block's KV and emits its
    # tokens (models/slot.block_step). The logits are unshifted: row i
    # scores position i itself.
    gen_block: int = 0
    denoise_steps: int = 1
    remask: str = "sequential"
    denoise_threshold: Optional[float] = None
    mask_token_id: int = 0

    def __post_init__(self):
        for name in ("layer_types", "ssm_mults"):  # a list: stored as a tuple
            if not isinstance(getattr(self, name), tuple):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if not self.head_dim:
            # dataclasses.replace carries the filled-in value: a replace
            # that changes d_model or n_heads passes head_dim=0 with them.
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def patterned(self) -> bool:
        return bool(self.layer_types)

    @property
    def single_blocks(self) -> bool:
        """Each layer is one residual block (SINGLE_OPS)."""
        return any(t in SINGLE_OPS for t in self.layer_types)

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the mixer's convolution: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_width(self) -> int:
        return self.d_ff_expert or self.d_ff

    def op_kind(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else OP_ATTN

    def ff_sparse(self, layer: int) -> bool:
        """The layer's OWN feed-forward is sparse (a single-block layer
        has none: its sparse block is the operator OP_MOE)."""
        return (bool(self.n_experts) and layer >= self.n_dense_layers
                and self.op_kind(layer) in FUSED_OPS)

    def _count(self, *kinds: str) -> int:
        return sum(1 for t in self.layer_types if t in kinds)

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold KV."""
        if not self.layer_types:
            return self.n_layers
        return self._count(*KV_OPS)

    @property
    def n_window_layers(self) -> int:
        """Layers whose KV is the window's ring."""
        return self._count(*WINDOW_OPS)

    def heads(self, op: str) -> int:
        """Query heads of an attention layer of kind `op`."""
        return self.n_heads_window if op in WINDOW_OPS and \
            self.n_heads_window else self.n_heads

    @property
    def n_conv_layers(self) -> int:
        """Layers that hold a short-conv state."""
        return self._count(OP_CONV)

    @property
    def n_mamba_layers(self) -> int:
        """Layers that hold an SSM state (and the mixer's conv state);
        an "attention_mamba" layer counts here AND among n_attn_layers."""
        return self._count(*SSM_OPS)

    @property
    def n_sparse_layers(self) -> int:
        if not self.n_experts:
            return 0
        if self.single_blocks:
            return self._count(OP_MOE)
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    @property
    def multipliers(self) -> Tuple[float, ...]:
        """Every scalar multiplier, in the fields' order."""
        return (self.embed_mult, self.logits_mult, self.attn_in_mult,
                self.attn_out_mult, self.key_mult, self.ssm_in_mult,
                self.ssm_out_mult, *self.ssm_mults, self.mlp_gate_mult,
                self.mlp_down_mult)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def validate(self) -> "ModelConfig":
        assert self.patterned or self.d_model % self.n_heads == 0, (
            "d_model must divide by n_heads")
        assert self.n_heads % self.n_kv_heads == 0, "n_heads must divide by n_kv_heads"
        assert self.attn_impl in ("xla", "flash", "ring"), (
            f"unknown attn_impl {self.attn_impl!r}"
        )
        assert self.kv_cache_dtype in ("bf16", "int8"), (
            f"unknown kv_cache_dtype {self.kv_cache_dtype!r}"
        )
        assert self.weight_dtype in ("bf16", "int8"), (
            f"unknown weight_dtype {self.weight_dtype!r}"
        )
        assert self.act_dtype in ("bf16", "int8"), (
            f"unknown act_dtype {self.act_dtype!r}"
        )
        assert self.rope_scaling_type in (None, "linear", "llama3", "yarn"), (
            f"unknown rope_scaling_type {self.rope_scaling_type!r}"
        )
        assert self.router in ("softmax", "sigmoid"), (
            f"unknown router {self.router!r}"
        )
        assert 0 <= self.n_dense_layers <= self.n_layers, (
            "n_dense_layers must lie in [0, n_layers]"
        )
        if self.layer_types:
            assert len(self.layer_types) == self.n_layers, (
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}"
            )
            bad = sorted(set(self.layer_types) - set(FUSED_OPS + SINGLE_OPS))
            assert not bad, f"unknown layer_types entries {bad}"
            assert self.conv_kernel >= 2, "conv_kernel must be >= 2"
            if self.single_blocks:
                assert not set(self.layer_types) & set(FUSED_OPS) \
                    and self.n_dense_layers == 0, (
                        "layers of one block each (mamba / attention / moe) "
                        "do not mix with operator + feed-forward layers "
                        "(conv / full_attention / attention_mamba) or "
                        "leading dense ones")
                assert (OP_MOE in self.layer_types) == bool(self.n_experts), (
                    "moe layers need n_experts, and n_experts moe layers")
            if self.n_mamba_layers:
                assert self.ssm_heads > 0 and self.ssm_head_dim > 0 \
                    and self.ssm_state > 0 and self.ssm_chunk > 0 \
                    and self.ssm_heads % self.ssm_groups == 0, (
                        "mamba and attention_mamba layers need ssm_heads (a "
                        "multiple of ssm_groups), ssm_head_dim, ssm_state "
                        "and ssm_chunk")
            assert OP_ATTN_MAMBA in self.layer_types or \
                self.attn_in_mult == self.attn_out_mult == 1.0, (
                    "attn_in_mult / attn_out_mult act in attention_mamba "
                    "layers only")
            assert len(self.ssm_mults) in (0, 5) and (
                self.n_mamba_layers or not self.ssm_mults), (
                    "ssm_mults is the five multipliers of a mixer's input "
                    "projection (z, x, B, C, dt): mamba or attention_mamba "
                    "layers only")
            if self.n_window_layers:
                assert self.sliding_window > 0 and self.n_attn_layers \
                    and not self.single_blocks and not self.n_mamba_layers \
                    and self.heads(OP_SWA) % self.n_kv_heads == 0, (
                        "sliding_attention layers need sliding_window, at "
                        "least one full_attention layer beside them (the "
                        "slab as long as the engine's window), query heads "
                        "that divide by n_kv_heads, and no Mamba-2 mixer "
                        "or single-block layer in the stack: not built")
                assert self.rotary and not self.qk_norm \
                    and self.key_mult == 1.0, (
                        "rotary=False, qk_norm and key_mult are not built "
                        "for a stack with sliding_attention layers")
            else:
                assert not (self.sliding_window or self.n_heads_window
                            or self.rope_theta_window or self.attn_gate
                            or self.rotary_share != 1.0
                            or self.rope_scaling_type == "yarn"), (
                    "sliding_window / n_heads_window / rope_theta_window / "
                    "attn_gate / rotary_share / yarn act in a stack with "
                    "sliding_attention layers only")
            assert 0.0 < self.rotary_share <= 1.0 and \
                int(self.head_dim * self.rotary_share) % 2 == 0, (
                    "rotary_share must leave an even number of rotated dims")
            assert self.kv_cache_dtype == "bf16" and \
                self.weight_dtype == "bf16" and self.attn_impl == "xla", (
                    "a patterned stack (layer_types) is served in bf16 "
                    "with attn_impl='xla': int8 weights / KV and the "
                    "flash / ring kernels know only the homogeneous stack"
                )
        else:
            # These fields act only in the patterned stack; a homogeneous
            # config that sets them would silently run without them.
            assert (self.n_dense_layers == 0 and self.d_ff_expert == 0
                    and self.router == "softmax" and not self.router_bias
                    and not self.qk_norm), (
                "n_dense_layers / d_ff_expert / router / router_bias / "
                "qk_norm need layer_types (the patterned stack)"
            )
            assert (self.head_dim * self.n_heads == self.d_model
                    and self.rotary and self.ff_act == "swiglu"
                    and not self.d_ff_shared and not self.n_experts_held
                    and not self.expert_first and not self.ssm_heads
                    and all(m == 1.0 for m in self.multipliers)
                    and not (self.sliding_window or self.n_heads_window
                             or self.rope_theta_window or self.attn_gate)
                    and self.rotary_share == 1.0
                    and self.rope_scaling_type != "yarn"
                    and self.router_scale == 1.0), (
                "head_dim / rotary / ff_act / d_ff_shared / n_experts_held "
                "/ expert_first / ssm_* / the *_mult multipliers / "
                "sliding_window / n_heads_window / rope_theta_window / "
                "attn_gate / rotary_share / yarn / router_scale need "
                "layer_types (the patterned stack)"
            )
        assert self.ff_act in ("swiglu", "relu2"), (
            f"unknown ff_act {self.ff_act!r}")
        if self.gen_block:
            assert set(self.layer_types) == {OP_ATTN}, (
                "gen_block (generation by diffusion over blocks) is built "
                "for full_attention layers only: a conv, Mamba-2 or "
                "sliding_attention layer's state cannot hold a block that "
                "is not final yet")
            assert self.gen_block > 1 and self.denoise_steps >= 1 \
                and self.gen_block % self.denoise_steps == 0, (
                    "gen_block must be a multiple of denoise_steps")
            assert self.remask in ("sequential", "low_confidence"), (
                f"unknown remask {self.remask!r}")
            assert 0 <= self.mask_token_id < self.vocab_size, (
                "mask_token_id must lie in the vocabulary")
        else:
            assert (self.denoise_steps == 1 and self.remask == "sequential"
                    and self.denoise_threshold is None
                    and self.mask_token_id == 0), (
                "denoise_steps / remask / denoise_threshold / mask_token_id "
                "need gen_block")
        assert 0 <= self.expert_first and \
            self.expert_first + self.experts_held <= max(self.n_experts, 0) \
            or not self.n_experts, (
                "the experts held, [expert_first, expert_first + "
                "n_experts_held), must lie among the n_experts routed over")
        if self.n_experts:
            assert self.n_experts_per_token <= self.n_experts
        return self


PRESETS = {
    # CPU-testable config: every dim divides an 8-way mesh.
    "tiny": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        rope_theta=10000.0,
        eos_token_id=1,
    ),
    "tiny-moe": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        rope_theta=10000.0,
        eos_token_id=1,
        n_experts=4,
        n_experts_per_token=2,
    ),
    # The patterned stack at CPU-test size: 2 leading dense layers, then
    # one period (attention, conv, conv, conv) of sparse layers with a
    # sigmoid router, expert bias, QK-norm, tied embeddings.
    "tiny-lfm2": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=6,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        rope_theta=1000000.0,
        eos_token_id=1,
        tie_embeddings=True,
        n_experts=8,
        n_experts_per_token=4,
        layer_types=("conv", "conv", "full_attention", "conv", "conv",
                     "conv"),
        n_dense_layers=2,
        d_ff_expert=32,
        router="sigmoid",
        router_bias=True,
        qk_norm=True,
        conv_kernel=3,
    ),
    # Layers of one block each at CPU-test size: one period (mamba, moe,
    # mamba, moe, mamba, attention, moe), heads wider than d_model /
    # n_heads and without rotary embedding, 8 routed relu2 experts top-2
    # of which this program holds the first 4, a shared expert, 4 SSM
    # heads in 2 groups, prefill scanned in chunks of 8.
    "tiny-nemotron": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=7,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        rotary=False,
        d_ff=32,
        max_seq_len=128,
        eos_token_id=1,
        n_experts=8,
        n_experts_per_token=2,
        n_experts_held=4,
        layer_types=("mamba", "moe", "mamba", "moe", "mamba", "attention",
                     "moe"),
        d_ff_expert=32,
        d_ff_shared=96,
        ff_act="relu2",
        router="sigmoid",
        router_bias=True,
        router_scale=2.5,
        router_norm_eps=1e-20,
        conv_kernel=4,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_groups=2,
        ssm_state=16,
        ssm_chunk=8,
    ),
    # Attention and a Mamba-2 mixer side by side in every layer at
    # CPU-test size: 5 query heads a KV head, heads whose total width is
    # not d_model, 4 SSM heads in 2 groups with a state wider than a head,
    # rotary embedding, a dense SwiGLU, untied head, every multiplier
    # off 1 (the embedding's and the gate's no power of two).
    "tiny-falcon-h1": ModelConfig(
        vocab_size=256,
        d_model=80,
        n_layers=3,
        n_heads=10,
        n_kv_heads=2,
        head_dim=16,
        d_ff=160,
        max_seq_len=128,
        rope_theta=1e11,
        eos_token_id=1,
        layer_types=("attention_mamba",) * 3,
        conv_kernel=4,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_groups=2,
        ssm_state=32,
        ssm_chunk=8,
        embed_mult=2.8284271247461903,
        logits_mult=0.125,
        attn_in_mult=0.75,
        attn_out_mult=0.3,
        key_mult=0.35,
        ssm_in_mult=0.5,
        ssm_out_mult=0.4,
        ssm_mults=(0.7071067811865476, 0.5, 0.3535533905932738, 1.5,
                   0.7071067811865476),
        mlp_gate_mult=0.6,
        mlp_down_mult=0.2,
    ),
    # Window and full attention layers in one pattern at CPU-test size:
    # one dense full layer, then one period (sliding x 3, full) of sparse
    # layers; 6 query heads on the full kind and 8 on the window kind over
    # 2 KV heads, a window of 8, half-rotated YaRN on the full kind and a
    # plain table of another base on the window kind, the per-head gate,
    # 16 experts top-4 scaled by 2.5 beside a shared one, untied head.
    "tiny-laguna": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=5,
        n_heads=6,
        n_heads_window=8,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=128,
        rope_theta=500000.0,
        rope_theta_window=10000.0,
        rotary_share=0.5,
        rope_scaling_type="yarn",
        rope_scaling_factor=8.0,
        rope_scaling_original_max_position=16,
        rope_scaling_beta_fast=4.0,
        rope_scaling_beta_slow=1.0,
        rms_norm_eps=1e-6,
        eos_token_id=1,
        n_experts=16,
        n_experts_per_token=4,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention",
                     "full_attention"),
        n_dense_layers=1,
        d_ff_expert=32,
        d_ff_shared=32,
        router_scale=2.5,
        sliding_window=8,
        attn_gate=True,
    ),
    # Generation by diffusion over blocks at CPU-test size: attention with
    # QK-norm and a softmax-routed sparse SwiGLU (8 experts top-2) in
    # every layer, blocks of 4 positions denoised in 2 passes, untied head.
    "tiny-sdar": ModelConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        eos_token_id=1,
        n_experts=8,
        n_experts_per_token=2,
        layer_types=("full_attention",) * 2,
        d_ff_expert=32,
        qk_norm=True,
        gen_block=4,
        denoise_steps=2,
        mask_token_id=255,
    ),
    # ~1.1B params: single v5e chip (16 GB HBM) with room for KV cache.
    "bench-1b": ModelConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        d_ff=5632,
        max_seq_len=2048,
        rope_theta=10000.0,
        eos_token_id=2,
    ),
    # The north-star serving target (BASELINE.json): Llama-3-8B geometry.
    "llama3-8b": ModelConfig(),
    "llama3-70b": ModelConfig(
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        d_ff=28672,
    ),
}


def get_config(name_or_cfg, **overrides) -> ModelConfig:
    if isinstance(name_or_cfg, ModelConfig):
        cfg = name_or_cfg
    else:
        cfg = PRESETS[name_or_cfg]
    if overrides:
        if {"d_model", "n_heads"} & set(overrides) \
                and cfg.head_dim * cfg.n_heads == cfg.d_model:
            overrides.setdefault("head_dim", 0)  # derived: derive it again
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg.validate()
