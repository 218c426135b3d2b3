"""Decoder models, functional JAX, TPU-first: the homogeneous Llama-
family stack and (cfg.layer_types) the patterned stack at the end of
this file, whose layers differ in operator (short conv, attention, or
attention and a Mamba-2 mixer in parallel, or attention inside a
sliding window beside full attention, each kind with its own head count,
rotary table and cache length) and feed-forward (dense or
token -> expert dispatch), or are one residual block each (a Mamba-2
mixer, an attention or a sparse feed-forward alone).

Design (vs the reference's black-box CPU model servers, SURVEY.md §2.5):
 * Params are a plain pytree with layers STACKED on a leading [L, ...] axis
   and the forward pass is a `lax.scan` over layers — one traced block, so
   compile time is O(1) in depth and XLA fuses each block aggressively.
 * bf16 params/compute, f32 for norms/softmax/logits (MXU-friendly).
 * Static shapes everywhere; decode is a fixed-size KV cache with per-row
   write positions, so the whole generate loop jits once per bucket.
 * GQA + RoPE (half-split convention, HF-compatible) + SwiGLU; optional
   MoE blocks (top-k routing, experts sharded over 'ep').
 * Sharding is supplied externally (parallel/sharding.py) via GSPMD specs;
   this file only places `with_sharding_constraint` hints on activations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from seldon_tpu.models.config import (
    FUSED_OPS,
    KV_OPS,
    OP_ATTN,
    OP_ATTN_MAMBA,
    OP_ATTN_ONLY,
    OP_CONV,
    OP_MAMBA,
    OP_MOE,
    OP_SWA,
    SSM_OPS,
    WINDOW_OPS,
    ModelConfig,
)
from seldon_tpu.models.quantize import dequant
from seldon_tpu.ops import (
    decode_attention,
    moe_dispatch,
    prefill_attention,
    ssm_update,
)

Params = Dict[str, Any]


def _w(container: Dict[str, Any], name: str, dtype) -> jnp.ndarray:
    """Weight fetch with transparent int8 dequant (models/quantize.py):
    `name_scale` present -> int8 * per-output-channel scale, which XLA
    fuses into the consuming matmul's operand read."""
    return dequant(container[name], container.get(name + "_scale"), dtype)


def _quantize_act(x: jnp.ndarray):
    """Dynamic per-token symmetric int8 for W8A8 matmul inputs:
    x [..., D] -> (int8 [..., D], f32 scale [..., 1]).

    The optimization_barrier pins the quantization input to the
    MATERIALIZED activation: without it XLA may fuse this max into the
    producer and reduce over unrounded f32 intermediates, making the
    scale — and hence the int8 bits — a function of fusion choices.
    Fusion differs between the single-chip and the SPMD-partitioned
    (graftmesh tp>1) compilations of the same model, so an unpinned
    scale breaks the engine's bit-exact-across-configs contract on
    near-ties (observed: tp=2 vs tp=1 greedy divergence at the 128
    bucket). The barrier costs one activation materialization the
    int8 dot was about to force anyway. Machine-certified: graftlint's
    num-barrier pass proves every int8 scale in the tree reads a
    barrier-pinned input (make lint)."""
    x = jax.lax.optimization_barrier(x)
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s), -127, 127
    ).astype(jnp.int8)
    return q, s


def _w8a8_applies(container: Dict[str, Any], name: str,
                  cfg: ModelConfig) -> bool:
    return (cfg.act_dtype == "int8"
            and container[name].dtype == jnp.int8
            and container.get(name + "_scale") is not None)


def _qdot(x: jnp.ndarray, container: Dict[str, Any], name: str,
          cfg: ModelConfig, act_q=None) -> jnp.ndarray:
    """x [..., D] @ W [D, F] with optional W8A8.

    When cfg.act_dtype == "int8" and the weight is int8-quantized:
    dynamic per-token A8 feeds an s8 x s8 -> s32 dot — the v5e MXU runs
    int8 at double rate, and the round-5 profile shows decode is
    COMPUTE-bound past the slot knee, so this halves the binding
    resource (probe: tools/probe_w8a8.py, 2.2x on the MLP stack).
    Scales apply to the f32 output; exact algebra since weight scales
    are per-output-channel ([1, F]). Otherwise falls back to the
    dequant-in-fusion bf16-math path (identical contraction to the
    einsums it replaces). `act_q` shares one _quantize_act(x) across
    the projections that consume the same input (XLA CSE would dedupe
    anyway under jit; sharing keeps eager/debug runs cheap too)."""
    w = container[name]
    wscale = container.get(name + "_scale")
    if not _w8a8_applies(container, name, cfg):
        return jnp.einsum("...d,df->...f", x, dequant(w, wscale, x.dtype))
    xq, xs = act_q if act_q is not None else _quantize_act(x)
    y = jax.lax.dot_general(
        xq, w, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    # graftlint: allow(num-barrier) the s32->f32 epilogue is exact
    # algebra (per-channel scales commute with the dot); both inputs to
    # the product are already-materialized jit values, so fusion cannot
    # change the bits — the hazard lives in the SCALES, which are
    # barrier-pinned inside _quantize_act.
    return (y.astype(jnp.float32) * xs
            * wscale.astype(jnp.float32)).astype(x.dtype)


def _embed_rows(params: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    """Embedding gather with transparent dequant (scale is per-column,
    so it broadcasts over gathered rows)."""
    rows = jnp.take(params["embed"], tokens, axis=0)
    scale = params.get("embed_scale")
    if scale is None:
        return rows
    # graftlint: allow(num-barrier) weight dequant of constant embed
    # rows: the int8 bits and per-column scale are load-time constants
    # identical in every compilation, so the product is too.
    return rows.astype(dtype) * scale.astype(dtype)[0]


def _scaled(x: jnp.ndarray, mult) -> jnp.ndarray:
    """x times one of ModelConfig's fixed multipliers (a Python float, or
    a vector over the last axis), in x's dtype and where the published
    model applies it; a multiplier of 1 adds no operation, so a stack
    without multipliers lowers as it did."""
    if isinstance(mult, (int, float)) and mult == 1:
        return x
    with jax.named_scope("mixer/scale"):
        return x * jnp.asarray(mult, x.dtype)


Cache = Dict[str, jnp.ndarray]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    cfg = cfg.validate()
    if cfg.patterned:
        return _init_params_patterned(cfg, key)
    dt = _dtype(cfg)
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k = iter(jax.random.split(key, 16))

    def norm(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, *shape, scale=0.02):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dt)

    out_scale = 0.02 / (2 * L) ** 0.5  # residual-stream init damping
    blocks = {
        "attn_norm": norm(L, D),
        "wq": dense(next(k), L, D, H * Dh),
        "wk": dense(next(k), L, D, Hkv * Dh),
        "wv": dense(next(k), L, D, Hkv * Dh),
        "wo": dense(next(k), L, H * Dh, D, scale=out_scale),
        "mlp_norm": norm(L, D),
    }
    if cfg.n_experts:
        E = cfg.n_experts
        blocks.update(
            {
                "router": dense(next(k), L, D, E).astype(jnp.float32),
                "w_gate": dense(next(k), L, E, D, F),
                "w_up": dense(next(k), L, E, D, F),
                "w_down": dense(next(k), L, E, F, D, scale=out_scale),
            }
        )
    else:
        blocks.update(
            {
                "w_gate": dense(next(k), L, D, F),
                "w_up": dense(next(k), L, D, F),
                "w_down": dense(next(k), L, F, D, scale=out_scale),
            }
        )
    params: Params = {
        "embed": dense(next(k), V, D),
        "blocks": blocks,
        "final_norm": norm(D),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), D, V)
    return params


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope_frequencies(cfg: ModelConfig) -> jnp.ndarray:
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    )
    if cfg.rope_scaling_type == "linear":
        return inv_freq / cfg.rope_scaling_factor
    if cfg.rope_scaling_type == "llama3":
        # HF transformers' _compute_llama3_parameters: frequencies whose
        # wavelength exceeds the ORIGINAL context window are slowed by
        # `factor`; those well inside it are untouched; a smooth ramp
        # (parameterized by the low/high frequency knees) interpolates.
        factor = cfg.rope_scaling_factor
        lo_f = cfg.rope_scaling_low_freq_factor
        hi_f = cfg.rope_scaling_high_freq_factor
        old_ctx = cfg.rope_scaling_original_max_position
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = old_ctx / lo_f
        high_wavelen = old_ctx / hi_f
        smooth = (old_ctx / wavelen - lo_f) / (hi_f - lo_f)
        scaled = jnp.where(
            wavelen > low_wavelen,
            inv_freq / factor,
            jnp.where(
                wavelen < high_wavelen,
                inv_freq,
                (1.0 - smooth) * inv_freq / factor + smooth * inv_freq,
            ),
        )
        return scaled
    return inv_freq


def rope_by_kind(cfg: ModelConfig, op: str):
    """(inv_freq, factor on cos and sin) of an attention layer of kind
    `op` in a stack whose kinds differ in them (cfg.n_window_layers).

    A sliding_attention layer: the plain table of rope_theta_window over
    the whole head. A full_attention layer: the first d = rotary_share x
    head_dim dims rotate; with "yarn" over those d,
    dim(r) = d ln(original / (2 pi r)) / (2 ln theta),
    low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), both
    clamped to [0, d - 1], ramp_i = clip((i - low) / (high - low), 0, 1),
    inv_freq_i = theta^(-2i/d) x ((1 - ramp_i) + ramp_i / factor), and cos
    and sin are multiplied by rope_attention_factor (0: 0.1 ln(factor) +
    1), so the rotated halves' product carries its square and the
    pass-through dims' none. Computed on the host in float64 (a constant
    of the program), handed over in float32."""
    if op in WINDOW_OPS:
        half = cfg.head_dim // 2
        theta = cfg.rope_theta_window or cfg.rope_theta
        return jnp.asarray(
            theta ** -(np.arange(half) / half), jnp.float32), 1.0
    d = int(cfg.head_dim * cfg.rotary_share)
    inv = cfg.rope_theta ** -(np.arange(d // 2) * 2.0 / d)
    if cfg.rope_scaling_type != "yarn":
        return jnp.asarray(inv, jnp.float32), 1.0
    factor = cfg.rope_scaling_factor

    def dim(rotations):
        return d * np.log(cfg.rope_scaling_original_max_position
                          / (2 * np.pi * rotations)) \
            / (2 * np.log(cfg.rope_theta))

    low = min(max(int(np.floor(dim(cfg.rope_scaling_beta_fast))), 0), d - 1)
    high = min(max(int(np.ceil(dim(cfg.rope_scaling_beta_slow))), 0), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    mscale = cfg.rope_attention_factor or 0.1 * float(np.log(factor)) + 1.0
    return jnp.asarray(inv * ((1 - ramp) + ramp / factor), jnp.float32), \
        float(mscale)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray,
               mscale: float = 1.0):
    """x: [B, S, H, Dh], positions: [B, S] -> rotated x (half-split pairing
    over the first 2 x len(inv_freq) dims; the rest pass through)."""
    rot = 2 * inv_freq.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], positions, inv_freq, mscale),
             x[..., rot:]], axis=-1)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gqa_attention(
    q: jnp.ndarray,  # [B, Sq, H, Dh]
    k: jnp.ndarray,  # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,  # [B, Skv, Hkv, Dh]
    mask: jnp.ndarray,  # [B, Sq, Skv] bool (True = attend)
) -> jnp.ndarray:
    """Grouped-query attention, f32 softmax. Returns [B, Sq, H*Dh]."""
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q = q.reshape(B, Sq, Hkv, G, Dh)
    with jax.named_scope("attn/scores"):
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32
        ) / (Dh**0.5)
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        w = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
    with jax.named_scope("attn/out"):
        out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, Sq, H * Dh)


def gqa_attention_decode(
    q: jnp.ndarray,  # [B, 1, H, Dh]
    ck: jnp.ndarray,  # [B, 1, T, Hkv*Dh] OLD cache (pre-write; int8 if scales)
    cv: jnp.ndarray,  # or rows of fewer heads, [B, Hkv, T, Dh] the paged pool
    k_fresh: jnp.ndarray,  # [B, 1, Hkv, Dh] bf16 (exact, this token)
    v_fresh: jnp.ndarray,  # [B, 1, Hkv, Dh]
    mask_lt: jnp.ndarray,  # [B, 1, T] True where t < pos (strict)
    k_scale: Optional[jnp.ndarray] = None,  # [B, Hkv, T] f32 (int8 cache)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Decode attention over the PRE-write cache, read as it is stored,
    plus a fresh-token column.

    Why pre-write: scattering this step's k/v into the carried cache and
    slice-reading it back defeats XLA's operand fusion — the read-after-
    write materializes a copy of the whole [B,*,T,Dh] layer (measured 2x
    attention cost at [160, 257] on v5e). Reading the OLD cache (no data
    dependency on the write) fuses; the current token rides as one exact
    bf16 column appended to the score matrix, and cache writes happen
    OUTSIDE the layer scan in one batched scatter.

    Why the cache is not viewed per head first: a slab row holds a
    token's heads side by side (cache_spec), and reading it as
    [B, T, Hkv, Dh] transposed to head-major brings a per-layer copy of
    the slice back on every step (seen in the v5e's compiled HLO as
    copy s8[64,1024,8,128]). The contraction over the whole row, below,
    is what reads the slab as stored.

    For int8 caches the per-(token, head) scales are factored OUT of the
    einsums — scores = (q . k_q) * k_scale, out = (w * v_scale) . v_q —
    so the HBM read stays 1 byte/element (dequantizing first re-widens
    the operand: measured int8 bought only 3% that way). int8 values are
    exact in bf16 and scales apply in f32, so rounding is strictly
    tighter than dequantize-then-multiply. The fresh column is exact
    bf16 — requantization noise only enters through PAST tokens.

    A cache whose rows hold `side` heads side by side,
    [B, Hkv / side, T, side * Dh] (kv_heads_per_row: all Hkv in the
    slab, 1 in the paged pool's view; told by the shapes: k_fresh has
    the model's Hkv), is read as stored: the queries go block-diagonal
    over the row (_beside) and each keeps its own head's lanes of the
    weighted values (_own_side). The int8 scales stay [B, Hkv, T]
    whatever the rows: a query's scores and weights take the scale of
    its own head (_head_scale)."""
    B, S, H, Dh = q.shape
    Hkv = k_fresh.shape[2]
    side = Hkv // ck.shape[1]  # heads side by side in a cache row
    G = H // Hkv
    qr = q.reshape(B, S, Hkv, G, Dh)
    qc = qr if side == 1 else _beside(qr, side)
    with jax.named_scope("attn/scores"):
        scores = jnp.einsum(
            "bskgd,bktd->bkgst", qc, ck.astype(qr.dtype),
            preferred_element_type=jnp.float32,
        ) / (Dh**0.5)
        if k_scale is not None:
            scores = scores * _head_scale(k_scale, side, G)
        s_fresh = jnp.einsum(
            "bskgd,bukd->bkgsu", qr, k_fresh.astype(qr.dtype),
            preferred_element_type=jnp.float32,
        ).reshape(B, Hkv // side, side * G, S, 1) / (Dh**0.5)
        scores = jnp.where(mask_lt[:, None, None, :, :], scores, -1e30)
        # Flash-style combine of the fresh column — concatenating it as
        # a T+1th score column forces XLA to relayout the whole (lane-
        # padded) score tensor; explicit max/exp algebra touches only
        # what it must.
        m = jnp.maximum(
            jnp.max(scores, axis=-1, keepdims=True), s_fresh
        )  # [B,k,g,1,1]
        p = jnp.exp(scores - m)
        p_f = jnp.exp(s_fresh - m)  # [B,k,g,1,1]
        l = jnp.sum(p, axis=-1, keepdims=True) + p_f
        wc = p / l
        if v_scale is not None:
            wc = wc * _head_scale(v_scale, side, G)
    with jax.named_scope("attn/out"):
        out = jnp.einsum(
            "bkgst,bktd->bskgd", wc.astype(qr.dtype), cv.astype(qr.dtype)
        )
        if side > 1:
            out = _own_side(out, side)
        out = out + jnp.einsum(
            "bkgsu,bukd->bskgd",
            (p_f / l).reshape(B, Hkv, G, S, 1).astype(qr.dtype),
            v_fresh.astype(qr.dtype),
        )
    return out.reshape(B, S, H * Dh)


def kv_heads_per_row(cfg: ModelConfig) -> int:
    """KV heads that share one row of the slab (cache_spec): all of
    them, [La, B, 1, T, Hkv * Dh], one row a token, in every stack.

    Why (PERF.md section 6, PR 28 for heads of 64, PR 32 for heads of
    128): the decode step WRITES a token's heads together and the layer
    scan's attention READS a layer at a time. With the slab head-major
    [L, B, Hkv, T, Dh] the v5e compiler kept it T-minor for the
    attention and token-major ({4,2,3,1,0}) for the step's scatter and
    relaid the whole of it out between the two: on the chunk's entry
    and exit, and a layer's K and V slice on EVERY step of every layer
    (31 % of mistral7b.chat's device time, ledger PR 31; the form of
    the scatter alone did not cure it). With a single row a token both
    agree on the layout as stored: the compiled chunk holds no copy of
    the slab and the layer's slice is read inside the attention fusion.
    Token-major storage no longer costs the per-layer transpose it
    once did because attention does not view the row per head: it
    contracts over the whole row (_beside: Hkv times the products, of
    which all but a head's own are with zeros), n_heads FLOPs a byte of
    a bf16 slab, twice that of an int8 one."""
    return cfg.n_kv_heads


def _kv_rows(x: jnp.ndarray, side: int) -> jnp.ndarray:
    """Fresh k or v [B, S, Hkv, Dh] as the cache rows hold them:
    [B, S, Hkv / side, side * Dh], head h at lanes (h % side) * Dh."""
    B, S, Hkv, Dh = x.shape
    return x.reshape(B, S, Hkv // side, side * Dh)


def _beside(qr: jnp.ndarray, side: int) -> jnp.ndarray:
    """Queries [B, S, Hkv, G, Dh] against cache rows of `side` heads:
    [B, S, Hkv / side, side * G, side * Dh], each query in the lanes of
    its own KV head and zero in its neighbours', so one contraction over
    the row gives every head's scores (the zeros add nothing)."""
    B, S, Hkv, G, Dh = qr.shape
    q6 = qr.reshape(B, S, Hkv // side, side, G, 1, Dh)
    own = jnp.eye(side, dtype=bool)[:, None, :, None]
    return jnp.where(own, q6, 0).reshape(
        B, S, Hkv // side, side * G, side * Dh)


def _own_side(out: jnp.ndarray, side: int) -> jnp.ndarray:
    """The weighted values [B, S, Hkv / side, side * G, side * Dh] of
    _beside's scores, each query keeping its own head's lanes:
    [B, S, Hkv, G, Dh]."""
    B, S, Hc, N, C = out.shape
    o7 = out.reshape(B, S, Hc, side, N // side, side, C // side)
    own = jnp.stack([o7[:, :, :, j, :, j] for j in range(side)], axis=3)
    return own.reshape(B, S, Hc * side, N // side, C // side)


def _head_scale(scale: jnp.ndarray, side: int, G: int) -> jnp.ndarray:
    """int8 scales [B, Hkv, T] against scores or weights
    [B, Hkv / side, side * G, S, T]: query n of a row reads KV head
    n // G of that row."""
    B, Hkv, T = scale.shape
    per_query = jnp.broadcast_to(
        scale.reshape(B, Hkv // side, side, 1, 1, T),
        (B, Hkv // side, side, G, 1, T))
    return per_query.reshape(B, Hkv // side, side * G, 1, T)


def _kv_slab(x: jnp.ndarray, side: int) -> jnp.ndarray:
    """Fresh k or v [B, S, Hkv, Dh] as a layer of the slab holds them,
    [B, Hkv / side, S, side * Dh]: with a row a token (side = Hkv) the
    transpose is over an axis of 1 and moves nothing."""
    return _kv_rows(x, side).transpose(0, 2, 1, 3)


def _kv_tokens(c: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """A cache layer [B, Hkv / side, P, side * Dh], slab rows or the
    paged pool's head-major view alike, as attention over whole
    sequences wants it: [B, P, Hkv, Dh]."""
    B, Hc, P_, C = c.shape
    return c.transpose(0, 2, 1, 3).reshape(
        B, P_, n_kv_heads, Hc * C // n_kv_heads)


def moe_block(x: jnp.ndarray, bp: Dict[str, jnp.ndarray], cfg: ModelConfig):
    """Top-k MoE. Dense-mixing formulation: every expert runs on every token
    and results are combined with the (sparsified) router weights. This is
    compute-inflated by E/k but fully static-shaped and shards cleanly over
    'ep'. Who runs it: training's forward (the load-balance loss is
    computed here alone), tp > 1, a mesh of several devices, and the
    paged, prefix, chunked and speculative paths' own runners. The
    default engine's two runners (_run_blocks_prefill, _run_blocks_decode
    with the stack whole on one device) compute the same function by
    token -> expert dispatch (ops/moe_dispatch.py, _dispatched_experts:
    grouped products over the experts that hold live rows; with the
    softmax router it is this block's function, tests/test_patterned.py),
    as the patterned stack always does (_sparse_ff).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.n_experts_per_token
    with jax.named_scope("moe/router"):
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32), bp["router"]
        )
        probs_full = jax.nn.softmax(logits, axis=-1)  # [B,S,E] f32
        top_vals, top_idx = jax.lax.top_k(logits, K)  # [B,S,K]
        gates = jax.nn.softmax(top_vals, axis=-1)
        # Scatter the top-k gates back into a dense [B,S,E] mixing matrix.
        onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B,S,K,E]
        mix = jnp.einsum("bske,bsk->bse", onehot, gates)
        # Switch-style load-balance aux: E * Σ_e frac_routed(e) ·
        # mean_prob(e); minimized (→1) by a uniform router, grows as
        # experts collapse.
        frac = onehot.sum(axis=2).mean(axis=(0, 1)) / K  # [E]
        lb_loss = E * jnp.sum(frac * probs_full.mean(axis=(0, 1)))
    with jax.named_scope("moe/experts"):
        hidden = jax.nn.silu(
            jnp.einsum("bsd,edf->besf", x, _w(bp, "w_gate", x.dtype))
        ) * jnp.einsum("bsd,edf->besf", x, _w(bp, "w_up", x.dtype))
        expert_out = jnp.einsum(
            "besf,efd->besd", hidden, _w(bp, "w_down", x.dtype)
        )
        out = jnp.einsum("besd,bse->bsd", expert_out, mix.astype(x.dtype))
    return out, lb_loss


# ---------------------------------------------------------------------------
# Transformer block via lax.scan
# ---------------------------------------------------------------------------


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric int8: x [..., Dh] -> (int8 [..., Dh],
    scale [...]). Halves KV-cache HBM traffic — the decode-step
    bottleneck once weights are amortized over enough slots.

    Scales are stored bf16: their relative error (2^-8 ~ 0.4%) sits
    below the int8 quantization noise itself, and f32 scales measurably
    hurt — they double the scale read AND the full-array relayout copy
    XLA inserts for the scale buffers each decode step.

    The optimization_barrier pins the scale to the MATERIALIZED k/v
    (same hazard as _quantize_act: a max fused into the rope/projection
    producer reads unrounded f32 and its value drifts across the
    single-chip vs SPMD-partitioned compilations of the same model)."""
    x = jax.lax.optimization_barrier(x)
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _quantize_kv_rows(x: jnp.ndarray, head_dim: int):
    """_quantize_kv over stacked cache rows [L, B, Hkv / side, S,
    side * Dh]: each head's lanes by that head's own scale. Returns
    (int8 rows of the same shape, scales [L, B, Hkv, S] as every cache
    layout holds them)."""
    L, B, Hc, S, C = x.shape
    side = C // head_dim
    q, scale = _quantize_kv(x.reshape(L, B, Hc, S, side, head_dim))
    return q.reshape(x.shape), scale.transpose(0, 1, 2, 4, 3).reshape(
        L, B, Hc * side, S)


def kv_writes(kv: Cache, cache: Cache, cfg: ModelConfig) -> Cache:
    """Fresh k / v (a prefill's stacked ys in the activation dtype, slab
    rows or by head) as `cache` stores them: int8 with per-(token, head)
    scales, or a cast. What every admission scatters, into a slab or
    through block tables."""
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv_rows(kv["k"], cfg.head_dim)
        vq, vs = _quantize_kv_rows(kv["v"], cfg.head_dim)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    dt = cache["k"].dtype
    return {"k": kv["k"].astype(dt), "v": kv["v"].astype(dt)}


def kv_by_head(kv: Cache, cfg: ModelConfig) -> Cache:
    """Slab-layout k / v [L, B, 1, S, Hkv * Dh] (a cold prefill's) as
    the paged pool and its scatter hold them, head-major
    [L, B, Hkv, S, Dh]: a transpose of one admission's fresh KV, never
    of a cache. The int8 scales are [L, B, Hkv, S] in both and pass
    through."""
    def turn(x):
        L, B, _, S, C = x.shape
        return x.reshape(L, B, S, cfg.n_kv_heads, cfg.head_dim).transpose(
            0, 1, 3, 2, 4)
    return {key: turn(x) if key in ("k", "v") else x
            for key, x in kv.items()}


def _block(
    x: jnp.ndarray,
    bp: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    mask: jnp.ndarray,
    act_spec: Optional[P] = None,
    ring_mesh=None,
):
    """One CACHE-FREE transformer block (training / scoring / ring).
    Serving paths live in _run_blocks_prefill / _run_blocks_decode."""
    B, S, _ = x.shape
    Dh = cfg.head_dim
    h = rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(h, bp, cfg, positions, inv_freq)

    use_flash = cfg.attn_impl == "flash" and S > 1
    # Ring attention: long-context full-sequence path with the sequence
    # axis sharded over 'sp' — exact attention, k/v blocks rotate over ICI
    # (parallel/ring_attention.py).
    use_ring = cfg.attn_impl == "ring" and ring_mesh is not None and S > 1

    if use_ring:
        from seldon_tpu.parallel.ring_attention import ring_attention

        # GQA is native in the ring: only the Hkv-head k/v blocks rotate
        # over ICI (q_per_kv x less traffic than pre-expanding to H).
        out = ring_attention(q, k, v, ring_mesh, axis="sp", causal=True)
        attn = out.reshape(B, S, cfg.n_heads * Dh)
    elif use_flash:
        # Full-sequence causal path through the pallas flash kernel
        # (ops/flash_attention.py). GQA is native in the kernel: kv stays
        # at Hkv heads and the q-head grid maps onto shared kv rows.
        from seldon_tpu.ops.flash_attention import flash_attention

        def fold(t):
            n = t.shape[2]
            return t.transpose(0, 2, 1, 3).reshape(B * n, S, Dh)

        out = flash_attention(fold(q), fold(k), fold(v), causal=True,
                              q_per_kv=cfg.q_per_kv)
        attn = (
            out.reshape(B, cfg.n_heads, S, Dh)
            .transpose(0, 2, 1, 3)
            .reshape(B, S, cfg.n_heads * Dh)
        )
    else:
        attn = gqa_attention(q, k, v, mask)

    with jax.named_scope("attn/out"):
        x = x + _qdot(attn, bp, "wo", cfg)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    x, aux = _mlp_res(x, bp, cfg, act_spec)
    return x, aux


def _run_blocks(params, x, cfg, positions, inv_freq, mask,
                act_spec=None, remat=False, ring_mesh=None):
    """Cache-free lax.scan over the stacked layer axis."""

    def body(carry, bp):
        out, aux = _block(carry, bp, cfg, positions, inv_freq, mask,
                          act_spec=act_spec, ring_mesh=ring_mesh)
        return out, aux

    if remat:
        body = jax.checkpoint(body)
    x, aux = jax.lax.scan(body, x, params["blocks"])
    return x, None, jnp.mean(aux)


def _qkv(h, bp, cfg, positions, inv_freq, tp=None, op=None, step=False):
    """`op`: the layer's kind where a stack's attention kinds differ in
    head count and rotary table (cfg.n_window_layers; rope_by_kind).
    `step`: a decode step whatever S is (cfg.gen_block: a block of
    positions a slot), fenced as S == 1 is below.

    `tp` (models/tp_sharding.TpHints, EngineConfig.tp > 1 only) pins
    the projected heads sharded on 'tp': each device computes the FULL
    d_model contraction for its own disjoint head slice, so per-element
    reduction order — and hence the bits — match tp=1 exactly.

    In a decode step (S == 1) the flat [B, 1, H*Dh] results of the wq and
    wk products are fenced before the reshape to heads. Unfenced, the
    TPU compiler folds that reshape into the product, which then has two
    free weight dimensions, wants the whole stored stack relaid out
    contraction-minor (a copy of the parameter on every chunk's entry)
    and takes neither the int8 dequantise nor a bf16 stack's layer slice
    into its fusion: a layer's whole projection matrix is written out
    and read back, three passes over wq where wv and wo, whose products
    stay flat, make one (PERF.md section 6, PR 42). The fence moves no
    value. A step is bound by those bytes; a prefill (S > 1) is bound
    by the matrix unit and a fence there would only send its
    activations through memory once more, so it keeps its program."""
    B, S, _ = h.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    flat = jax.lax.optimization_barrier if S == 1 or step else (lambda t: t)
    with jax.named_scope("attn/qkv"):
        hq = _quantize_act(h) if _w8a8_applies(bp, "wq", cfg) else None
        q = flat(_qdot(h, bp, "wq", cfg, act_q=hq)).reshape(
            B, S, cfg.heads(op) if op else cfg.n_heads, Dh)
        k = _scaled(flat(_qdot(h, bp, "wk", cfg, act_q=hq)),
                    cfg.key_mult).reshape(B, S, Hkv, Dh)
        v = _qdot(h, bp, "wv", cfg, act_q=hq).reshape(B, S, Hkv, Dh)
        if cfg.qk_norm:
            with jax.named_scope("attn/qk_norm"):
                q = rms_norm(q, bp["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, bp["k_norm"], cfg.rms_norm_eps)
        if cfg.rotary:
            rope = rope_by_kind(cfg, op) if cfg.n_window_layers \
                else (inv_freq,)
            q = apply_rope(q, positions, *rope)
            k = apply_rope(k, positions, *rope)
        if tp is not None:
            q, k, v = tp.heads(q), tp.heads(k), tp.heads(v)
    return q, k, v


def _dispatched_experts(blocks, cfg, whole: bool):
    """A homogeneous stack's blocks as (what the layer scan slices, what
    the expert kernel is handed whole), or (blocks, None) where the
    sparse block stays moe_block: no experts, or a stack that is not
    `whole` on one device (tp, a mesh the compiler partitions the
    program over, a ring). The expert matrices [L, E, ...] and their int8
    scales (under "scales", None for a stack that has none) leave the
    scan with the layer and expert axes merged, [L * E, ...], which
    moves nothing (and dequantises nothing: an int8
    stack goes to the grouped product as it is stored); the scan rides
    the layer's index and the kernel picks that layer's E groups by it
    (ops/moe_dispatch.dispatch_experts says why no slice of the stack
    may be a kernel's operand)."""
    if not (cfg.n_experts and whole):
        return blocks, None
    def merged(name):
        return blocks[name].reshape((-1,) + blocks[name].shape[2:])

    experts = {n: merged(n) for n in _EXPERT_STACKS}
    scales = {n: merged(n + "_scale") for n in _EXPERT_STACKS
              if n + "_scale" in blocks}
    experts["scales"] = scales or None
    taken = set(_EXPERT_STACKS) | {n + "_scale" for n in scales}
    return {n: v for n, v in blocks.items() if n not in taken}, experts


def _mlp_res(x, bp, cfg, act_spec, tp=None, experts=None, layer=None,
             live=None):
    """Post-attention half of a block: residual + (SwiGLU | MoE).
    Returns (x, aux): the sparse block's load-balance loss (zero for a
    dense MLP) or, handed the expert stacks, what routing did.

    Under `tp` the gate/up projections run output-sharded on d_ff and
    the hidden is ALL-GATHERED (exact data movement) before the
    REPLICATED w_down contraction — no partial-sum reduction ever forms,
    keeping outputs bit-identical to tp=1 (tp_sharding module doc). MoE
    weights replicate, so that branch needs no hints.

    `experts` (_dispatched_experts: the merged expert stacks, `layer`
    this block's index in them) sends each row of `live` [B, S] to the
    experts it chose and no row anywhere else
    (ops/moe_dispatch.dispatch_experts); aux is then this layer's
    counters [1, experts touched, assignments] (routing_width)."""
    h = rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if experts is not None:
        B, S, D = h.shape
        rows = h.reshape(B * S, D)
        with jax.named_scope("moe/router"):
            top_idx, top_w = moe_dispatch.route(
                rows, bp["router"], None, top_k=cfg.n_experts_per_token,
                router="softmax")
        out, stats = moe_dispatch.dispatch_experts(
            rows, top_idx, top_w, experts["w_gate"], experts["w_up"],
            experts["w_down"], None if live is None else live.reshape(B * S),
            n_experts=cfg.n_experts, layer=layer, scales=experts["scales"])
        x = x + out.reshape(B, S, D)
        aux = _routing_counts(cfg, stats)
    elif cfg.n_experts:
        mlp_out, aux = moe_block(h, bp, cfg)
        x = x + mlp_out
    else:
        with jax.named_scope("mlp"):
            hq = (_quantize_act(h) if _w8a8_applies(bp, "w_gate", cfg)
                  else None)
            hidden = jax.nn.silu(_qdot(h, bp, "w_gate", cfg, act_q=hq)) \
                * _qdot(h, bp, "w_up", cfg, act_q=hq)
            if tp is not None:
                hidden = tp.gather(tp.flat(hidden))
            x = x + _qdot(hidden, bp, "w_down", cfg)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    return x, aux


def _run_blocks_prefill(params, x, cfg, positions, inv_freq, mask,
                        act_spec=None, ring_mesh=None, tp=None, plens=None,
                        spread=False):
    """Layer scan for PREFILL: attention runs over the fresh k/v only
    (every serving prefill starts at position 0, so the fresh tokens ARE
    the whole visible window — the cache is never read) and each layer's
    rope'd k/v come back as scan ys, stacked [L, B, 1, S, Hkv * Dh],
    exactly the slab's layout (a reshape of the fresh [B, S, Hkv, Dh]).
    The caller builds/updates the cache from them in ONE operation — no
    per-layer cache traffic at all.

    `ring_mesh` (with cfg.attn_impl == "ring") runs the attention as
    CONTEXT-PARALLEL ring attention over the 'sp' mesh axis — long
    prompts prefill with the sequence sharded across devices, k/v blocks
    rotating over ICI (parallel/ring_attention.py). The returned k/v ys
    are full arrays; GSPMD gathers the sp shards when the caller
    scatters them into the (T-unsharded) decode cache.

    A stack with experts that lies whole on one device (no `tp`, no
    ring, not `spread` over a mesh) computes its sparse block by token ->
    expert dispatch (_dispatched_experts) for each row's first `plens`
    [B] tokens (None: all S), so right-padding routes nowhere, and aux is
    then the routing counters summed over the layers.

    Returns (x, {"k","v"} stacked bf16, aux)."""
    side = kv_heads_per_row(cfg)
    blocks, experts = _dispatched_experts(
        params["blocks"], cfg,
        tp is None and ring_mesh is None and act_spec is None and not spread)
    live = None if experts is None or plens is None else \
        jnp.arange(x.shape[1])[None, :] < plens[:, None]

    def body(carry, xs):
        bp, layer = xs
        h = rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, bp, cfg, positions, inv_freq, tp=tp)
        B, S = q.shape[0], q.shape[1]
        if ring_mesh is not None and cfg.attn_impl == "ring" and S > 1:
            from seldon_tpu.parallel.ring_attention import ring_attention

            # Hkv-head k/v rotate directly (GQA native in the ring).
            out = ring_attention(q, k, v, ring_mesh, axis="sp", causal=True)
            attn = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
        elif cfg.attn_impl == "flash" and S > 1:
            from seldon_tpu.ops.flash_attention import flash_attention

            Dh = cfg.head_dim

            def fold(t):
                n = t.shape[2]
                return t.transpose(0, 2, 1, 3).reshape(B * n, S, Dh)

            out = flash_attention(fold(q), fold(k), fold(v), causal=True,
                                  q_per_kv=cfg.q_per_kv)
            attn = (out.reshape(B, cfg.n_heads, S, Dh)
                    .transpose(0, 2, 1, 3).reshape(B, S, -1))
        else:
            attn = gqa_attention(q, k, v, mask)
        if tp is not None:
            # Exact all-gather of the head-sharded attention before the
            # REPLICATED wo contraction (tp_sharding module doc).
            attn = tp.gather(tp.flat(attn))
        with jax.named_scope("attn/out"):
            x = carry + _qdot(attn, bp, "wo", cfg)
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        x, aux = _mlp_res(x, bp, cfg, act_spec, tp=tp, experts=experts,
                          layer=layer, live=live)
        # ys in cache layout: [B, 1, S, Hkv * Dh] per layer.
        return x, (_kv_slab(k, side), _kv_slab(v, side), aux)

    if experts is None:
        x, (ks, vs, aux) = jax.lax.scan(
            lambda carry, bp: body(carry, (bp, None)), x, blocks)
        return x, {"k": ks, "v": vs}, jnp.mean(aux)
    x, (ks, vs, aux) = jax.lax.scan(
        body, x, (blocks, jnp.arange(cfg.n_layers)))
    return x, {"k": ks, "v": vs}, jnp.sum(aux, axis=0)


def _run_blocks_prefill_prefix(params, x, cfg, positions, inv_freq, mask,
                               prefix_kv, tp=None):
    """Layer scan for SUFFIX prefill (prefix-cache admissions): attention
    runs over reused prefix KV plus the fresh suffix k/v. `prefix_kv` is
    {"k","v"[,"k_scale","v_scale"]} in cache storage dtype, stacked slab
    rows [L, B, 1, Pb, Hkv * Dh] or the paged pool's head-major view
    [L, B, Hkv, Pb, Dh] (told by the shapes; scales [L, B, Hkv, Pb] in
    both) — it rides the scan as xs next to the blocks, so each layer
    reads exactly its own slice (int8 caches dequantize per layer; the
    scales' relative error already sits below the int8 noise, see
    _quantize_kv). Fresh suffix k/v come back as ys in the layout the
    prefix came in (its caller scatters them where the prefix was
    read), stacked like _run_blocks_prefill's."""
    quantized = "k_scale" in prefix_kv
    Hkv = cfg.n_kv_heads
    side = Hkv // prefix_kv["k"].shape[2]  # heads in a row of the prefix

    def body(carry, xs):
        bp, pl = xs
        h = rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, bp, cfg, positions, inv_freq, tp=tp)
        # Attention wants the prefix as token-major columns
        # [B, Pb, Hkv, Dh] in front of the fresh suffix: a reshape of
        # slab rows, a transpose of the pool's head-major view.
        pk = _kv_tokens(pl["k"], Hkv).astype(q.dtype)
        pv = _kv_tokens(pl["v"], Hkv).astype(q.dtype)
        if quantized:
            # Barrier-pinned: the dequanted prefix must materialize to
            # ONE value before the concat so every consumer fusion reads
            # the same bits (certified by graftlint's num-barrier pass).
            pk = jax.lax.optimization_barrier(
                pk * pl["k_scale"].transpose(0, 2, 1)[..., None].astype(
                    q.dtype))
            pv = jax.lax.optimization_barrier(
                pv * pl["v_scale"].transpose(0, 2, 1)[..., None].astype(
                    q.dtype))
        k_all = jnp.concatenate([pk, k], axis=1)
        v_all = jnp.concatenate([pv, v], axis=1)
        if tp is not None:
            k_all, v_all = tp.heads(k_all), tp.heads(v_all)
        attn = gqa_attention(q, k_all, v_all, mask)
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        with jax.named_scope("attn/out"):
            x = carry + _qdot(attn, bp, "wo", cfg)
        x, aux = _mlp_res(x, bp, cfg, None, tp=tp)
        return x, (_kv_slab(k, side), _kv_slab(v, side), aux)

    x, (ks, vs, aux) = jax.lax.scan(body, x, (params["blocks"], prefix_kv))
    return x, {"k": ks, "v": vs}, jnp.mean(aux)


def _sparse_decode(cfg, cache, live, pos, spread: bool, ring: bool = False):
    """`ring`: the list of the sliding_attention layers, over their ring
    "kw" (decode_attention.schedule), else:

    The decode step's work list for ops/decode_attention, or None
    where its attention layers keep gqa_attention_decode's einsums over
    the whole layer: off a TPU, where the slab is `spread` over several
    devices (tensor parallelism: each device contracts its own lanes of
    the row, tp.rows; or a mesh the compiler partitions the program
    over, which it cannot do to a kernel) and for a slab the kernel
    cannot read (decode_attention.applies). Made once a step: every
    layer walks the same live slots to the same positions."""
    k = cache["kw" if ring else "k"]
    block = 0 if spread else decode_attention.applies(k, cfg.head_dim)
    if not block:
        return None
    if live is None:
        live = jnp.ones(pos.shape, bool)
    return decode_attention.schedule(live, pos, k.shape[3], block, ring)


def decode_kv_counts(cfg, cache, live, pos, spread=False) -> jnp.ndarray:
    """int32 [4]: KV tokens the attention layers of one decode step
    read, and KV tokens the slab holds for them (slots x window x
    attention layers); their ratio is the share of the slab a step
    touches. The einsums read all they hold; the kernel whole blocks of
    the live slots up to their positions (_sparse_decode). Then the K
    rows the step wrote (slab and rings) and slots x those layers: the
    scatter after the einsums writes a row of every slot, the kernel
    the live slots' (one whose position has reached the slab's end
    writes none)."""
    La, B, _, T, _ = cache["k"].shape
    held = jnp.asarray(La * B * T, jnp.int32)
    sched = _sparse_decode(cfg, cache, live, pos, spread)
    alive = jnp.ones(pos.shape, bool) if live is None else live
    written = jnp.asarray(La * B, jnp.int32) if sched is None else \
        La * jnp.sum(alive & (pos < T), dtype=jnp.int32)
    if "kw" in cache:
        return _decode_kv_counts_by_kind(cfg, cache, alive, pos, spread,
                                         sched, held, written)
    read = held if sched is None else La * decode_attention.tokens_read(sched)
    return jnp.stack([read, held, written, jnp.asarray(La * B, jnp.int32)])


def _decode_kv_counts_by_kind(cfg, cache, alive, pos, spread, sched, held,
                              written):
    """int32 [9] for a stack with sliding_attention layers: decode_kv_counts'
    four over both kinds, then the window layers' tokens read and held
    (their rings: slots x window x layers), what those layers' live rows
    would have read without a window (their positions), and the full
    layers' read and held."""
    La, Lw, B, W = (cache["k"].shape[0], *cache["kw"].shape[:2],
                    cache["kw"].shape[3])
    read = held if sched is None else \
        La * decode_attention.tokens_read(sched)
    w_held = jnp.asarray(Lw * B * W, jnp.int32)
    sched_w = _sparse_decode(cfg, cache, alive, pos, spread, ring=True)
    w_read = w_held if sched_w is None else \
        Lw * decode_attention.tokens_read(sched_w)
    w_written = jnp.asarray(Lw * B, jnp.int32) if sched_w is None else \
        Lw * jnp.sum(alive, dtype=jnp.int32)
    unwindowed = Lw * jnp.sum(jnp.where(alive, pos, 0)).astype(jnp.int32)
    return jnp.stack([read + w_read, held + w_held, written + w_written,
                      jnp.asarray((La + Lw) * B, jnp.int32), w_read, w_held,
                      unwindowed, read, held])


def _run_blocks_decode(params, x, cfg, positions, inv_freq, pos, cache,
                       act_spec=None, tp=None, live=None, spread=False):
    """Layer scan for DECODE: the cache is read PRE-write (attention
    handles the current token via an exact fresh column). Two ways to
    read and write it, told by what is there to see (_sparse_decode):
    on a TPU, with the dense slab whole on one device, the scan rides
    on the layer's INDEX and carries K and V whole; ops/decode_attention
    reads the live rows' tokens out of the slab where it lies and writes
    the fresh token's row of the live slots into it (`live`: the slots
    that hold a request; None = every slot; a dead slot's rows keep what
    they held). Otherwise the cache rides the scan as xs — read-only
    per-layer slices fuse into the attention einsums (GSPMD-shardable),
    unlike slice-reads of a just-scattered carry — every slot's whole
    window is scored and masked, and all L layers' fresh k/v are written
    back AFTER the scan in one batched scatter over every slot. (The
    Pallas decode kernel of rounds 3-4 that lost to these einsums, 16.3
    vs 8.1 ms/step, read every block of 160 slots ALL live: it priced
    reading everything by a kernel, where the einsums ride at 85-90 % of
    the HBM peak. The kernel here wins by what it does not read;
    PERF.md section 5 has its table by occupancy.)

    A stack with experts that lies whole on one device (no `tp`, not
    `spread`) computes its sparse block by token -> expert dispatch
    (_dispatched_experts) for the `live` rows, so a step reads the
    weights of the experts those rows chose and of no other; aux is then
    the routing counters summed over the layers (routing_width).

    Returns (x, new_cache, aux)."""
    quantized = cfg.kv_cache_dtype == "int8"
    Smax = cache["k"].shape[3]
    mask_lt = jnp.arange(Smax)[None, None, :] < pos[:, None, None]
    side = kv_heads_per_row(cfg)
    sched = _sparse_decode(cfg, cache, live, pos, spread or tp is not None)
    blocks, experts = _dispatched_experts(
        params["blocks"], cfg,
        tp is None and act_spec is None and not spread)
    # the one token a slot holds is a live row where the slot is
    routed = None if experts is None or live is None else live[:, None]

    def einsums(q, k, v, cl):
        ck, cv = cl["k"], cl["v"]
        if tp is not None:
            # Each device contracts over its own head group's lanes of
            # the row, never over another's (tp_sharding module doc).
            ck, cv = tp.rows(ck), tp.rows(cv)
        return gqa_attention_decode(
            q, ck, cv, k, v, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"),
        )

    def stored(k, v):
        """The fresh token's rows as the slab stores them."""
        if quantized:
            kq, ksc = _quantize_kv(k)
            vq, vsc = _quantize_kv(v)
            return {"k": _kv_rows(kq, side)[:, 0],
                    "v": _kv_rows(vq, side)[:, 0],
                    "k_scale": ksc[:, 0], "v_scale": vsc[:, 0]}
        dt = cache["k"].dtype
        return {"k": _kv_rows(k, side)[:, 0].astype(dt),
                "v": _kv_rows(v, side)[:, 0].astype(dt)}

    def body(carry, xs):
        x, *slab = carry  # K and V whole, where the kernel writes them
        bp, cl, *layer = xs
        h = rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, bp, cfg, positions, inv_freq, tp=tp)
        if sched is not None:
            fresh = stored(k, v)
            attn, *slab = decode_attention.attend(
                q, k, v, {**cache, "k": slab[0], "v": slab[1]}, cl, sched,
                fresh if quantized else None)
            fresh = {key: val for key, val in fresh.items()
                     if key not in ("k", "v")}  # the scales' where, below
        else:
            attn = einsums(q, k, v, cl)
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        with jax.named_scope("attn/out"):
            x = x + _qdot(attn, bp, "wo", cfg)
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        x, aux = _mlp_res(x, bp, cfg, act_spec, tp=tp, experts=experts,
                          layer=layer[0] if layer else None, live=routed)
        if sched is None:
            fresh = stored(k, v)
        return (x, *slab), (fresh, aux)

    xs = (blocks,
          cache if sched is None else jnp.arange(cache["k"].shape[0]))
    if experts is not None:
        xs += (jnp.arange(cfg.n_layers),)
    slab = () if sched is None else (cache["k"], cache["v"])
    (x, *slab), (fresh, aux) = jax.lax.scan(body, (x, *slab), xs)
    rows = jnp.arange(pos.shape[0])
    # k / v: one scatter covers all layers, with layer, row and position
    # all INDICES of it and only the token's row [1, Hkv*Dh] its window:
    # advanced indices land in front, so the update operand is fresh[key]
    # [L, B, ...] transposed to [B, L, ...]. With the layer axis a window
    # dimension (`.at[:, rows, :, pos]`) the TPU compiler carried the
    # slab layer-minor through the chunk's steps and relaid the whole of
    # it out for the layer scan on every step (kv_heads_per_row).
    # The int8 scales [L, B, Hkv, T] are T-minor, so a token's are single
    # elements T apart: a select over the array (1/64 of the slab) writes
    # them. Scattered, the v5e compiler kept k_scale in fast memory
    # through the chunk and moved all of it out and back in inside every
    # layer: 2.7 ms of a 22.2 ms step (PERF.md section 6, PR 32).
    layers = jnp.arange(cache["k"].shape[0])[None, :]
    here = (jnp.arange(Smax)[None, :] == pos[:, None])[None, :, None, :]
    with jax.named_scope("attn/cache_update"):
        new_cache = dict(zip(("k", "v"), slab))  # the kernel wrote them
        for key in fresh:
            new_cache[key] = cache[key].at[
                layers, rows[:, None], :, pos[:, None]].set(
                jnp.swapaxes(fresh[key], 0, 1), unique_indices=True) \
                if key in ("k", "v") else \
                jnp.where(here, fresh[key][..., None], cache[key])
    aux = jnp.mean(aux) if experts is None else jnp.sum(aux, axis=0)
    return x, new_cache, aux


@jax.named_scope("lm_head")
def _logits(params, x, cfg):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if "lm_head" not in params:
        # Tied embeddings: contract against embed's OWN layout ("vd") —
        # materializing embed.T would move the whole vocab matrix per
        # decode step (measured 2.3ms/step for a 131MB bf16 table on v5e).
        return _scaled(jnp.einsum(
            "bsd,vd->bsv", x, _w(params, "embed", x.dtype),
            preferred_element_type=jnp.float32,
        ), cfg.logits_mult)
    return _scaled(jnp.einsum(
        "bsd,dv->bsv", x, _w(params, "lm_head", x.dtype),
        preferred_element_type=jnp.float32,
    ), cfg.logits_mult)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _full_mask(cfg: ModelConfig, B: int, S: int) -> jnp.ndarray:
    """[B, S, S] bool of attention over whole sequences from position 0:
    causal, or block-causal under cfg.gen_block (position i sees its
    whole own block and every block before it)."""
    at = jnp.arange(S) // cfg.gen_block if cfg.gen_block else jnp.arange(S)
    return jnp.broadcast_to(at[None, :] <= at[:, None], (B, S, S))


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: ModelConfig,
    act_spec: Optional[P] = None,
    remat: bool = False,
    return_aux: bool = False,
    ring_mesh=None,
):
    """Full-sequence teacher-forced logits [B, S, V] (training / scoring).
    With return_aux=True also returns {"moe_lb_loss": scalar} (zero for
    dense configs). `ring_mesh` activates ring attention over 'sp' when
    cfg.attn_impl == "ring" (long-context path)."""
    B, S = tokens.shape
    x = _scaled(_embed_rows(params, tokens, _dtype(cfg)), cfg.embed_mult)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    inv_freq = rope_frequencies(cfg)
    mask = None if cfg.n_window_layers else \
        jnp.tril(jnp.ones((S, S), dtype=bool))[None].repeat(B, 0)
    if cfg.gen_block:
        mask = _full_mask(cfg, B, S)
    if cfg.patterned:
        refuse_patterned(cfg, "sharded or rematerialised forward",
                          act_spec is not None or remat
                          or ring_mesh is not None)
        x, _, _ = _run_patterned_full(params, x, cfg, positions, inv_freq,
                                      mask, None)
        logits = _logits(params, x, cfg)
        if return_aux:
            return logits, {"moe_lb_loss": jnp.zeros((), jnp.float32)}
        return logits
    x, _, aux = _run_blocks(params, x, cfg, positions, inv_freq, mask,
                            act_spec=act_spec, remat=remat,
                            ring_mesh=ring_mesh)
    logits = _logits(params, x, cfg)
    if return_aux:
        return logits, {"moe_lb_loss": aux}
    return logits


class CacheEntry(NamedTuple):
    """One array of the per-slot cache: what it holds ("kv" = keys,
    values and their int8 scales, one position per token; "kv_window" =
    the keys and values of the layers that attend inside a window, a
    ring of the window's length a slot; "conv" = the
    short convolution's last inputs, "ssm" = a Mamba-2 mixer's state and
    "ssm_conv" = its convolution's last inputs, each a fixed size per
    slot), its shape
    [layers of that kind, batch, ...], dtype, fill value, and the axis
    that runs over token positions (None: no such axis)."""
    kind: str
    shape: Tuple[int, ...]
    dtype: Any
    fill: float
    time_axis: Optional[int]


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, CacheEntry]:
    """THE description of the per-slot cache, by kind. init_cache builds
    from it; the engine's admission scatter (cache_scatter_slots), the
    HBM ledger and /metadata read it (cache_bytes); cost_model.py's
    closed forms (kv_bytes_per_token, state_bytes_per_slot: no JAX there)
    are held equal to it by tests/test_patterned.py.

    KV is one row a token, [La, B, 1, T, Hkv * Dh], over the La layers
    that hold KV: every layer of a homogeneous stack, the attention
    layers of a patterned one, among them those that run attention and a
    Mamba-2 mixer side by side and so hold an SSM state as well, each
    counted once under either kind (kv_heads_per_row; five axes with T at 3,
    so that a head-major array indexes alike). The int8 scales are per
    (token, head), [La, B, Hkv, T]: T-minor, because a trailing axis of
    Hkv = 8 would be padded to the TPU's 128 lanes, and small enough
    (1/64 of the slab) that how they are carried costs little.

    Why a row a token (the v5e's compiled HLO, PERF.md section 6, PR 28
    and PR 32): it is the layout BOTH sides of a decode chunk want. The
    step writes a token's heads together, in ONE batched scatter for all
    layers after the layer scan (_run_blocks_decode: the cache is read
    pre-write, gqa_attention_decode); the scan reads a layer at a time.
    Stored head-major [La, B, Hkv, T, Dh] the compiler kept two layouts
    of the slab, one for each side, and copied the whole of it between
    them on every chunk and a layer's slice on every step. Stored
    token-major and VIEWED per head ([B, T, Hkv, Dh] transposed for the
    einsums) it copied every layer's slice on every step instead. The
    attention now contracts over the row as stored (_beside), so
    neither copy is left.

    The conv state is [Lc, B, conv_kernel - 1, D] over the Lc conv
    layers of a patterned stack: the gated inputs u of the slot's last
    conv_kernel - 1 positions, oldest first.

    The SSM state is [Lm, B, ssm_heads, ssm_head_dim, ssm_state] over the
    Lm Mamba-2 layers, FLOAT32 whatever the compute dtype: the recurrence
    accumulates into it over the whole context. It is three orders larger
    than a conv state (2 MB a slot and layer at 64 x 64 x 128, 4 MB at
    32 x 128 x 256) and every
    decode step reads and writes all of it, so the step updates it in
    place (_run_patterned_decode carries it whole through the layer
    scan). "ssm_conv" is [Lm, B, conv_kernel - 1, ssm_conv_dim]: the
    mixer's [x | B | C] inputs of the last conv_kernel - 1 positions.

    "kw" / "vw" (kind "kv_window") are the keys and values of the Lw
    sliding_attention layers, [Lw, B, 1, W, Hkv * Dh] with W =
    cfg.sliding_window whatever max_len: a RING, position p at row
    p % W, so a slot holds the W newest positions and nothing older. W
    rows are enough, with none added for a decode chunk's steps: every
    step of a chunk writes its own token after its own read
    (_run_patterned_decode), and the one row a step reads too many, the
    row it is about to overwrite, which holds position p - W, is masked.
    It has no token axis an admission could cut at a bucket's width: a
    prefill hands over the ring whole (_ring_rows) and the scatter
    replaces a slot's (time_axis None, as the fixed-size states)."""
    side = kv_heads_per_row(cfg)
    shape = (cfg.n_attn_layers, batch, cfg.n_kv_heads // side, max_len,
             cfg.head_dim * side)
    spec: Dict[str, CacheEntry] = {}
    if cfg.kv_cache_dtype == "int8":
        assert dtype is None, (
            "dtype override is meaningless for an int8 cache (slots are "
            "int8 + f32 scales by construction)"
        )
        sshape = (cfg.n_attn_layers, batch, cfg.n_kv_heads, max_len)
        spec["k"] = CacheEntry("kv", shape, jnp.int8, 0, 3)
        spec["v"] = CacheEntry("kv", shape, jnp.int8, 0, 3)
        # Scales min-clamped at init so a read of a never-written slot
        # dequantizes to exact zeros (0 * 1e-8), like the bf16 cache.
        # bf16 storage: see _quantize_kv.
        spec["k_scale"] = CacheEntry("kv", sshape, jnp.bfloat16, 1e-8, 3)
        spec["v_scale"] = CacheEntry("kv", sshape, jnp.bfloat16, 1e-8, 3)
    else:
        dt = dtype or _dtype(cfg)
        spec["k"] = CacheEntry("kv", shape, dt, 0, 3)
        spec["v"] = CacheEntry("kv", shape, dt, 0, 3)
    if cfg.n_window_layers:
        ring = (cfg.n_window_layers, batch, 1, cfg.sliding_window,
                cfg.head_dim * cfg.n_kv_heads)
        spec["kw"] = CacheEntry("kv_window", ring, dtype or _dtype(cfg), 0,
                                None)
        spec["vw"] = CacheEntry("kv_window", ring, dtype or _dtype(cfg), 0,
                                None)
    if cfg.n_conv_layers:
        spec["conv"] = CacheEntry(
            "conv",
            (cfg.n_conv_layers, batch, cfg.conv_kernel - 1, cfg.d_model),
            dtype or _dtype(cfg), 0, None)
    if cfg.n_mamba_layers:
        spec["ssm"] = CacheEntry(
            "ssm",
            (cfg.n_mamba_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
             cfg.ssm_state), jnp.float32, 0, None)
        spec["ssm_conv"] = CacheEntry(
            "ssm_conv",
            (cfg.n_mamba_layers, batch, cfg.conv_kernel - 1,
             cfg.ssm_conv_dim), dtype or _dtype(cfg), 0, None)
    return spec


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Cache:
    """The per-slot cache cache_spec describes, empty."""
    return {
        key: (jnp.zeros(e.shape, e.dtype) if e.fill == 0
              else jnp.full(e.shape, e.fill, e.dtype))
        for key, e in cache_spec(cfg, batch, max_len, dtype).items()
    }


def cache_bytes(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, int]:
    """Bytes of the per-slot cache by kind ({"kv": ..., "kv_window": ...,
    "conv": ..., "ssm": ..., "ssm_conv": ...}: the kinds the stack has)."""
    out: Dict[str, int] = {}
    for e in cache_spec(cfg, batch, max_len).values():
        n = jnp.dtype(e.dtype).itemsize
        for d in e.shape:
            n *= d
        out[e.kind] = out.get(e.kind, 0) + n
    return out


def cache_scatter_slots(cfg: ModelConfig, cache: Cache, sub: Cache,
                        slots: jnp.ndarray, width: int) -> Cache:
    """Admission: the group's freshly prefilled cache `sub` ([*, G, ...],
    `width` positions) into rows `slots` of the slab, array by array as
    cache_spec describes them. Arrays with a token axis (k/v
    [L, B, 1, T, Hkv*Dh] + scales [L, B, Hkv, T]: T at dim 3 of k/v and
    trailing on the scales, so one indexing expression covers them all)
    take the first `width` positions; a fixed-size state (conv, SSM) is
    overwritten whole, so a reused slot keeps nothing of its last
    request."""
    spec = cache_spec(cfg, 1, 1)
    out = {}
    for key, arr in cache.items():
        upd = sub[key].astype(arr.dtype)
        if spec[key].time_axis is None:
            out[key] = arr.at[:, slots].set(upd)
        else:
            out[key] = arr.at[:, slots, :, :width].set(upd)
    return out


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block: int) -> Cache:
    """Paged KV pool: HEAD-major [L, NB, Hkv, block, Dh] (scales
    [L, NB, Hkv, block]) — the dense slab's [B, T] plane cut into NB
    fixed-size blocks of `block` tokens, addressed through per-slot
    int32 block tables instead of a contiguous slice. Layout inside a
    block is identical to the slab, so a gather through the table
    reproduces the dense cache bit-for-bit (paged_gather_kv) and the
    attention math is shared with the dense path."""
    refuse_patterned(cfg, "the paged KV pool")
    shape = (cfg.n_layers, num_blocks, cfg.n_kv_heads, block, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        sshape = shape[:-1]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            # Same min-clamp as init_cache: unwritten slots dequantize to
            # exact zeros, keeping garbage finite (the hard t < pos mask
            # zeroes its weight either way).
            "k_scale": jnp.full(sshape, 1e-8, jnp.bfloat16),
            "v_scale": jnp.full(sshape, 1e-8, jnp.bfloat16),
        }
    dt = _dtype(cfg)
    return {"k": jnp.zeros(shape, dtype=dt), "v": jnp.zeros(shape, dtype=dt)}


def paged_gather_kv(pool_layer: Cache, table: jnp.ndarray) -> Cache:
    """Gather ONE layer's K/V dense view through block tables.

    pool_layer: {"k","v"[,scales]} [NB, Hkv, block, (Dh)];
    table: [B, T // block] int32 block ids. Returns [B, Hkv, T, (Dh)]
    arrays elementwise IDENTICAL to the dense slab's layer slice at
    every written position — a pure gather, no arithmetic — so the
    shared attention kernels produce bit-identical outputs (unwritten
    positions differ only where the strict t < pos mask already forces
    exactly-zero weight)."""
    out = {}
    for key, arr in pool_layer.items():
        g = arr[table]  # [B, nb, Hkv, block, (Dh)]
        g = jnp.moveaxis(g, 1, 2)  # [B, Hkv, nb, block, (Dh)]
        shape = g.shape
        out[key] = g.reshape(
            shape[0], shape[1], shape[2] * shape[3], *shape[4:]
        )
    return out


def paged_prefix_view(pool: Cache, table: jnp.ndarray, nb: int) -> Cache:
    """Stacked-layer dense view of the first `nb` table blocks:
    pool [L, NB, Hkv, block, (Dh)] + table [B, >=nb] ->
    {key: [L, B, Hkv, nb*block, (Dh)]} — the paged stand-in for the
    dense engine's resident-prefix slice cache[:, slots, :, :W]."""
    tb = table[:, :nb]
    out = {}
    for key, arr in pool.items():
        g = arr[:, tb]  # [L, B, nb, Hkv, block, (Dh)]
        g = jnp.moveaxis(g, 2, 3)  # [L, B, Hkv, nb, block, (Dh)]
        shape = g.shape
        out[key] = g.reshape(
            shape[0], shape[1], shape[2], shape[3] * shape[4], *shape[5:]
        )
    return out


def paged_scatter_tokens(
    pool: Cache, writes: Cache, table: jnp.ndarray, spos: jnp.ndarray
) -> Cache:
    """Scatter per-token KV writes through block tables.

    writes: {key: [L, B, Hkv, S, (Dh)]} landing at absolute positions
    spos [B, S]; table [B, NBs]. The flat position decomposes into
    (block id via the table, offset inside the block); advanced indices
    on dims 1 and 3 land in front exactly like the dense engine's
    cache[:, slots[:, None], :, spos] scatter, so the update operand is
    the same moveaxis. Rows whose table entry is 0 (unallocated tail of
    a padded bucket) write into the reserved trash block — same
    harmless-garbage discipline as the dense slab's pad writes, hence
    no unique_indices claim (trash collisions are fine). Positions past
    the table's window are routed to the trash block explicitly: the
    dense scatter DROPS out-of-bounds rows, but take_along_axis CLAMPS,
    which would silently corrupt the row's last real block."""
    block = pool["k"].shape[3]
    idx = spos // block  # [B, S]
    bids = jnp.where(
        idx < table.shape[1],
        jnp.take_along_axis(
            table, jnp.minimum(idx, table.shape[1] - 1), axis=1
        ),
        0,
    )
    offs = spos % block
    return {
        key: pool[key].at[:, bids, :, offs].set(
            jnp.moveaxis(writes[key], (1, 3), (0, 1)).astype(pool[key].dtype)
        )
        for key in pool
    }


def _run_blocks_decode_paged(params, x, cfg, positions, inv_freq, pos,
                             pool, table, tp=None):
    """Paged twin of _run_blocks_decode: per layer, K/V are GATHERED
    through the block table into the dense head-major view and fed to
    the SAME gqa_attention_decode — a pure relayout, so greedy decode is
    bit-identical to the slab path. The pool rides the scan as xs (read-
    only per-layer slices, like the dense cache) and all L layers' fresh
    k/v land after the scan in one batched scatter at the flat
    (table[pos // block], pos % block) address."""
    quantized = cfg.kv_cache_dtype == "int8"
    block = pool["k"].shape[3]
    Smax = table.shape[1] * block
    mask_lt = jnp.arange(Smax)[None, None, :] < pos[:, None, None]

    def body(carry, xs):
        bp, pl = xs
        h = rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, bp, cfg, positions, inv_freq, tp=tp)
        cl = paged_gather_kv(pl, table)
        attn = gqa_attention_decode(
            q, cl["k"], cl["v"], k, v, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"),
        )
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        with jax.named_scope("attn/out"):
            x = carry + _qdot(attn, bp, "wo", cfg)
        x, aux = _mlp_res(x, bp, cfg, None, tp=tp)
        if quantized:
            kq, ksc = _quantize_kv(k[:, 0])
            vq, vsc = _quantize_kv(v[:, 0])
            fresh = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            dt = pool["k"].dtype
            fresh = {"k": k[:, 0].astype(dt), "v": v[:, 0].astype(dt)}
        return x, (fresh, aux)

    x, (fresh, aux) = jax.lax.scan(body, x, (params["blocks"], pool))
    rows = jnp.arange(pos.shape[0])
    idx = pos // block
    # pos can sit AT Smax for rows admitted with a full-window prompt
    # (first_done, frozen): the dense scatter drops that OOB write, so
    # the paged one must route it to trash — plain indexing would clamp
    # into the row's last (possibly trie-shared) block.
    bid = jnp.where(
        idx < table.shape[1],
        table[rows, jnp.minimum(idx, table.shape[1] - 1)],
        0,
    )
    off = pos % block
    # Same one-scatter-for-all-layers shape as the dense write: advanced
    # indices (bid on dim 1, off on dim 3) land in front, update operand
    # is fresh[key] [L, B, Hkv, (Dh)] with B swapped forward. Inactive
    # rows write through table entry 0 (trash) — collisions allowed.
    with jax.named_scope("attn/cache_update"):
        new_pool = {
            key: pool[key].at[:, bid, :, off].set(
                jnp.swapaxes(fresh[key], 0, 1)
            )
            for key in pool
        }
    return x, new_pool, jnp.mean(aux)


def paged_decode_step(
    params: Params,
    token: jnp.ndarray,  # [B] int32 current tokens
    pos: jnp.ndarray,  # [B] int32 positions to write at
    pool: Cache,  # [L, NB, Hkv, block, (Dh)] global block pool
    table: jnp.ndarray,  # [B, Smax // block] int32 block tables
    cfg: ModelConfig,
    tp=None,
) -> Tuple[jnp.ndarray, Cache]:
    """One autoregressive step over the paged pool. Returns
    (logits [B, V], updated pool) — the block-table twin of decode_step,
    bit-identical for greedy outputs. `tp` (tp_sharding.TpHints) runs
    the step SPMD over the 'tp' mesh axis, still bit-identical."""
    refuse_patterned(cfg, "paged decode")
    x = _embed_rows(params, token, _dtype(cfg))[:, None, :]
    positions = pos[:, None]
    inv_freq = rope_frequencies(cfg)
    x, pool, _ = _run_blocks_decode_paged(params, x, cfg, positions,
                                          inv_freq, pos, pool, table,
                                          tp=tp)
    return _logits(params, x, cfg)[:, 0], pool


def prefill(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] right-padded prompts
    prompt_lens: jnp.ndarray,  # [B] true lengths
    cache: Cache,
    cfg: ModelConfig,
    ring_mesh=None,
    tp=None,
    spread: bool = False,  # the program lies over several devices
) -> Tuple[jnp.ndarray, Cache]:
    """Run prompts through the model, filling cache slots [0, S).
    Returns (next-token logits [B, V] taken at each row's last real token,
    updated cache). `ring_mesh` + cfg.attn_impl=="ring": context-parallel
    prefill — the prompt's sequence axis shards over 'sp' and attention
    runs as a ring (long-prompt admissions scale across the slice; the
    decode cache stays T-unsharded, GSPMD gathers the shards at the
    cache write). A stack with experts, whole on one device, sends the
    prompts' own tokens to the experts they choose and the right-padding
    nowhere (_run_blocks_prefill); `spread`, tp and the ring keep
    moe_block."""
    B, S = tokens.shape
    if cfg.patterned:
        refuse_patterned(cfg, "tensor-parallel or ring prefill",
                          tp is not None or ring_mesh is not None)
        return _prefill_patterned(params, tokens, prompt_lens, cache, cfg)
    x = _embed_rows(params, tokens, _dtype(cfg))
    use_ring = ring_mesh is not None and cfg.attn_impl == "ring" and S > 1
    if use_ring:
        # Pin the activation sequence axis to 'sp' so the per-layer qkv
        # projections and MLP also run sequence-sharded, not just the
        # ring attention itself.
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(
                ring_mesh, P(None, "sp", None)
            )
        )
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    inv_freq = rope_frequencies(cfg)
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None].repeat(B, 0)
    Smax = cache["k"].shape[3]
    # Attention never reads `cache` — prefill starts at position 0, so the
    # fresh tokens are the entire visible window (_run_blocks_prefill).
    # The stacked ys land in the cache in one update per array.
    x, kv, _ = _run_blocks_prefill(params, x, cfg, positions, inv_freq, mask,
                                   ring_mesh=ring_mesh if use_ring else None,
                                   tp=tp, plens=prompt_lens, spread=spread)
    with jax.named_scope("attn/cache_update"):
        writes = kv_writes(kv, cache, cfg)
        if S == Smax:
            cache = writes
        else:
            # T is dim 3 of k/v and the trailing dim of the scales, so
            # one indexing expression covers every cache array.
            cache = {
                key: cache[key].at[:, :, :, :S].set(writes[key])
                for key in cache
            }
    # Gather each row's last real hidden state BEFORE the vocab projection:
    # projecting all S positions would materialize [B,S,V] f32 (~4 GB for an
    # 8k-prompt llama3-8b bucket) only to keep one row.
    last = jnp.clip(prompt_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B,1,D]
    return _logits(params, x_last, cfg)[:, 0], cache


def prefill_with_prefix(
    params: Params,
    tokens: jnp.ndarray,  # [B, Sq] right-padded SUFFIX tokens
    prompt_lens: jnp.ndarray,  # [B] FULL prompt lengths
    prefix_kv: Cache,  # reused prefix, cache dtype: slab rows
    # [L, B, 1, Pb, Hkv*Dh] or the pool's view [L, B, Hkv, Pb, Dh]
    prefix_lens: jnp.ndarray,  # [B] true prefix lengths (<= Pb)
    cfg: ModelConfig,
    tp=None,
) -> Tuple[jnp.ndarray, Cache]:
    """Prefill that RESUMES at a position offset: runs only the uncached
    suffix of each prompt, attending to already-computed prefix KV
    (prefix-cache admissions, servers/engine.py).

    RoPE is position-absolute, so suffix q/k rotate at their true
    positions (prefix_len + i) and the reused prefix KV — rotated at its
    own absolute positions when first computed — lines up exactly with a
    cold full prefill. The mask exposes prefix columns t < prefix_len
    plus the causal triangle over the suffix; padded prefix/suffix
    columns are masked or land past each row's real tokens, where the
    decode-side strict t < pos mask guarantees write-before-read.

    Returns (next-token logits [B, V] at each row's last real suffix
    token, fresh suffix KV {"k","v"} bf16, stacked in prefix_kv's layout:
    slab rows [L, B, 1, Sq, Hkv*Dh] or by head [L, B, Hkv, Sq, Dh] — the
    caller scatters prefix and suffix into the slot cache)."""
    refuse_patterned(cfg, "suffix prefill over a reused prefix")
    B, Sq = tokens.shape
    Pb = prefix_kv["k"].shape[3]
    x = _embed_rows(params, tokens, _dtype(cfg))
    positions = prefix_lens[:, None] + jnp.arange(Sq)[None, :]
    inv_freq = rope_frequencies(cfg)
    pmask = jnp.broadcast_to(
        jnp.arange(Pb)[None, None, :] < prefix_lens[:, None, None],
        (B, Sq, Pb),
    )
    smask = jnp.broadcast_to(
        jnp.tril(jnp.ones((Sq, Sq), dtype=bool))[None], (B, Sq, Sq)
    )
    mask = jnp.concatenate([pmask, smask], axis=2)
    x, kv, _ = _run_blocks_prefill_prefix(
        params, x, cfg, positions, inv_freq, mask, prefix_kv, tp=tp
    )
    # Last real token of the SUFFIX (admissions cap the reused prefix at
    # prompt_len - 1, so there is always at least one suffix token).
    last = jnp.clip(prompt_lens - prefix_lens - 1, 0, Sq - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return _logits(params, x_last, cfg)[:, 0], kv


def decode_step(
    params: Params,
    token: jnp.ndarray,  # [B] int32 current tokens
    pos: jnp.ndarray,  # [B] int32 positions to write at
    cache: Cache,
    cfg: ModelConfig,
    tp=None,
    live: Optional[jnp.ndarray] = None,  # [B] bool: rows that decode
    return_routing: bool = False,
    spread: bool = False,  # the cache lies over several devices
):
    """One autoregressive step. Returns (logits [B, V], updated cache).

    `live` tells which rows hold a request (None: every row): a
    patterned stack's sparse layers route the others to no expert, so a
    step reads the weights of the experts its live rows select and no
    more, and on a TPU the attention layers read the live rows' tokens
    of the slab and nothing of the others (_sparse_decode), whose
    output is then their fresh token's value alone; `spread` says that
    the cache lies over several devices (tp says so too), where the
    einsums stay.
    return_routing adds a third value, int32 [routing_width(cfg)]:
    sparse layers run, distinct experts they read summed over those
    layers, (row, expert) assignments (zeros for a stack without
    dispatch, and where a homogeneous stack's experts keep moe_block:
    tp, spread); a stack that holds a share of its experts or has Mamba-2
    layers adds the assignments to experts held here and the Mamba-2
    layers run."""
    x = _scaled(_embed_rows(params, token, _dtype(cfg)),
                cfg.embed_mult)[:, None, :]  # [B,1,D]
    positions = pos[:, None]
    inv_freq = rope_frequencies(cfg)
    routing = jnp.zeros((routing_width(cfg),), jnp.int32)
    if cfg.patterned:
        refuse_patterned(cfg, "tensor-parallel decode", tp is not None)
        x, cache, routing = _run_patterned_decode(
            params, x, cfg, positions, inv_freq, pos, cache, live, spread)
    else:
        x, cache, aux = _run_blocks_decode(params, x, cfg, positions,
                                           inv_freq, pos, cache, tp=tp,
                                           live=live, spread=spread)
        if aux.ndim:  # the sparse block ran by dispatch: its counters
            routing = aux
    logits = _logits(params, x, cfg)[:, 0]
    if return_routing:
        return logits, cache, routing
    return logits, cache


# ---------------------------------------------------------------------------
# The patterned stack (cfg.layer_types)
# ---------------------------------------------------------------------------
#
# Layers differ in two ways: the operator (gated short convolution,
# attention, or attention and a Mamba-2 mixer that read the same normed
# input and are summed, cfg.layer_types) and the feed-forward (dense SwiGLU for the
# first cfg.n_dense_layers, then the sparse block by token -> expert
# dispatch). The layer list is cut into SEGMENTS, each a period of layer
# kinds repeated R times (layer_plan), and each segment is ONE lax.scan
# over its R repeats with the period unrolled inside the body: compile
# time is flat in depth, as with the homogeneous scan. Parameters are
# stored the way they are scanned, params["segments"][s][j] = the stacked
# [R, ...] weights of position j of segment s's period, so that no
# expert matrix is ever sliced or copied inside a program. The cache is
# by kind (cache_spec): "k"/"v" over the attention layers in layer order,
# "conv" over the conv layers in layer order, "ssm" / "ssm_conv" over the
# layers that hold a Mamba-2 mixer in layer order (an "attention_mamba"
# layer has a row in "k"/"v" AND one in "ssm" / "ssm_conv").
#
# A stack of SINGLE-BLOCK layers (config.SINGLE_OPS) is the same plan with
# other kinds: each layer is x + block(RMSNorm(x)) with the block a
# Mamba-2 mixer ("mamba"), an attention ("attention") or the sparse
# feed-forward ("moe": routed experts, of which this program may hold a
# share, plus a shared expert) and has no feed-forward of its own.


class Segment(NamedTuple):
    kinds: Tuple[Tuple[str, bool], ...]  # (operator, sparse ff) per position
    reps: int
    first_layer: int
    attn_start: int  # index of its first attention layer among those
    conv_start: int  # and of its first conv layer
    ssm_start: int = 0  # and of its first layer with a Mamba-2 mixer
    window_start: int = 0  # and of its first sliding_attention layer


_MAX_PERIOD = 8
# cache arrays without a token axis: an admission replaces a slot's whole
_FIXED_STATE = ("conv", "ssm", "ssm_conv", "kw", "vw")


def _count_ops(kinds, *ops) -> int:
    return sum(1 for op, _ in kinds if op in ops)


def routing_width(cfg: ModelConfig) -> int:
    """Length of the counters a decode step returns (decode_step):
    [sparse layers run, experts read, assignments] and, for a stack that
    holds a share of its experts or has Mamba-2 layers, [assignments to
    experts held here, Mamba-2 layers run] after them."""
    return 5 if cfg.n_experts_held or cfg.n_mamba_layers else 3


@functools.lru_cache(maxsize=None)
def layer_plan(cfg: ModelConfig) -> Tuple[Segment, ...]:
    """The layer list as (period, repeats) segments, greedily from the
    front: at each layer the period (up to _MAX_PERIOD kinds) that
    repeats at least twice and covers most layers, else one layer."""
    kinds = [(cfg.op_kind(l), cfg.ff_sparse(l)) for l in range(cfg.n_layers)]
    plan, i, n_attn, n_conv, n_ssm, n_win = [], 0, 0, 0, 0, 0
    while i < len(kinds):
        best_p, best_r = 1, 1
        for p in range(1, min(_MAX_PERIOD, len(kinds) - i) + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r >= 2 and p * r > best_p * best_r:
                best_p, best_r = p, r
        period = tuple(kinds[i:i + best_p])
        plan.append(Segment(period, best_r, i, n_attn, n_conv, n_ssm, n_win))
        n_win += best_r * _count_ops(period, *WINDOW_OPS)
        n_attn += best_r * _count_ops(period, *KV_OPS)
        n_conv += best_r * _count_ops(period, OP_CONV)
        n_ssm += best_r * _count_ops(period, *SSM_OPS)
        i += best_p * best_r
    return tuple(plan)


def fixed_state_names(cfg: ModelConfig) -> str:
    """The fixed-size per-slot state this stack holds, in words."""
    if cfg.n_window_layers:
        return "the sliding_attention layers' ring of keys and values"
    if OP_ATTN_MAMBA in cfg.layer_types:
        return ("the attention_mamba layers' SSM and conv state (a Mamba-2 "
                "mixer beside the attention in the same layer)")
    if cfg.n_mamba_layers:
        return "the Mamba-2 layers' SSM and conv state"
    return "the conv layers' state"


def refuse_patterned(cfg: ModelConfig, what: str, when: bool = True):
    if cfg.patterned and when:
        raise NotImplementedError(
            f"{what} does not know the patterned stack (layer_types): it "
            f"would run without {fixed_state_names(cfg)}")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _seeded(key, shape, scale, dtype):
    """One weight, made in one fused program (the float32 draws of a
    stacked expert matrix are never held whole beside it)."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _init_params_patterned(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded weights of a patterned stack, leaf by leaf (each its own
    small program, so building the tree never needs more than the tree
    plus one leaf). Matrices are drawn at fan_in ** -0.5 (0.022 at
    d_model 2048, the usual 0.02; at a test's 64 it keeps the layers'
    share of the residual stream what it is at real width, where a fixed
    0.02 would leave a tied head reading little but the input token's
    own embedding), projections back into the residual stream damped by
    (2 L) ** -0.5. Layer norms are ones; the router's bias is drawn
    non-zero (a zero bias would leave 'select with, weight without'
    unexercised) but small beside the scores' spread, so that routing
    stays near uniform as a trained, load-balanced router's is. A matrix
    whose input or output one of the config's multipliers scales is drawn
    at that scale DIVIDED by the multiplier (the published multipliers
    are small because trained weights are large: at the usual draws both
    mixers and the feed-forward would vanish beside the embedding), so
    every block carries the share of the residual stream it carries in a
    stack without multipliers."""
    dt = _dtype(cfg)
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Fe, Kc = cfg.n_experts, cfg.expert_width, cfg.conv_kernel
    Eh, Fs = cfg.experts_held, cfg.d_ff_shared
    gated = cfg.ff_act == "swiglu"
    damp = (2 * L) ** -0.5  # residual-stream init damping
    count = [0]

    def dense(*shape, scale=None, dtype=dt):
        count[0] += 1
        if scale is None:
            scale = shape[-2] ** -0.5  # [..., fan_in, fan_out]
        return _seeded(jax.random.fold_in(key, count[0]), tuple(shape),
                       float(scale), jnp.dtype(dtype))

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def sparse_block(R):
        """Router over all E, the Eh experts held here, the shared one."""
        lp = dict(router=dense(R, D, E, dtype=jnp.float32))
        if gated:
            lp.update(w_gate=dense(R, Eh, D, Fe), w_up=dense(R, Eh, D, Fe))
        else:  # stored as w_down is, applied transposed (dispatch_experts)
            lp["w_up"] = dense(R, Eh, Fe, D, scale=D ** -0.5)
        lp["w_down"] = dense(R, Eh, Fe, D, scale=damp * Fe ** -0.5)
        if cfg.router_bias:
            lp["router_bias"] = dense(R, E, scale=0.05, dtype=jnp.float32)
        if Fs:
            if gated:
                lp["shared_gate"] = dense(R, D, Fs)
            lp.update(shared_up=dense(R, D, Fs),
                      shared_down=dense(R, Fs, D, scale=damp * Fs ** -0.5))
        return lp

    def mamba_block(R):
        """Mamba-2's own seeded rule for what is not a matrix: A in
        [1, 16] uniform, dt log-uniform in [1e-3, 1e-1] floored at 1e-4
        and stored as the inverse softplus, D = 1."""
        count[0] += 3
        ka, kd, kb = (jax.random.fold_in(key, count[0] - i) for i in range(3))
        Hs, Di, Cd = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
        GN = cfg.ssm_groups * cfg.ssm_state
        into = D ** -0.5 / cfg.ssm_in_mult
        # [z | x | B | C], each segment against its own multiplier
        cols = [dense(R, D, w, scale=into / m) for w, m in
                zip((Di, Di, GN, GN), cfg.ssm_mults)]
        dt_mult = cfg.ssm_mults[4] if cfg.ssm_mults else 1.0
        step = jnp.maximum(jnp.exp(
            jax.random.uniform(kd, (R, Hs), jnp.float32)
            * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)), 1e-4)
        return dict(
            # in_proj as two stacks, [z | xBC] and dt (stored [Hs, D]):
            # as one its width, 2 Di + 2 G N + Hs, is no multiple of the
            # TPU's 128 lanes, the device stores it D-minor and the
            # compiled chunk relays it out on every entry
            ssm_in=jnp.concatenate(cols, axis=-1) if cols
            else dense(R, D, Di + Cd, scale=into),
            ssm_dt_in=dense(R, Hs, D, scale=into / dt_mult),
            ssm_conv_w=dense(R, Kc, Cd, scale=Kc ** -0.5),
            ssm_conv_b=(jax.random.normal(kb, (R, Cd), jnp.float32) * 0.1
                        ).astype(dt),
            ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)),
            ssm_A_log=jnp.log(jax.random.uniform(
                ka, (R, Hs), jnp.float32, 1.0, 16.0)),
            ssm_D=ones(R, Hs), ssm_norm=ones(R, Di),
            ssm_out=dense(R, Di, D,
                          scale=damp * Di ** -0.5 / cfg.ssm_out_mult))

    def attn_block(R):
        into = D ** -0.5 / cfg.attn_in_mult
        lp = dict(
            wq=dense(R, D, H * Dh, scale=into),
            wk=dense(R, D, Hkv * Dh, scale=into / cfg.key_mult),
            wv=dense(R, D, Hkv * Dh, scale=into),
            wo=dense(R, H * Dh, D,
                     scale=damp * (H * Dh) ** -0.5 / cfg.attn_out_mult))
        if cfg.qk_norm:
            lp.update(q_norm=ones(R, Dh), k_norm=ones(R, Dh))
        return lp

    segments = []
    for seg in layer_plan(cfg):
        R, period = seg.reps, []
        for op, sparse in seg.kinds:
            if op not in FUSED_OPS:  # one block, one norm
                block = {OP_MAMBA: mamba_block, OP_ATTN_ONLY: attn_block,
                         OP_MOE: sparse_block}[op](R)
                period.append({"op_norm": ones(R, D), **block})
                continue
            lp = {"op_norm": ones(R, D), "ff_norm": ones(R, D)}
            if op == OP_ATTN_MAMBA:  # both mixers' weights in one layer
                lp.update(attn_block(R))
                lp.update(mamba_block(R))
            elif op in (OP_ATTN, OP_SWA):
                Ht = cfg.heads(op)  # the kind's own head count
                lp.update(
                    wq=dense(R, D, Ht * Dh), wk=dense(R, D, Hkv * Dh),
                    wv=dense(R, D, Hkv * Dh),
                    wo=dense(R, Ht * Dh, D, scale=damp * (Ht * Dh) ** -0.5))
                if cfg.qk_norm:
                    lp.update(q_norm=ones(R, Dh), k_norm=ones(R, Dh))
                if cfg.attn_gate:  # [D, heads]: one value a head
                    lp["wa"] = dense(R, D, Ht)
            else:
                lp.update(
                    conv_in=dense(R, D, 3 * D),
                    conv_w=dense(R, Kc, D, scale=Kc ** -0.5),
                    conv_out=dense(R, D, D, scale=damp * D ** -0.5))
            if sparse:
                lp.update(
                    router=dense(R, D, E, dtype=jnp.float32),
                    w_gate=dense(R, E, D, Fe), w_up=dense(R, E, D, Fe),
                    w_down=dense(R, E, Fe, D, scale=damp * Fe ** -0.5))
                if cfg.router_bias:
                    lp["router_bias"] = dense(R, E, scale=0.05,
                                              dtype=jnp.float32)
                if Fs:  # the shared expert beside the routed ones
                    lp.update(
                        shared_gate=dense(R, D, Fs), shared_up=dense(R, D, Fs),
                        shared_down=dense(R, Fs, D,
                                          scale=damp * Fs ** -0.5))
            else:
                lp.update(
                    w_gate=dense(R, D, F, scale=D ** -0.5 / cfg.mlp_gate_mult),
                    w_up=dense(R, D, F),
                    w_down=dense(R, F, D,
                                 scale=damp * F ** -0.5 / cfg.mlp_down_mult))
            period.append(lp)
        segments.append(tuple(period))
    params: Params = {
        "embed": dense(V, D, scale=D ** -0.5 / cfg.embed_mult),
        "segments": tuple(segments),
        "final_norm": ones(D),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(D, V, scale=D ** -0.5 / cfg.logits_mult)
    return params


def layer_params(params: Params, cfg: ModelConfig, layer: int) -> Dict:
    """One layer's weights out of a patterned tree (loaders, tests)."""
    for seg, sp in zip(layer_plan(cfg), params["segments"]):
        p = len(seg.kinds)
        if seg.first_layer <= layer < seg.first_layer + p * seg.reps:
            r, j = divmod(layer - seg.first_layer, p)
            return {k: v[r] for k, v in sp[j].items()}
    raise IndexError(layer)


def _conv_mix(u_hist, w):
    """Depthwise causal convolution: u_hist [B, S + Kc - 1, D] (the Kc - 1
    inputs before the first output position in front), w [Kc, D] ->
    [B, S, D] with c_t = sum_j w[j] * u_{t - (Kc - 1) + j}."""
    Kc = w.shape[0]
    S = u_hist.shape[1] - (Kc - 1)
    uf = u_hist.astype(jnp.float32)
    return sum(w[j].astype(jnp.float32) * uf[:, j:j + S] for j in range(Kc))


def _conv_op(h, lp, cfg, state=None, plens=None):
    """Gated short convolution on normed input h [B, S, D].

    state [B, Kc - 1, D] (decode: the slot's last inputs) or None
    (a sequence from position 0: zeros before it). Returns (y [B, S, D],
    new state): with `plens` the state after each row's OWN last real
    token (right-padded prefill), else after the last position."""
    B, S, D = h.shape
    Kc = cfg.conv_kernel
    with jax.named_scope("conv/in_proj"):
        bcx = _qdot(h, lp, "conv_in", cfg)
        b_gate, c_gate, xin = jnp.split(bcx, 3, axis=-1)
    with jax.named_scope("conv/mix"):
        u = b_gate * xin
        if state is None:
            state = jnp.zeros((B, Kc - 1, D), u.dtype)
        hist = jnp.concatenate([state.astype(u.dtype), u], axis=1)
        mixed = (c_gate.astype(jnp.float32)
                 * _conv_mix(hist, lp["conv_w"])).astype(h.dtype)
        if plens is None:
            new_state = hist[:, S:]
        else:
            # hist index of position t is t + Kc - 1: the Kc - 1 inputs
            # that end at position plen - 1 start at hist index plen.
            idx = plens[:, None] + jnp.arange(Kc - 1)[None, :]
            new_state = jnp.take_along_axis(hist, idx[:, :, None], axis=1)
    with jax.named_scope("conv/out_proj"):
        y = _qdot(mixed, lp, "conv_out", cfg)
    return y, new_state


def _ssd_scan(x, dt, a_log, b, c, chunk):
    """The SSM recurrence over whole sequences, chunk by chunk (the SSD
    form): S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t.

    x [B, S, H, P]; dt [B, S, H] float32, after the softplus and ZERO at
    positions that are not live (the state passes them unchanged);
    a_log [H] (A = -exp(a_log)); b, c [B, S, G, N], head h reading group
    h // (H / G). Inside a chunk of Q positions the outputs are one
    masked [Q, Q] product (the decays between every pair of positions),
    between chunks the state is carried by a scan over S / Q steps.
    Returns (y [B, S, H, P] float32, the state after the last position
    [B, H, P, N] float32)."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    K = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (S + pad) // Q
    f32 = jnp.float32
    xg = x.reshape(B, nc, Q, G, K, P)
    dtg = dt.reshape(B, nc, Q, G, K)
    bg, cg = b.reshape(B, nc, Q, G, N), c.reshape(B, nc, Q, G, N)
    a = dtg * -jnp.exp(a_log.astype(f32)).reshape(G, K)  # log decay a step
    acum = jnp.cumsum(a, axis=2)  # [B, nc, Q, G, K], up to and with t
    # inside a chunk: y_t = sum_{s <= t} (C_t . B_s) exp(acum_t - acum_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcqsg", cg, bg, preferred_element_type=f32)
    seg = acum[:, :, :, None] - acum[:, :, None, :]  # [B, nc, Q, Q, G, K]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    m = cb[..., None] * jnp.exp(jnp.where(causal, seg, -jnp.inf)) \
        * dtg[:, :, None]
    y = jnp.einsum("bcqsgk,bcsgkp->bcqgkp", m.astype(x.dtype), xg,
                   preferred_element_type=f32)
    # what each chunk adds to the state, decayed to the chunk's end
    xw = (xg * (dtg * jnp.exp(acum[:, :, -1:] - acum))[..., None]
          ).astype(x.dtype)
    added = jnp.einsum("bcsgkp,bcsgn->cbgkpn", xw, bg,
                       preferred_element_type=f32)
    decay = jnp.moveaxis(jnp.exp(acum[:, :, -1]), 1, 0)  # [nc, B, G, K]

    def carry_on(state, chunk_):
        add, dec = chunk_
        return dec[..., None, None] * state + add, state

    last, before = jax.lax.scan(
        carry_on, jnp.zeros((B, G, K, P, N), f32), (added, decay))
    # the state a chunk starts from, read by each of its positions
    y = y + jnp.einsum("bcqgn,cbgkpn->bcqgkp", cg.astype(f32), before,
                       preferred_element_type=f32) * jnp.exp(acum)[..., None]
    return y.reshape(B, S + pad, H, P)[:, :S], last.reshape(B, H, P, N)


def _ssm_update(state, layer, x, dt, a_log, b, c):
    """One step of the recurrence for every row, where the state lies:
    state [Lm, B, H, P, N] float32 over all the Mamba-2 layers, `layer`
    the one stepped, x [B, H, P], dt [B, H] float32 (after the softplus),
    b, c [B, G, N]. Returns (y [B, H, P] float32, the state with that
    layer stepped): ops/ssm_update.py, a kernel on a TPU."""
    f32 = jnp.float32
    keep = jnp.exp(dt * -jnp.exp(a_log.astype(f32)))  # [B, H]
    return ssm_update.update(state, layer, keep,
                             dt[..., None] * x.astype(f32), b, c)


def _mamba_op(h, lp, cfg, state=None, conv_state=None, live=None,
              plens=None):
    """A Mamba-2 mixer on normed input h [B, S, D].

    Decode (S = 1): `state` = (every Mamba-2 layer's state [Lm, B, H, P,
    N] float32, which of them is this layer's) and `conv_state` [B, Kc -
    1, C] are the slots'; one step of the recurrence, in place. Whole
    sequences from position 0 (state None): the chunked scan; positions
    where `live` [B, S] is False act as dt = 0, and with `plens` the conv
    state is taken after each row's own last real token, so that a padded
    row is left the state of its prompt. Returns (y [B, S, D], SSM state
    (decode: every layer's, this one stepped; else this layer's after the
    last live position), conv state). The config's multipliers act where
    the published mixer applies them: on h, on the input projection's
    segments (z, x, B, C, dt) and on the output."""
    B, S, _ = h.shape
    Hs, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    Di, Cd, Kc = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.conv_kernel
    f32 = jnp.float32
    h = _scaled(h, cfg.ssm_in_mult)
    with jax.named_scope("ssm/in_proj"):
        zxbc = _qdot(h, lp, "ssm_in", cfg)
        if cfg.ssm_mults:  # one multiplier a segment: z, x, B, C
            zxbc = _scaled(zxbc, np.repeat(
                np.asarray(cfg.ssm_mults[:4], np.float32),
                [Di, Di, G * N, G * N]))
        z, xbc = zxbc[..., :Di], zxbc[..., Di:]
        dt = jnp.einsum("bsd,hd->bsh", h, _w(lp, "ssm_dt_in", h.dtype),
                        preferred_element_type=f32)
        if cfg.ssm_mults:
            dt = _scaled(dt, cfg.ssm_mults[4])
    with jax.named_scope("ssm/conv"):
        if conv_state is None:
            conv_state = jnp.zeros((B, Kc - 1, Cd), xbc.dtype)
        hist = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        xbc = jax.nn.silu(
            _conv_mix(hist, lp["ssm_conv_w"]) + lp["ssm_conv_b"].astype(f32)
        ).astype(h.dtype)
        if plens is None:
            new_conv = hist[:, S:]
        else:  # as _conv_op: the Kc - 1 inputs that end at plen - 1
            idx = plens[:, None] + jnp.arange(Kc - 1)[None, :]
            new_conv = jnp.take_along_axis(hist, idx[:, :, None], axis=1)
        x = xbc[..., :Di].reshape(B, S, Hs, P)
        b = xbc[..., Di:Di + G * N].reshape(B, S, G, N)
        c = xbc[..., Di + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt.astype(f32) + lp["ssm_dt_bias"])
    if state is not None:
        with jax.named_scope("ssm/update"):
            y, new_state = _ssm_update(
                *state, x[:, 0], dt[:, 0], lp["ssm_A_log"], b[:, 0], c[:, 0])
            y = y[:, None]
    else:
        with jax.named_scope("ssm/scan"):
            if live is not None:
                dt = jnp.where(live[..., None], dt, 0.0)
            y, new_state = _ssd_scan(x, dt, lp["ssm_A_log"], b, c,
                                     cfg.ssm_chunk)
    with jax.named_scope("ssm/gate_norm"):
        y = y + lp["ssm_D"][:, None] * x.astype(f32)
        y = y.reshape(B, S, Di) * jax.nn.silu(z.astype(f32))
        # RMSNorm over each of the G groups of Di / G channels, after the gate
        yg = y.reshape(B, S, G, Di // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = (yg.reshape(B, S, Di) * lp["ssm_norm"]).astype(h.dtype)
    with jax.named_scope("ssm/out_proj"):
        return _scaled(_qdot(y, lp, "ssm_out", cfg), cfg.ssm_out_mult), \
            new_state, new_conv


_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _split_experts(period, cfg):
    """A segment's stacked weights as (what the scan slices per repeat,
    what the expert kernel is handed whole): position j's expert
    matrices [R, E, ...] with the repeat and expert axes merged
    [R * E, ...], which moves nothing. The kernel picks its repeat's E
    groups by index (ops/moe_dispatch.dispatch_experts: a slice of the
    stack as a custom call's operand would copy every expert's weights
    on every step)."""
    sliced, whole = [], []
    for lp in period:
        sparse = "router" in lp
        sliced.append({k: v for k, v in lp.items()
                       if not (sparse and k in _EXPERT_STACKS)})
        whole.append({
            k: _w(lp, k, _dtype(cfg)).reshape((-1,) + lp[k].shape[2:])
            for k in _EXPERT_STACKS if k in lp} if sparse else None)
    return tuple(sliced), whole


def _sparse_ff(h, lp, experts, rep, cfg, live):
    """The sparse feed-forward by token -> expert dispatch
    (ops/moe_dispatch.py). h [B, S, D], live [B, S] bool or None;
    `experts` the segment position's merged expert stacks, `rep` which
    repeat of the segment this layer is. The router scores all
    cfg.n_experts; where the program holds a share of them
    (cfg.n_experts_held) the dispatch is told which, and computes their
    part of the sum. A shared expert (cfg.d_ff_shared) takes every row."""
    B, S, D = h.shape
    x = h.reshape(B * S, D)
    with jax.named_scope("moe/router"):
        top_idx, top_w = moe_dispatch.route(
            x, lp["router"], lp.get("router_bias"),
            top_k=cfg.n_experts_per_token, router=cfg.router,
            norm_topk=cfg.router_norm_topk, scale=cfg.router_scale,
            norm_eps=cfg.router_norm_eps)
    out, stats = moe_dispatch.dispatch_experts(
        x, top_idx, top_w, experts.get("w_gate"), experts["w_up"],
        experts["w_down"], None if live is None else live.reshape(B * S),
        n_experts=cfg.experts_held, layer=rep,
        first=cfg.expert_first if cfg.n_experts_held else None)
    if cfg.d_ff_shared:
        with jax.named_scope("moe/shared"):
            up = _qdot(x, lp, "shared_up", cfg)
            hidden = jnp.square(jax.nn.relu(up)) if cfg.ff_act == "relu2" \
                else jax.nn.silu(_qdot(x, lp, "shared_gate", cfg)) * up
            out = out + _qdot(hidden, lp, "shared_down", cfg)
    return out.reshape(B, S, D), stats


def _routing_counts(cfg, stats=None, ssm: bool = False):
    """One layer's share of a step's counters (routing_width)."""
    wide = routing_width(cfg) == 5
    if stats is None and not ssm:
        return jnp.zeros((routing_width(cfg),), jnp.int32)
    one, zero = jnp.ones((), jnp.int32), jnp.zeros((), jnp.int32)
    if stats is None:
        return jnp.stack([zero, zero, zero, zero, one])
    r = [one, stats["touched"], stats["assignments"]]
    if wide:
        r += [stats.get("held", stats["assignments"]), zero]
    return jnp.stack(r)


def _ff_res(x, lp, experts, rep, cfg, live):
    """x + FF(RMSNorm(x)); also the routing counters [layers, touched,
    assignments] of this layer (zeros for a dense one)."""
    h = rms_norm(x, lp["ff_norm"], cfg.rms_norm_eps)
    if experts is not None:
        out, st = _sparse_ff(h, lp, experts, rep, cfg, live)
        routing = _routing_counts(cfg, st)
        return x + out, routing
    with jax.named_scope("mlp"):
        hidden = jax.nn.silu(_scaled(_qdot(h, lp, "w_gate", cfg),
                                     cfg.mlp_gate_mult)) \
            * _qdot(h, lp, "w_up", cfg)
        return x + _scaled(_qdot(hidden, lp, "w_down", cfg),
                           cfg.mlp_down_mult), _routing_counts(cfg)


def _segment_cache(cache, seg: Segment):
    """The slices of the by-kind cache a segment's scan rides on:
    {"k","v"} [R, na, ...], "conv" [R, nc, ...] and "ssm_conv"
    [R, nm, ...] (absent kinds left out). A segment that owns every
    layer of a kind reshapes and copies nothing. The SSM state is not
    among them: the decode step carries it whole (_run_patterned_decode)."""
    out = {}
    held = {"conv": ((OP_CONV,), seg.conv_start),
            "ssm_conv": (SSM_OPS, seg.ssm_start),
            "kw": (WINDOW_OPS, seg.window_start),
            "vw": (WINDOW_OPS, seg.window_start)}
    for key, arr in cache.items():
        if key == "ssm":
            continue
        ops, start = held.get(key, (KV_OPS, seg.attn_start))
        n = _count_ops(seg.kinds, *ops)
        if not n:
            continue
        part = arr if arr.shape[0] == n * seg.reps else \
            arr[start:start + n * seg.reps]
        out[key] = part.reshape((seg.reps, n) + arr.shape[1:])
    return out


def _unsegment(parts):
    """Per-segment scan outputs [R, n, ...] back to [layers of kind, ...]."""
    flat = [p.reshape((-1,) + p.shape[2:]) for p in parts]
    return flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=0)


def _gated(attn, h, lp, cfg):
    """The per-head output gate (cfg.attn_gate): sigmoid(h Wa), one value
    a head from the layer's normed input, on that head's attention output
    attn [B, S, H * Dh]."""
    if not cfg.attn_gate:
        return attn
    with jax.named_scope("attn/gate"):
        g = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, _w(lp, "wa", h.dtype),
            preferred_element_type=jnp.float32))
        B, S, _ = attn.shape
        return (attn.reshape(B, S, g.shape[-1], -1).astype(jnp.float32)
                * g[..., None]).astype(attn.dtype).reshape(B, S, -1)


def _ring_rows(x, plens, window: int):
    """A prefill's keys or values [B, S, Hkv, Dh] as the ring holds them,
    [B, 1, W, Hkv * Dh]: row s takes the newest position below the row's
    own prompt length that is s modulo W. Rows no position has reached
    yet (a prompt shorter than the window) take whatever lies at the
    clamp: decode masks them (s >= pos)."""
    B, S = x.shape[:2]
    s = jnp.arange(window)[None, :]
    at = plens[:, None] - 1 - (plens[:, None] - 1 - s) % window
    rows = jnp.take_along_axis(
        x.reshape(B, S, -1), jnp.clip(at, 0, S - 1)[:, :, None], axis=1)
    return rows[:, None]


def _run_patterned_full(params, x, cfg, positions, inv_freq, mask, plens):
    """Every layer over whole sequences from position 0 (forward,
    prefill). Returns (x, fresh cache arrays by kind or {} when plens is
    None, routing): k/v [La, B, 1, S, Hkv * Dh] in cache layout, conv
    [Lc, B, Kc - 1, D] and the Mamba-2 mixers' SSM and conv state taken
    at each row's own prompt length (an "attention_mamba" layer yields
    both k/v and that state). Positions at or past a row's plens
    are not live: they route to no expert and pass the SSM state on
    unchanged."""
    S = x.shape[1]
    live = None if plens is None else \
        jnp.arange(S)[None, :] < plens[:, None]
    fresh = {"k": [], "v": [], "conv": [], "ssm": [], "ssm_conv": [],
             "kw": [], "vw": []}
    routing = jnp.zeros((routing_width(cfg),), jnp.int32)
    side = kv_heads_per_row(cfg)
    for seg, sp in zip(layer_plan(cfg), params["segments"]):
        sliced, experts = _split_experts(sp, cfg)

        def body(carry, xs, seg=seg, experts=experts):
            x, routing = carry
            rep, lps = xs
            ks, vs, cs, ss, scs, kws, vws = [], [], [], [], [], [], []
            for lp, ex, (op, _) in zip(lps, experts, seg.kinds):
                h = rms_norm(x, lp["op_norm"], cfg.rms_norm_eps)
                if op == OP_ATTN_MAMBA:
                    # attention and the mixer both read h; their outputs,
                    # each times its multiplier, are summed into x
                    q, k, v = _qkv(_scaled(h, cfg.attn_in_mult), lp, cfg,
                                   positions, inv_freq)
                    attn = gqa_attention(q, k, v, mask)
                    with jax.named_scope("attn/out"):
                        a = _scaled(_qdot(attn, lp, "wo", cfg),
                                    cfg.attn_out_mult)
                    y, st, cst = _mamba_op(h, lp, cfg, live=live, plens=plens)
                    with jax.named_scope("mixer/sum"):
                        x = x + a + y
                    ks.append(_kv_slab(k, side))
                    vs.append(_kv_slab(v, side))
                    ss.append(st)
                    scs.append(cst)
                    routing = routing + _routing_counts(cfg, ssm=True)
                elif cfg.n_window_layers and op in (OP_ATTN, OP_SWA):
                    # a stack whose attention kinds differ: by blocks of
                    # keys on both kinds (no S x S scores), inside the
                    # window's band on the sliding layers
                    windowed = op in WINDOW_OPS
                    q, k, v = _qkv(h, lp, cfg, positions, inv_freq, op=op)
                    with jax.named_scope(
                            "attn/window" if windowed else "attn/full"):
                        attn = prefill_attention.attend(
                            q, k, v, plens, head_dim=cfg.head_dim,
                            window=cfg.sliding_window if windowed else 0)
                    attn = _gated(attn, h, lp, cfg)
                    with jax.named_scope("attn/out"):
                        x = x + _qdot(attn, lp, "wo", cfg)
                    if plens is None:
                        pass  # forward(): no cache is kept
                    elif windowed:
                        kws.append(_ring_rows(k, plens, cfg.sliding_window))
                        vws.append(_ring_rows(v, plens, cfg.sliding_window))
                    else:
                        ks.append(_kv_slab(k, side))
                        vs.append(_kv_slab(v, side))
                elif op in KV_OPS:
                    q, k, v = _qkv(h, lp, cfg, positions, inv_freq)
                    attn = gqa_attention(q, k, v, mask)
                    with jax.named_scope("attn/out"):
                        x = x + _qdot(attn, lp, "wo", cfg)
                    ks.append(_kv_slab(k, side))
                    vs.append(_kv_slab(v, side))
                elif op == OP_MAMBA:
                    y, st, cst = _mamba_op(h, lp, cfg, live=live, plens=plens)
                    x = x + y
                    ss.append(st)
                    scs.append(cst)
                    routing = routing + _routing_counts(cfg, ssm=True)
                elif op == OP_MOE:
                    y, st = _sparse_ff(h, lp, ex, rep, cfg, live)
                    x = x + y
                    routing = routing + _routing_counts(cfg, st)
                else:
                    y, st = _conv_op(h, lp, cfg, plens=plens)
                    x = x + y
                    cs.append(st)
                if op in FUSED_OPS:
                    x, r = _ff_res(x, lp, ex, rep, cfg, live)
                    routing = routing + r
            ys = {}
            if plens is not None:
                if ks:
                    ys["k"], ys["v"] = jnp.stack(ks), jnp.stack(vs)
                if cs:
                    ys["conv"] = jnp.stack(cs)
                if ss:
                    ys["ssm"], ys["ssm_conv"] = jnp.stack(ss), jnp.stack(scs)
                if kws:
                    ys["kw"], ys["vw"] = jnp.stack(kws), jnp.stack(vws)
            return (x, routing), ys

        (x, routing), ys = jax.lax.scan(
            body, (x, routing), (jnp.arange(seg.reps), sliced))
        for key, val in ys.items():
            fresh[key].append(val)
    return x, {k: _unsegment(v) for k, v in fresh.items() if v}, routing


def _run_patterned_decode(params, x, cfg, positions, inv_freq, pos, cache,
                          live, spread=False):
    """One decode step through every layer. KV is read PRE-write and all
    attention layers' fresh k/v land after the scans in one scatter
    (_run_blocks_decode's discipline); the conv state is small and is
    replaced whole. The SSM state is neither: every layer's [B, H, P, N]
    float32 is read and written on every step, so it rides in the scans'
    CARRY and each Mamba-2 layer updates its own slice of it where it
    lies (as scanned inputs and outputs it would be a second array, and
    the step a copy of the whole state). Rows that are not live still
    shift their conv state, step their SSM state and scribble KV at
    their frozen position: harmless, an admission overwrites all three
    before the slot is read again. On a TPU the attention layers read
    the live rows' tokens out of the whole slab by the layer's index
    (_sparse_decode) and write the live rows' fresh k/v into it, so K
    and V ride the scans' CARRY whole beside the SSM state, no scatter
    follows, and a slot that is not live keeps its KV as it was.

    A sliding_attention layer reads its ring "kw" / "vw" the same way,
    pre-write: of the W rows, those a position has reached (s < pos) less
    the row s = pos % W, which this step overwrites and which, once pos
    >= W, still holds position pos - W, the one that has just left the
    window; the fresh column makes the W-th key. Its fresh k/v land at
    row pos % W: by the kernel, or in a scatter of their own after the
    scans."""
    Smax = cache["k"].shape[3]
    mask_lt = jnp.arange(Smax)[None, None, :] < pos[:, None, None]
    live2 = None if live is None else live[:, None]
    sched = _sparse_decode(cfg, cache, live, pos, spread)
    fresh = {"k": [], "v": [], "conv": [], "ssm_conv": [], "kw": [], "vw": []}
    sched_w = mask_w = None
    if "kw" in cache:
        W = cache["kw"].shape[3]
        sched_w = _sparse_decode(cfg, cache, live, pos, spread, ring=True)
        s_ = jnp.arange(W)[None, None, :]
        mask_w = (s_ < pos[:, None, None]) & (s_ != (pos % W)[:, None, None])
    routing = jnp.zeros((routing_width(cfg),), jnp.int32)
    dt = cache["k"].dtype
    side = kv_heads_per_row(cfg)
    # what the layers update where it lies, carried whole by the scans:
    # the SSM state, and K and V of a kind the kernel reads and writes
    held = {key: cache[key] for key in
            ("ssm",) + (("k", "v") if sched is not None else ())
            + (("kw", "vw") if sched_w is not None else ())
            if key in cache}
    riding = {key: arr for key, arr in cache.items() if key not in held}
    for seg, sp in zip(layer_plan(cfg), params["segments"]):
        sliced, experts = _split_experts(sp, cfg)
        nm = _count_ops(seg.kinds, *SSM_OPS)
        na = _count_ops(seg.kinds, *KV_OPS)
        nw = _count_ops(seg.kinds, *WINDOW_OPS)

        def body(carry, xs, seg=seg, experts=experts, nm=nm, na=na, nw=nw):
            x, routing, held = carry
            held = dict(held)
            rep, lps, cl = xs
            ia = ic = im = iw = 0
            ks, vs, cs, scs, kws, vws = [], [], [], [], [], []

            def attention(h, lp, op, i):
                """Attention layer `i` of its kind (`op`) in this repeat
                over the cache as it was before this step: the window
                kind over its ring, every other over the slab; through
                the gate, where the stack has one, and the output
                projection; the fresh k and v with it, which the kernel
                has written into `held` where it runs."""
                q, k, v = _qkv(h, lp, cfg, positions, inv_freq, op=op)
                windowed = op in WINDOW_OPS
                with jax.named_scope(
                        "attn/window" if windowed else "attn/full"):
                    if windowed and sched_w is None:
                        attn = gqa_attention_decode(
                            q, cl["kw"][i], cl["vw"][i], k, v, mask_w)
                    elif windowed:
                        attn, held["kw"], held["vw"] = decode_attention.attend(
                            q, k, v, {"k": held["kw"], "v": held["vw"]},
                            seg.window_start + rep * nw + i, sched_w)
                    elif sched is None:
                        attn = gqa_attention_decode(
                            q, cl["k"][i], cl["v"][i], k, v, mask_lt)
                    else:
                        attn, held["k"], held["v"] = decode_attention.attend(
                            q, k, v, {"k": held["k"], "v": held["v"]},
                            seg.attn_start + rep * na + i, sched)
                attn = _gated(attn, h, lp, cfg)
                with jax.named_scope("attn/out"):
                    return _qdot(attn, lp, "wo", cfg), k, v

            def mixer(h, lp, im):
                """Mamba-2 mixer `im` of this repeat, its state stepped
                where it lies in the carried `held`."""
                at = seg.ssm_start + rep * nm + im
                y, held["ssm"], cst = _mamba_op(
                    h, lp, cfg, conv_state=cl["ssm_conv"][im],
                    state=(held["ssm"], at))
                return y, cst.astype(cl["ssm_conv"].dtype)

            for lp, ex, (op, _) in zip(lps, experts, seg.kinds):
                h = rms_norm(x, lp["op_norm"], cfg.rms_norm_eps)
                if op == OP_ATTN_MAMBA:
                    # as _run_patterned_full: both read h, summed into x;
                    # the layer's k/v are written by the kernel or join
                    # the scatter after the scans, its SSM state rides
                    # the carry
                    a, k, v = attention(_scaled(h, cfg.attn_in_mult), lp, op,
                                        ia)
                    a = _scaled(a, cfg.attn_out_mult)
                    y, cst = mixer(h, lp, im)
                    with jax.named_scope("mixer/sum"):
                        x = x + a + y
                    ks.append(_kv_rows(k, side)[:, 0].astype(dt))
                    vs.append(_kv_rows(v, side)[:, 0].astype(dt))
                    scs.append(cst)
                    routing = routing + _routing_counts(cfg, ssm=True)
                    ia += 1
                    im += 1
                elif op == OP_MAMBA:
                    y, cst = mixer(h, lp, im)
                    x = x + y
                    scs.append(cst)
                    routing = routing + _routing_counts(cfg, ssm=True)
                    im += 1
                elif op == OP_MOE:
                    y, st = _sparse_ff(h, lp, ex, rep, cfg, live2)
                    x = x + y
                    routing = routing + _routing_counts(cfg, st)
                elif op in WINDOW_OPS:
                    a, k, v = attention(h, lp, op, iw)
                    x = x + a
                    kws.append(_kv_rows(k, side)[:, 0].astype(dt))
                    vws.append(_kv_rows(v, side)[:, 0].astype(dt))
                    iw += 1
                elif op in KV_OPS:
                    a, k, v = attention(h, lp, op, ia)
                    x = x + a
                    ks.append(_kv_rows(k, side)[:, 0].astype(dt))
                    vs.append(_kv_rows(v, side)[:, 0].astype(dt))
                    ia += 1
                else:
                    y, st = _conv_op(h, lp, cfg, state=cl["conv"][ic])
                    x = x + y
                    cs.append(st.astype(cl["conv"].dtype))
                    ic += 1
                if op in FUSED_OPS:
                    x, r = _ff_res(x, lp, ex, rep, cfg, live2)
                    routing = routing + r
            ys = {}
            if ks and sched is None:
                ys["k"], ys["v"] = jnp.stack(ks), jnp.stack(vs)
            if cs:
                ys["conv"] = jnp.stack(cs)
            if scs:
                ys["ssm_conv"] = jnp.stack(scs)
            if kws and sched_w is None:
                ys["kw"], ys["vw"] = jnp.stack(kws), jnp.stack(vws)
            return (x, routing, held), ys

        (x, routing, held), ys = jax.lax.scan(
            body, (x, routing, held),
            (jnp.arange(seg.reps), sliced, _segment_cache(riding, seg)))
        for key, val in ys.items():
            fresh[key].append(val)
    rows = jnp.arange(pos.shape[0])
    new_cache = {**cache, **held}
    # Layer, row and position are all INDICES of the scatter and only
    # the token's row is its window. With the layer axis a window
    # dimension (`.at[:, rows, :, pos]`) the TPU compiler carried the
    # slab layer-minor through the chunk's steps and relaid the whole of
    # it out for the layer scan on every step (kv_heads_per_row).
    layers = jnp.arange(cache["k"].shape[0])[None, :]
    with jax.named_scope("attn/cache_update"):
        for key in ("k", "v"):
            if fresh[key]:
                new_cache[key] = cache[key].at[
                    layers, rows[:, None], :, pos[:, None]].set(
                    jnp.swapaxes(_unsegment(fresh[key]), 0, 1),
                    unique_indices=True)
        for key in ("kw", "vw"):  # the ring: position p at row p % W
            if fresh[key]:
                rings = jnp.arange(cache[key].shape[0])[None, :]
                new_cache[key] = cache[key].at[
                    rings, rows[:, None], :,
                    (pos % cache[key].shape[3])[:, None]].set(
                    jnp.swapaxes(_unsegment(fresh[key]), 0, 1),
                    unique_indices=True)
    if fresh["conv"]:
        new_cache["conv"] = _unsegment(fresh["conv"])
    if fresh["ssm_conv"]:
        new_cache["ssm_conv"] = _unsegment(fresh["ssm_conv"])
    return x, new_cache, routing


def gqa_attention_block(q, ck, cv, k_fresh, v_fresh, mask_lt):
    """gqa_attention_decode for a BLOCK of query positions a slot
    (cfg.gen_block): q [B, Bk, H, Dh] against the pre-write slab layer
    ck / cv [B, 1, T, Hkv * Dh] below each slot's position (`mask_lt`
    [B, 1, T]) and against the block's own fresh keys and values
    [B, Bk, Hkv, Dh], every one of which every query of the block sees.
    The einsums' leg: off a TPU and where the slab is spread over
    devices; ops/decode_attention.attend is the other."""
    B, S, H, Dh = q.shape
    Hkv = k_fresh.shape[2]
    G = H // Hkv
    qr = q.reshape(B, S, Hkv, G, Dh)
    with jax.named_scope("attn/scores"):
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", qr, _kv_tokens(ck, Hkv).astype(qr.dtype),
            preferred_element_type=jnp.float32) / (Dh**0.5)
        scores = jnp.where(mask_lt[:, None, None, :, :], scores, -1e30)
        s_fresh = jnp.einsum(
            "bskgd,bukd->bkgsu", qr, k_fresh.astype(qr.dtype),
            preferred_element_type=jnp.float32) / (Dh**0.5)
        w = jax.nn.softmax(
            jnp.concatenate([scores, s_fresh], axis=-1), axis=-1)
        T = scores.shape[-1]
    with jax.named_scope("attn/out"):
        out = jnp.einsum("bkgst,btkd->bskgd", w[..., :T].astype(qr.dtype),
                         _kv_tokens(cv, Hkv).astype(qr.dtype))
        out = out + jnp.einsum("bkgsu,bukd->bskgd",
                               w[..., T:].astype(qr.dtype),
                               v_fresh.astype(qr.dtype))
    return out.reshape(B, S, H * Dh)


def block_kv_counts(cfg, cache, live, pos, commit, spread=False):
    """decode_kv_counts for a pass over a block of positions a slot
    (cfg.gen_block): KV tokens the attention layers read and hold, as
    there, then the K rows written, which only the rows that commit
    write (gen_block each), beside slots x gen_block x layers."""
    La, B, _, T, _ = cache["k"].shape
    held = jnp.asarray(La * B * T, jnp.int32)
    sched = _sparse_decode(cfg, cache, live, pos, spread)
    read = held if sched is None else La * decode_attention.tokens_read(sched)
    written = La * cfg.gen_block * jnp.sum(
        live & commit & (pos < T), dtype=jnp.int32)
    return jnp.stack([read, held, written,
                      jnp.asarray(La * B * cfg.gen_block, jnp.int32)])


def decode_block(params, tokens, known, pos, cache, cfg, live, commit,
                 spread: bool = False):
    """One PASS of a model that generates by diffusion over blocks
    (cfg.gen_block = Bk; full_attention layers only): the Bk positions
    pos .. pos + Bk - 1 of every slot, `tokens` [B, Bk] where `known`
    and the embedding of cfg.mask_token_id elsewhere, against the cache
    below pos and against each other in both directions. Returns
    (the hidden rows [B, Bk, D] of the block's own positions, for
    block_logits to score, the cache with the
    block's K/V rows written for the slots that `commit` and are `live`
    and for no other, the routing counters of decode_step). A slot that
    is not live routes its Bk rows to no expert."""
    Bk = cfg.gen_block
    with jax.named_scope("diff/mask_embed"):
        x = _embed_rows(params, jnp.where(known, tokens, cfg.mask_token_id),
                        _dtype(cfg))
    positions = pos[:, None] + jnp.arange(Bk)[None, :]
    inv_freq = rope_frequencies(cfg)
    Smax = cache["k"].shape[3]
    mask_lt = jnp.arange(Smax)[None, None, :] < pos[:, None, None]
    live2 = jnp.broadcast_to(live[:, None], (live.shape[0], Bk))
    writes = live & commit & (pos < Smax)
    sched = _sparse_decode(cfg, cache, live, pos, spread)
    if sched is not None:
        sched = decode_attention.committing(sched, writes, Smax)
    routing = jnp.zeros((routing_width(cfg),), jnp.int32)
    dt = cache["k"].dtype
    side = kv_heads_per_row(cfg)
    held = {key: cache[key] for key in ("k", "v")} if sched is not None \
        else {}
    fresh = {"k": [], "v": []}
    for seg, sp in zip(layer_plan(cfg), params["segments"]):
        sliced, experts = _split_experts(sp, cfg)
        na = len(seg.kinds)  # every layer is a full_attention one

        def body(carry, xs, seg=seg, experts=experts, na=na):
            x, routing, held = carry
            held = dict(held)
            rep, lps, cl = xs
            ks, vs = [], []
            for i, (lp, ex) in enumerate(zip(lps, experts)):
                h = rms_norm(x, lp["op_norm"], cfg.rms_norm_eps)
                q, k, v = _qkv(h, lp, cfg, positions, inv_freq, step=True)
                with jax.named_scope("attn/full"):
                    if sched is None:
                        attn = gqa_attention_block(
                            q, cl["k"][i], cl["v"][i], k, v, mask_lt)
                    else:
                        with jax.named_scope("attn/commit"):
                            attn, held["k"], held["v"] = \
                                decode_attention.attend(
                                    q, k, v, held,
                                    seg.attn_start + rep * na + i, sched)
                with jax.named_scope("attn/out"):
                    x = x + _qdot(attn, lp, "wo", cfg)
                ks.append(_kv_rows(k, side)[:, :, 0].astype(dt))
                vs.append(_kv_rows(v, side)[:, :, 0].astype(dt))
                x, r = _ff_res(x, lp, ex, rep, cfg, live2)
                routing = routing + r
            ys = {"k": jnp.stack(ks), "v": jnp.stack(vs)} \
                if sched is None else {}
            return (x, routing, held), ys

        riding = {} if sched is not None else \
            {key: cache[key] for key in ("k", "v")}
        (x, routing, held), ys = jax.lax.scan(
            body, (x, routing, held),
            (jnp.arange(seg.reps), sliced, _segment_cache(riding, seg)))
        for key, val in ys.items():
            fresh[key].append(val)
    new_cache = {**cache, **held}
    if sched is None:
        # the committing slots' Bk rows; every other slot's index lies
        # past the window and its write is dropped (its bytes stay)
        rows = jnp.arange(pos.shape[0])[:, None, None]
        layers = jnp.arange(cache["k"].shape[0])[None, :, None]
        at = jnp.where(writes[:, None], positions, Smax)[:, None, :]
        with jax.named_scope("attn/commit"):
            for key in ("k", "v"):
                # [La, B, Bk, C] -> [B, La, Bk, C]
                new_cache[key] = cache[key].at[layers, rows, 0, at].set(
                    jnp.swapaxes(_unsegment(fresh[key]), 0, 1), mode="drop")
    return x, new_cache, routing


def block_logits(params, x, cfg):
    """The head over rows `x` [N, D] of a pass's hidden rows
    (decode_block), whichever slots' they are: logits [N, V]. One row a
    (slot, position): [slots, Bk, V] would put Bk in the tile's sublanes
    and every later view of it [slots * Bk, V] would be a copy."""
    return _logits(params, x[None], cfg)[0]


def _prefill_patterned(params, tokens, prompt_lens, cache, cfg):
    """prefill() for a patterned stack: KV of the attention layers into
    positions [0, S), each conv or Mamba-2 layer's state at the row's
    own prompt length (rows of one admission group are right-padded to
    the bucket: the state at the bucket's end would be the padding's).
    Under cfg.gen_block the mask is block-causal, `prompt_lens` are the
    prompts' whole blocks and no logits are returned (None)."""
    B, S = tokens.shape
    x = _scaled(_embed_rows(params, tokens, _dtype(cfg)), cfg.embed_mult)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    # a stack with sliding_attention layers attends by blocks of keys and
    # makes no S x S mask either (_run_patterned_full)
    mask = None if cfg.n_window_layers else \
        jnp.tril(jnp.ones((S, S), dtype=bool))[None].repeat(B, 0)
    if cfg.gen_block:
        mask = _full_mask(cfg, B, S)
    x, fresh, _ = _run_patterned_full(
        params, x, cfg, positions, rope_frequencies(cfg), mask, prompt_lens)
    new_cache = dict(cache)
    with jax.named_scope("attn/cache_update"):
        for key, val in fresh.items():
            val = val.astype(cache[key].dtype)
            if key in _FIXED_STATE or S == cache[key].shape[3]:
                new_cache[key] = val
            else:
                new_cache[key] = cache[key].at[:, :, :, :S].set(val)
    if cfg.gen_block:  # a prefill deposits KV and scores nothing
        return None, new_cache
    last = jnp.clip(prompt_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return _logits(params, x_last, cfg)[:, 0], new_cache
