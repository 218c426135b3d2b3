"""The family of Laguna-XS.2 (`model_type` laguna; Laguna-S-2.1 is the
same with other numbers): full-attention and sliding-window layers in one
pattern, each kind with its own number of query heads over the same 8 KV
heads, its own rotary table (YaRN over half of each head on the full
kind, a plain table over the whole head on the window kind) and, in the
program, its own cache length; a sigmoid gate a head on the attention's
output; a dense SwiGLU in layer 0 and, in every other layer, 256 experts
top-8 by softmax scores beside one shared expert; untied head. Served
from the program's seeded bf16 tree.

Keys of a configuration file of this family (Hugging Face names, values
as run): hidden_size, intermediate_size (the dense SwiGLU width),
moe_intermediate_size, shared_expert_intermediate_size,
num_hidden_layers, layer_types ("full_attention" / "sliding_attention"
per layer), mlp_layer_types ("dense" for leading layers, then "sparse"),
num_attention_heads (the full kind's) and num_attention_heads_per_layer
(one other value on the sliding layers), num_key_value_heads, head_dim,
vocab_size, max_position_embeddings, rms_norm_eps, sliding_window,
rope_parameters.full_attention {rope_theta, rope_type "yarn", factor,
original_max_position_embeddings, beta_fast, beta_slow, attention_factor,
partial_rotary_factor} and .sliding_attention {rope_theta, rope_type
"default", partial_rotary_factor 1}, num_experts, num_experts_per_tok,
moe_routed_scaling_factor, moe_apply_router_weight_on_input (must be
false), tie_word_embeddings (false), attention_bias (false), gating
(true: `assumed.gating` says per head); `serving` weight_dtype /
kv_cache_dtype (bf16) and kv_budget_tokens. The other keys of the
catalog's row are kept in the file as published and read by nothing.

The layer equations (h is [S, D]; eps = rms_norm_eps; kind t of layer l,
H_t its query heads, Hkv KV heads of Dh):

  layer l   h = h + Attn_l(RMSNorm(h; op_norm));  h = h + FF_l(RMSNorm(h; ff_norm))
  Attn      n = the normed input; q = n Wq [S, H_t, Dh], k = n Wk, v = n Wv
            [S, Hkv, Dh], no bias; rotary by kind (below); scores
            q k^T / sqrt(Dh); key j is seen from i when j <= i and, on a
            sliding layer, i - j < sliding_window; softmax; o = p v, H_t /
            Hkv query heads a KV head; gate g = sigmoid(n Wa) [S, H_t],
            o_head <- g_head * o_head; out projection Wo
  rotary    half-split pairing over the rotated dims. sliding: theta^(-2i/Dh)
            over all Dh dims. full: the first d = partial_rotary_factor x Dh
            dims rotate, the rest pass through; YaRN over d:
            dim(r) = d ln(original / (2 pi r)) / (2 ln theta),
            low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), both
            clamped to [0, d - 1], ramp_i = clip((i - low) / (high - low), 0, 1),
            inv_freq_i = theta^(-2i/d) ((1 - ramp_i) + ramp_i / factor);
            cos and sin times attention_factor
  dense FF  w_down(silu(w_gate x) * w_up x), width intermediate_size
  sparse FF p = softmax(x W_router) over all experts in float32; the k
            largest; w = p_sel / sum(p_sel) * moe_routed_scaling_factor;
            sum_e w_e E_e(x) + Shared(x), each a SwiGLU
  head      RMSNorm(h; final_norm) @ lm_head

The reference follows these in straightforward jax.numpy: float32 under
jax.default_matmul_precision("highest"), a Python loop over layers and,
in a sparse layer, over experts (every expert on every token, masked by
its weight: no dispatch), the two masks written out as j <= i and
i - j < window, no cache, no ring, no scan, no kernel, no line of
seldon_tpu/models/transformer.py or seldon_tpu/ops. So that a probe of
3000 positions fits, attention runs as a plain Python loop over blocks of
QUERIES (each block against all S keys, its scores [H, block, S]) and
the head is multiplied block of positions by block; neither changes a
sum's order within a row. The tree's layout is the program's
(`segments`: periods of layer kinds stacked over their repeats); the
reference walks it in layer order and checks each layer's head count
against num_attention_heads_per_layer.

Its lower-precision twin, the negative control: the same forward pass
with every layer's matrices (not norms, router, gate or embedding)
rounded to float8 e4m3, the nearest precision below the served bf16.

The costs price what a decode step NEEDS (live rows, live context): the
full layers' KV at the context, the window layers' at min(context,
sliding_window); the k experts a row routes to (distinct experts under
uniform routing, or a measured count) and the shared one; layer 0's dense
block and the head whole. prefill_attention_flops is the closed form of a
prompt's attention products, causal on the full layers and banded on the
window layers.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

import math
from typing import Dict, Iterator, Optional, Tuple

CONTROL = "float8 e4m3 grid"
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 256   # queries a step of the attention loop takes
HEAD_BLOCK = 512    # positions a step of the head's product takes


# -- the configuration's keys as the program's ModelConfig -------------------

def heads_by_kind(cfg: Dict) -> Dict[str, int]:
    """Query heads of each attention kind, from num_attention_heads_per_layer."""
    per = cfg["num_attention_heads_per_layer"]
    kinds = cfg["layer_types"]
    if not len(per) == len(kinds) == cfg["num_hidden_layers"]:
        raise ValueError("layer_types and num_attention_heads_per_layer must "
                         "name num_hidden_layers layers")
    out: Dict[str, int] = {}
    for kind, h in zip(kinds, per):
        if out.setdefault(kind, h) != h:
            raise ValueError(f"{kind} layers differ in their head count")
    if out.get(FULL, cfg["num_attention_heads"]) != cfg["num_attention_heads"]:
        raise ValueError("num_attention_heads is the full_attention layers'")
    return out


def n_dense_layers(cfg: Dict) -> int:
    kinds = list(cfg["mlp_layer_types"])
    n = sum(1 for k in kinds if k == "dense")
    if kinds != ["dense"] * n + ["sparse"] * (len(kinds) - n) \
            or len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("mlp_layer_types: leading dense layers, then sparse")
    return n


def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig. Every value is
    what a JSON round trip of the program's config gives back."""
    serving = cfg.get("serving", {})
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings") \
            or cfg.get("moe_apply_router_weight_on_input") \
            or not cfg.get("gating"):
        raise ValueError("this family has no attention bias, an untied head, "
                         "router weights on the experts' outputs and the gate")
    heads = heads_by_kind(cfg)
    full = cfg["rope_parameters"][FULL]
    slid = cfg["rope_parameters"][SLIDING]
    if full["rope_type"] != "yarn" or slid["rope_type"] != "default" \
            or slid.get("partial_rotary_factor", 1) != 1:
        raise ValueError("rotary: yarn on full_attention, a plain whole-head "
                         "table on sliding_attention")
    return dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_heads_window=heads.get(SLIDING, 0),
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(full["rope_theta"]),
        rope_theta_window=float(slid["rope_theta"]),
        rotary_share=float(full["partial_rotary_factor"]),
        rope_scaling_type="yarn",
        rope_scaling_factor=float(full["factor"]),
        rope_scaling_original_max_position=int(
            full["original_max_position_embeddings"]),
        rope_scaling_beta_fast=float(full["beta_fast"]),
        rope_scaling_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=False,
        n_experts=int(cfg["num_experts"]),
        n_experts_per_token=int(cfg["num_experts_per_tok"]),
        n_dense_layers=n_dense_layers(cfg),
        d_ff_expert=int(cfg["moe_intermediate_size"]),
        d_ff_shared=int(cfg["shared_expert_intermediate_size"]),
        router="softmax",
        router_norm_topk=True,
        router_scale=float(cfg["moe_routed_scaling_factor"]),
        layer_types=list(cfg["layer_types"]),
        sliding_window=int(cfg["sliding_window"]),
        attn_gate=True,
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
    )


# -- the plain reference ------------------------------------------------------

def _need_jax() -> None:
    global jax, jnp
    import jax
    import jax.numpy as jnp


def build_params(cfg: Dict, seed: int):
    """The tree the unit serves: the program's seeded bf16 initialiser."""
    _need_jax()
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.transformer import init_params

    if cfg["serving"]["weight_dtype"] != "bf16":
        raise ValueError("this family is served, and read, in bf16")
    model = ModelConfig(**model_config_kwargs(cfg)).validate()
    return init_params(model, jax.random.key(int(seed)))


def _layers(params) -> Iterator[Dict]:
    """The tree's layers in layer order: segment by segment, repeat by
    repeat, position by position within the period."""
    for period in params["segments"]:
        reps = next(iter(period[0].values())).shape[0]
        for r in range(reps):
            for pos in period:
                yield {k: v[r] for k, v in pos.items()}


def _mat(w, control):
    """A layer's matrix in float32; control: rounded to the float8 e4m3
    grid (4 significant bits, normal down to 2^-6, then steps of 2^-9,
    largest 448), written out in arithmetic: as a pair of conversions the
    TPU compiler is free to drop it as excess precision (families/lfm2.py)."""
    w = w.astype(jnp.float32)
    if control:
        exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -20)))
        step = jnp.exp2(jnp.maximum(exp, -6.0) - 3.0)
        w = jnp.clip(jnp.round(w / step) * step, -448.0, 448.0)
    return w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(d: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """The d / 2 rotary frequencies of YaRN over d rotated dims, as a list
    of Python floats (the docstring's formula, in float64)."""
    def dim(r):
        return d * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    low = min(max(math.floor(dim(beta_fast)), 0), d - 1)
    high = min(max(math.ceil(dim(beta_slow)), 0), d - 1)
    out = []
    for i in range(d // 2):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(theta ** (-2.0 * i / d) * ((1.0 - ramp) + ramp / factor))
    return out


def rotary_of(cfg: Dict, kind: str) -> Tuple[list, float]:
    """(frequencies of the rotated dims, factor on cos and sin) of a kind."""
    rp, dh = cfg["rope_parameters"][kind], cfg["head_dim"]
    if kind == SLIDING:
        return [rp["rope_theta"] ** (-2.0 * i / dh) for i in range(dh // 2)], 1.0
    d = int(dh * rp["partial_rotary_factor"])
    return yarn_inv_freq(d, rp["rope_theta"], rp["factor"],
                         rp["original_max_position_embeddings"],
                         rp["beta_fast"], rp["beta_slow"]), rp["attention_factor"]


def _rope(x, inv_freq, mscale):
    """x [S, H, Dh]: the first 2 x len(inv_freq) dims rotate, pair
    (i, i + len(inv_freq)), by position x inv_freq_i; cos and sin times
    mscale; the other dims pass through."""
    s = x.shape[0]
    half = len(inv_freq)
    inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * mscale, jnp.sin(ang)[:, None, :] * mscale
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _qkv_gate(x, lw, dims, control):
    """The normed input's projections, rotated: q [S, H, Dh], k and v
    [S, Hkv, Dh], and the gate [S, H]."""
    n_heads, n_kv, dh, inv_freq, mscale, _, eps = dims
    s = x.shape[0]
    h = _rms(x, lw["op_norm"], eps)
    q = (h @ _mat(lw["wq"], control)).reshape(s, n_heads, dh)
    k = (h @ _mat(lw["wk"], control)).reshape(s, n_kv, dh)
    v = (h @ _mat(lw["wv"], control)).reshape(s, n_kv, dh)
    gate = jax.nn.sigmoid(h @ lw["wa"].astype(jnp.float32))
    return _rope(q, inv_freq, mscale), _rope(k, inv_freq, mscale), v, gate


def _attend_block(q, k, v, first, dims):
    """Queries [b, H, Dh] at positions first .. first + b - 1 against all
    S keys: [b, H * Dh]."""
    n_heads, n_kv, dh, _, _, window, _ = dims
    rep = n_heads // n_kv  # grouped-query attention: rep query heads a KV head
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thd->shd", p, v).reshape(q.shape[0], n_heads * dh)


def _attn_out(x, o, gate, lw, dims, control):
    n_heads, dh = dims[0], dims[2]
    o = (o.reshape(-1, n_heads, dh) * gate[:, :, None]).reshape(-1, n_heads * dh)
    return x + o @ _mat(lw["wo"], control)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _dense_ff(x, lw, eps, control):
    h = _rms(x, lw["ff_norm"], eps)
    return x + _swiglu(h, _mat(lw["w_gate"], control), _mat(lw["w_up"], control),
                       _mat(lw["w_down"], control))


def _route(x, lw, eps, top_k, scale):
    """Softmax over all experts in float32, the k largest, renormalised
    over those, times the routed scaling factor. Returns the normed
    input, the selected experts and their weights."""
    h = _rms(x, lw["ff_norm"], eps)
    p = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    top_p, top_idx = jax.lax.top_k(p, top_k)
    return h, top_idx, top_p / jnp.sum(top_p, axis=-1, keepdims=True) * scale


def _expert_add(acc, h, top_idx, top_w, e, gate, up, down, control):
    """acc += (weight of expert e for each token, 0 where not routed) * expert_e(h)."""
    w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)
    return acc + w_e[:, None] * _swiglu(h, _mat(gate, control), _mat(up, control),
                                        _mat(down, control))


def _shared_add(x, acc, h, lw, control):
    return x + acc + _swiglu(h, _mat(lw["shared_gate"], control),
                             _mat(lw["shared_up"], control),
                             _mat(lw["shared_down"], control))


def forward_logits(params, tokens, cfg: Dict, control: bool = False):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under the
    bf16 tree `params`. control: the layers' matrices on the float8 e4m3
    grid."""
    _need_jax()
    eps = float(cfg["rms_norm_eps"])
    heads, n_dense = heads_by_kind(cfg), n_dense_layers(cfg)
    n_experts = int(cfg["num_experts"])
    qkv_gate = jax.jit(_qkv_gate, static_argnums=(2, 3))
    attend_block = jax.jit(_attend_block, static_argnums=(4,))
    attn_out = jax.jit(_attn_out, static_argnums=(4, 5))
    dense_ff = jax.jit(_dense_ff, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3, 4))
    expert_add = jax.jit(_expert_add, static_argnums=(8,))
    shared_add = jax.jit(_shared_add, static_argnums=(4,))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        s, n = x.shape[0], 0
        for li, lw in enumerate(_layers(params)):
            kind = cfg["layer_types"][li]
            inv_freq, mscale = rotary_of(cfg, kind)
            dims = (heads[kind], cfg["num_key_value_heads"], cfg["head_dim"],
                    tuple(inv_freq), float(mscale),
                    int(cfg["sliding_window"]) if kind == SLIDING else 0, eps)
            if lw["wq"].shape[-1] != heads[kind] * cfg["head_dim"]:
                raise ValueError(f"layer {li} of the tree has not the {heads[kind]} "
                                 f"heads num_attention_heads_per_layer names")
            q, k, v, gate = qkv_gate(x, lw, dims, control)
            o = jnp.concatenate([  # a plain loop over blocks of queries
                attend_block(q[a:a + QUERY_BLOCK], k, v, a, dims)
                for a in range(0, s, QUERY_BLOCK)], axis=0)
            x = attn_out(x, o, gate, lw, dims, control)
            if li < n_dense:
                x = dense_ff(x, lw, eps, control)
            else:
                h, top_idx, top_w = route(
                    x, lw, eps, int(cfg["num_experts_per_tok"]),
                    float(cfg["moe_routed_scaling_factor"]))
                acc = jnp.zeros_like(x)
                for e in range(n_experts):
                    acc = expert_add(acc, h, top_idx, top_w, e, lw["w_gate"][e],
                                     lw["w_up"][e], lw["w_down"][e], control)
                x = shared_add(x, acc, h, lw, control)
            n = li + 1
        if n != len(cfg["layer_types"]):
            raise ValueError(f"the tree has {n} layers, layer_types {len(cfg['layer_types'])}")
        x = _rms(x, params["final_norm"], eps)
        head = params["lm_head"].astype(jnp.float32)
        return jnp.concatenate([x[a:a + HEAD_BLOCK] @ head
                                for a in range(0, s, HEAD_BLOCK)], axis=0)


# -- what a decode step needs -------------------------------------------------

_BYTES = {"bf16": 2}


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind the configuration runs."""
    types, dense = cfg["layer_types"], n_dense_layers(cfg)
    return {"full": sum(1 for t in types if t == FULL),
            "sliding": sum(1 for t in types if t == SLIDING),
            "sparse": len(types) - dense, "dense": dense}


def attn_params(cfg: Dict, kind: str) -> int:
    """q, k, v, o and the gate of one attention layer of a kind."""
    d, dh, hkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    h = heads_by_kind(cfg).get(kind, 0)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + d * h


def dense_ff_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Dict) -> int:
    """One routed expert's SwiGLU triple."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def experts_touched(cfg: Dict, rows: float) -> float:
    """Expected number of distinct experts per sparse layer that `rows`
    tokens route to, for a uniform router (top-k of E)."""
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return n_exp * (1.0 - (1.0 - k / n_exp) ** max(rows, 0.0))


def fixed_params(cfg: Dict) -> int:
    """Weights a step reads whatever its rows: attention, layer 0's dense
    block, the shared experts and the head (routers apart: float32)."""
    n = layer_counts(cfg)
    return (n["full"] * attn_params(cfg, FULL) + n["sliding"] * attn_params(cfg, SLIDING)
            + n["dense"] * dense_ff_params(cfg) + n["sparse"] * shared_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def flops_per_token(cfg: Dict) -> float:
    """Matmul FLOPs one token needs outside attention's score/value
    products: 2 per weight it multiplies through (the k experts routed
    to, not all), the routers and the head."""
    n = layer_counts(cfg)
    return 2.0 * (fixed_params(cfg) + n["sparse"] * (
        cfg["num_experts_per_tok"] * expert_params(cfg) + router_params(cfg)))


def kv_bytes_per_token_layer(cfg: Dict) -> int:
    """K and V of one position in one layer (both kinds: the KV heads are
    the same)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["serving"]["kv_cache_dtype"]]


def weight_bytes(cfg: Dict, touched: Optional[float] = None) -> float:
    """Bytes of the weights one step has to read: everything outside the
    routed experts once, `touched` experts per sparse layer (all by
    default) and the routers (float32)."""
    n = layer_counts(cfg)
    touched = cfg["num_experts"] if touched is None else touched
    return (_BYTES[cfg["serving"]["weight_dtype"]]
            * (fixed_params(cfg) + n["sparse"] * touched * expert_params(cfg))
            + 4 * n["sparse"] * router_params(cfg))


def decode_step_cost(cfg: Dict, rows: float, context: float,
                     touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each: a full layer attends over
    the context, a sliding layer over min(context, sliding_window).
    `touched`: the distinct experts a sparse layer read a step, as the
    unit counted them; absent (a unit that counts none), a uniform
    router's expectation at `rows`."""
    n, heads, dh = layer_counts(cfg), heads_by_kind(cfg), cfg["head_dim"]
    inside = min(context, float(cfg["sliding_window"]))
    attn = 4.0 * dh * (n["full"] * heads.get(FULL, 0) * context
                       + n["sliding"] * heads.get(SLIDING, 0) * inside)
    flops = rows * (flops_per_token(cfg) + attn)
    kv = n["full"] * (context + 1) + n["sliding"] * (inside + 1)
    touched = experts_touched(cfg, rows) if touched is None else touched
    bytes_ = (weight_bytes(cfg, touched)
              + rows * kv * kv_bytes_per_token_layer(cfg))
    return flops, bytes_


# -- what a prefill's attention needs -----------------------------------------

def causal_pairs(s: int) -> int:
    """(query, key) pairs with j <= i over s positions."""
    return s * (s + 1) // 2


def banded_pairs(s: int, window: int) -> int:
    """(query, key) pairs with j <= i and i - j < window over s positions."""
    if s <= window:
        return causal_pairs(s)
    return causal_pairs(window) + (s - window) * window


def prefill_attention_flops(cfg: Dict, prompt_len: int) -> float:
    """FLOPs of one prompt's attention products (q k^T and p v: 4 x Dh a
    pair and query head): causal on the full layers, banded on the
    sliding ones."""
    n, heads = layer_counts(cfg), heads_by_kind(cfg)
    return 4.0 * cfg["head_dim"] * (
        n["full"] * heads.get(FULL, 0) * causal_pairs(prompt_len)
        + n["sliding"] * heads.get(SLIDING, 0)
        * banded_pairs(prompt_len, int(cfg["sliding_window"])))
