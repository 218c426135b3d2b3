"""The family of LFM2-24B-A2B (`model_type` lfm2_moe; the dense sibling
lfm2 is the same with no experts): a layer pattern of gated short
convolutions and grouped-query attention with QK-norm, two leading dense
SwiGLU layers and then sparse blocks of 64 experts routed by sigmoid
scores, top-4; tied embeddings. Served from the program's seeded bf16
tree.

Keys of a configuration file of this family (Hugging Face names, values
as run): hidden_size, intermediate_size (the dense SwiGLU width),
moe_intermediate_size (one expert's width), num_hidden_layers,
layer_types (one of "conv" / "full_attention" per layer), num_dense_layers
(leading layers whose feed-forward is dense), num_attention_heads,
num_key_value_heads, vocab_size, max_position_embeddings, norm_eps,
rope_parameters.rope_theta, conv_L_cache (taps of the short convolution),
conv_bias (must be false), num_experts, num_experts_per_tok,
use_expert_bias, norm_topk_prob, routed_scaling_factor; `assumed`
tie_word_embeddings and qk_norm (both from the model code, true);
`serving` weight_dtype / kv_cache_dtype (bf16) and kv_budget_tokens.

The layer equations (h is [S, D]; eps = norm_eps):

  layer l   h = h + Op_l(RMSNorm(h; op_norm));  h = h + FF_l(RMSNorm(h; ff_norm))
  conv      [B, C, x] = h W_in (three chunks of D, in that order);
            u = B * x;  c_t = sum_j w[j] * u_{t-(K-1)+j} (u_s = 0, s < 0);
            y = (C * c) W_out                         (Lfm2ShortConv)
  attention q, k, v without bias; q, k RMS-normed per head over head_dim;
            then RoPE (pair i with i + head_dim/2); causal softmax at
            1/sqrt(head_dim); out projection
  dense FF  w_down(silu(w_gate x) * w_up x), width intermediate_size
  sparse FF s = sigmoid(x W_router) in float32; selected = top-k of
            s + expert_bias; weights = s at the selected (no bias),
            / (their sum + 1e-6) if norm_topk_prob, * routed_scaling_factor;
            sum over the k of weight * expert's SwiGLU (Lfm2MoeSparseMoeBlock)
  head      RMSNorm(h; final_norm) @ embed.T

The reference follows these in straightforward jax.numpy: float32 under
jax.default_matmul_precision("highest"), a Python loop over layers and,
in a sparse layer, over experts (every expert on every token, masked by
its weight: no dispatch), no cache, no scan, no kernels, no code of
seldon_tpu/models/transformer.py or ops/moe_dispatch.py. Departures from
the published model: weights are the program's seeded tree (as served,
bf16, read here in float32), `expert_bias` is seeded non-zero (zero in a
fresh published module) so that selecting with it and weighting without
it is exercised, and the conv taps are stored [K, D] (published
[D, 1, K]). The tree's layout is the program's: `segments`, each a
period of layer kinds stacked over its repeats; the reference walks it in
layer order (_layers) and checks each layer's kind against layer_types.

Its lower-precision twin, the negative control: the same forward pass
with every layer's matrices (not norms, router or embedding) rounded to
float8 e4m3, the nearest precision below the served bf16.

The costs price what a decode step NEEDS (live rows, live context): KV
for the attention layers only, the conv layers' fixed state, the experts
the rows route to (expected distinct experts under uniform routing, or
a measured count), dense layers whole, the tied head once.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

CONTROL = "float8 e4m3 grid"
CONV, ATTN = "conv", "full_attention"


# -- the configuration's keys as the program's ModelConfig -------------------

def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig. Every value is
    what a JSON round trip of the program's config gives back
    (layer_types a list), which is how run.check_metadata compares."""
    serving = cfg.get("serving", {})
    assumed = cfg.get("assumed", {})
    if cfg.get("conv_bias"):
        raise ValueError("the program's short convolution has no bias")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name num_hidden_layers layers")
    n_experts = int(cfg.get("num_experts", 0) or 0)
    kw = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(cfg["norm_eps"]),
        tie_embeddings=bool(assumed.get("tie_word_embeddings", True)),
        n_experts=n_experts,
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
        layer_types=list(cfg["layer_types"]),
        qk_norm=bool(assumed.get("qk_norm", True)),
        conv_kernel=int(cfg["conv_L_cache"]),
    )
    if n_experts:
        kw.update(
            n_experts_per_token=int(cfg["num_experts_per_tok"]),
            n_dense_layers=int(cfg["num_dense_layers"]),
            d_ff_expert=int(cfg["moe_intermediate_size"]),
            router="sigmoid",
            router_bias=bool(cfg["use_expert_bias"]),
            router_norm_topk=bool(cfg["norm_topk_prob"]),
            router_scale=float(cfg["routed_scaling_factor"]),
        )
    return kw


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
    largest 448). The rounding is written out in arithmetic: as a pair
    of conversions (to float8 and back) the TPU compiler is free to drop
    it as excess precision, and did: the control then WAS the reference
    (first chip run of this PR: every gap 0.0)."""
    w = w.astype(jnp.float32)
    if control:
        exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -20)))
        step = jnp.exp2(jnp.maximum(exp, -6.0) - 3.0)
        w = jnp.clip(jnp.round(w / step) * step, -448.0, 448.0)
    return w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, H, Dh]; position s rotates pair (i, i + Dh/2) by s * theta^(-2i/Dh)."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attention(x, lw, dims, control):
    n_heads, n_kv, dh, theta, eps = dims
    s = x.shape[0]
    h = _rms(x, lw["op_norm"], eps)
    q = (h @ _mat(lw["wq"], control)).reshape(s, n_heads, dh)
    k = (h @ _mat(lw["wk"], control)).reshape(s, n_kv, dh)
    v = (h @ _mat(lw["wv"], control)).reshape(s, n_kv, dh)
    if "q_norm" in lw:  # QK-norm: per head over head_dim, before RoPE
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv  # grouped-query attention: each kv head serves rep q heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", p, v).reshape(s, n_heads * dh)
    return x + out @ _mat(lw["wo"], control)


def _short_conv(x, lw, eps, control):
    s, d = x.shape
    h = _rms(x, lw["op_norm"], eps)
    bcx = h @ _mat(lw["conv_in"], control)
    b, c, xin = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = b * xin
    w = _mat(lw["conv_w"], control)  # [K, D]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), jnp.float32), u], axis=0)
    conv = jnp.zeros_like(u)
    for j in range(taps):  # c_t = sum_j w[j] * u_{t - (K-1) + j}
        conv = conv + w[j][None, :] * padded[j:j + s]
    return x + (c * conv) @ _mat(lw["conv_out"], control)


def _dense_ff(x, lw, eps, control):
    h = _rms(x, lw["ff_norm"], eps)
    return x + _swiglu(h, _mat(lw["w_gate"], control), _mat(lw["w_up"], control),
                       _mat(lw["w_down"], control))


def _route(x, lw, eps, top_k, norm_topk, scale):
    """Lfm2MoeSparseMoeBlock's router: sigmoid scores, selection on score +
    bias, weights from the unbiased scores. Returns the normed input, the
    selected experts and their weights."""
    h = _rms(x, lw["ff_norm"], eps)
    scores = jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32))
    select = scores + lw["router_bias"][None, :] if "router_bias" in lw else scores
    _, top_idx = jax.lax.top_k(select, top_k)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
    return h, top_idx, top_w * scale


def _expert_add(acc, h, top_idx, top_w, e, gate, up, down, control):
    """acc += (weight of expert e for each token, 0 where not routed) * expert_e(h)."""
    w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)
    return acc + w_e[:, None] * _swiglu(h, _mat(gate, control), _mat(up, control),
                                        _mat(down, control))


def forward_logits(params, tokens, cfg: Dict, control: bool = False):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under the
    bf16 tree `params`. control: the layers' matrices on the float8 e4m3
    grid."""
    _need_jax()
    n_heads = cfg["num_attention_heads"]
    eps = float(cfg["norm_eps"])
    dims = (n_heads, cfg["num_key_value_heads"], cfg["hidden_size"] // n_heads,
            float(cfg["rope_parameters"]["rope_theta"]), eps)
    n_experts = int(cfg.get("num_experts", 0) or 0)
    n_dense = int(cfg.get("num_dense_layers", 0)) if n_experts else len(cfg["layer_types"])
    attention = jax.jit(_attention, static_argnums=(2, 3))
    short_conv = jax.jit(_short_conv, static_argnums=(2, 3))
    dense_ff = jax.jit(_dense_ff, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3, 4, 5))
    expert_add = jax.jit(_expert_add, static_argnums=(8,))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        n = 0
        for li, lw in enumerate(_layers(params)):
            kind = cfg["layer_types"][li]
            if (kind == CONV) != ("conv_in" in lw):
                raise ValueError(f"layer {li} of the tree is not the {kind!r} "
                                 f"layer_types names")
            x = short_conv(x, lw, eps, control) if kind == CONV \
                else attention(x, lw, dims, control)
            if li < n_dense:
                x = dense_ff(x, lw, eps, control)
            else:
                h, top_idx, top_w = route(
                    x, lw, eps, int(cfg["num_experts_per_tok"]),
                    bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]))
                acc = jnp.zeros_like(x)
                for e in range(n_experts):
                    acc = expert_add(acc, h, top_idx, top_w, e, lw["w_gate"][e],
                                     lw["w_up"][e], lw["w_down"][e], control)
                x = x + acc
            n = li + 1
        if n != len(cfg["layer_types"]):
            raise ValueError(f"the tree has {n} layers, layer_types {len(cfg['layer_types'])}")
        x = _rms(x, params["final_norm"], eps)
        if "lm_head" in params:
            return x @ params["lm_head"].astype(jnp.float32)
        return x @ params["embed"].astype(jnp.float32).T


# -- what a decode step needs -------------------------------------------------

_BYTES = {"bf16": 2}


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind the configuration runs."""
    types = cfg["layer_types"]
    n_sparse = 0
    if cfg.get("num_experts"):
        n_sparse = max(0, len(types) - int(cfg["num_dense_layers"]))
    return {"attention": sum(1 for t in types if t == ATTN),
            "conv": sum(1 for t in types if t == CONV),
            "sparse": n_sparse, "dense": len(types) - n_sparse}


def attn_params(cfg: Dict) -> int:
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def conv_params(cfg: Dict) -> int:
    """in_proj (D x 3D), out_proj (D x D) and the K taps of D."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d + int(cfg["conv_L_cache"]) * d


def dense_ff_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Dict) -> int:
    """One expert's SwiGLU triple."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def experts_touched(cfg: Dict, rows: float) -> float:
    """Expected number of distinct experts per sparse layer that `rows`
    tokens route to, for a uniform router (top-k of E)."""
    n_exp = cfg.get("num_experts") or 1
    if n_exp == 1:
        return 1.0
    k = cfg["num_experts_per_tok"]
    return n_exp * (1.0 - (1.0 - k / n_exp) ** max(rows, 0.0))


def flops_per_token(cfg: Dict) -> float:
    """Matmul FLOPs one token needs outside attention's score/value
    products: 2 per weight it multiplies through (the k experts routed
    to, not all), the router and the tied head."""
    n = layer_counts(cfg)
    k = cfg.get("num_experts_per_tok", 0) if n["sparse"] else 0
    weights = (n["attention"] * attn_params(cfg) + n["conv"] * conv_params(cfg)
               + n["dense"] * dense_ff_params(cfg)
               + n["sparse"] * (k * expert_params(cfg) + router_params(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])
    return 2.0 * weights


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position, in the layers that hold KV."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    return 2 * layer_counts(cfg)["attention"] * hkv * (d // h) * b


def conv_state_bytes_per_row(cfg: Dict) -> int:
    """The conv layers' state of one slot: K - 1 inputs of D, bf16."""
    return (layer_counts(cfg)["conv"] * (int(cfg["conv_L_cache"]) - 1)
            * cfg["hidden_size"] * 2)


def weight_bytes(cfg: Dict, touched: Optional[float] = None) -> float:
    """Bytes of the weights one step has to read: everything outside the
    experts once, `touched` experts per sparse layer (all by default),
    the routers (float32) and the tied head (= the embedding, read whole
    as the head; the gathered input rows are noise)."""
    n = layer_counts(cfg)
    b = _BYTES[cfg["serving"]["weight_dtype"]]
    touched = (cfg.get("num_experts") or 0) if touched is None else touched
    body = (n["attention"] * attn_params(cfg) + n["conv"] * conv_params(cfg)
            + n["dense"] * dense_ff_params(cfg)
            + n["sparse"] * touched * expert_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])
    routers = 4 * n["sparse"] * router_params(cfg) if n["sparse"] else 0
    return b * body + routers


def decode_step_cost(cfg: Dict, rows: float, context: float,
                     touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each. `touched`: the distinct
    experts a sparse layer read a step, as the unit counted them; absent
    (a unit that counts none), a uniform router's expectation at `rows`."""
    h, dh = cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    n_attn = layer_counts(cfg)["attention"]
    touched = experts_touched(cfg, rows) if touched is None else touched
    flops = rows * (flops_per_token(cfg) + n_attn * h * 4.0 * dh * context)
    bytes_ = (weight_bytes(cfg, touched)
              + rows * (context + 1) * kv_bytes_per_token(cfg)
              + 2 * rows * conv_state_bytes_per_row(cfg))  # read and written
    return flops, bytes_


# -- what the grouped expert products need ------------------------------------

def sparse_period_repeats(cfg: Dict) -> int:
    """How often the layer pattern's period repeats over the sparse
    layers: the program scans its layers by period, so each grouped
    product in the decode program's text runs this many times a step
    (sparse layers = repeats x sparse layers in one period)."""
    n_dense = int(cfg["num_dense_layers"]) if cfg.get("num_experts") else 0
    types = list(cfg["layer_types"])[n_dense:]
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return len(types) // p
    return 1


def grouped_product_cost(cfg: Dict, rows: float,
                         touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) ONE grouped product (one of gate / up / down of one
    sparse layer) needs for `rows` live rows: rows x k assignments
    through one D x F matrix each, and that matrix of the `touched`
    experts (expected under uniform routing unless measured) read once.
    Activations (rows x k x (D + F) x 2 bytes) are counted too."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["num_experts_per_tok"]
    touched = experts_touched(cfg, rows) if touched is None else touched
    flops = 2.0 * rows * k * d * f
    bytes_ = touched * d * f * _BYTES[cfg["serving"]["weight_dtype"]] \
        + rows * k * (d + f) * 2
    return flops, bytes_
