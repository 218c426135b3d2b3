"""The family of NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type` nemotron_h):
layers that are ONE residual block each, by `hybrid_override_pattern`: a
Mamba-2 mixer (M), a grouped-query attention without rotary embedding
(*) or a sparse feed-forward (E) of un-gated relu^2 experts routed by
sigmoid scores, top-6, beside one shared expert; untied head. Served from
the program's seeded bf16 tree, of which one chip holds a SHARE: some of
the routed experts of every E layer and some rows of the vocabulary.

Keys of a configuration file of this family (Hugging Face names, values
as run): hidden_size, num_hidden_layers, hybrid_override_pattern (one of
M / E / * per layer), num_attention_heads, num_key_value_heads, head_dim,
vocab_size (the rows held here), max_position_embeddings,
layer_norm_epsilon, rope_theta and partial_rotary_factor (unread: below),
mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size, conv_kernel,
chunk_size, use_conv_bias (must be true), time_step_min / _max / _floor,
intermediate_size, moe_intermediate_size,
moe_shared_expert_intermediate_size, n_shared_experts, mlp_hidden_act
(must be relu2), n_routed_experts (the experts HELD here), router_width
(the experts the router scores: the published n_routed_experts),
num_experts_per_tok, norm_topk_prob, routed_scaling_factor, n_group /
topk_group (must be 1), every *_bias false; `serving` weight_dtype /
kv_cache_dtype (bf16), ssm_state_dtype (float32), kv_budget_tokens,
window_tokens, experts_held_from (the first expert held).

The layer equations (h is [S, D]; eps = layer_norm_epsilon):

  layer l   h = h + Block_l(RMSNorm(h; op_norm))
  M         [z | xBC | dt] = n W_in, widths Di | Di + 2 G N | H, with
            Di = mamba_num_heads x mamba_head_dim (not expand x hidden);
            xBC = silu(conv_K(xBC) + b), causal and depthwise;
            x [H, P], B [G, N], C [G, N] = split(xBC);
            dt = softplus(dt + dt_bias); A = -exp(A_log); g(h) = h // (H / G);
            S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)];
            y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h];
            y = GroupRMSNorm(y * silu(z)) (gate first, then each of the G
            groups of Di / G channels normed, times the weight);
            out = y W_out                                (NemotronHMamba2Mixer)
  *         q [Hq, Dh], k, v [Hkv, Dh] without bias, NO rotary embedding,
            causal softmax at Dh ** -0.5, out projection  (NemotronHAttention)
  E         s = sigmoid(n W_r) in float32 over all router_width experts;
            chosen = top-k of s + e_score_correction_bias; weights = s at
            the chosen / (their sum + 1e-20) x routed_scaling_factor;
            expert_e(n) = relu(n W_up,e)^2 W_down,e; out = sum over the
            chosen e HELD HERE of weight_e expert_e(n) + shared(n), the
            shared expert the same block at its own width (NemotronHMOE)
  head      RMSNorm(h; final_norm) @ lm_head

The share: the router scores every expert and the weights are normalised
over all the chosen; what the experts on the other chips would have added
is left out, here and in the program alike, and that partial sum is what
goes on to the next layer. No code stands in for the other chips.

The reference follows these in straightforward jax.numpy: float32 under
jax.default_matmul_precision("highest"), a Python loop over layers and,
in an E layer, over the experts held (each on every token, masked by its
weight: no dispatch), the M layer as the recurrence above one token at a
time (a lax.scan over positions: no chunks, no carried cache), no
kernels, no code of seldon_tpu/models/transformer.py or
ops/moe_dispatch.py. Departures from the published model: weights are
the program's seeded tree (as served, bf16, read here in float32); the
router's correction bias is seeded non-zero (zero in a fresh published
module) so that selecting with it and weighting without it is exercised;
conv taps are stored [K, C] (published [C, 1, K]); in_proj is stored as
two matrices, its [z | xBC] columns [D, Di + C] and its dt columns [H, D],
and an expert's up matrix as its down matrix is, [F, D] (the widths 10304
and 1856 are no multiples of the TPU's 128 lanes: as a stack's minor
dimension the device would store them D-minor and relay them out for the
kernels on every chunk); `rope_theta` and
`partial_rotary_factor` stand in config.json and the published attention
reads neither. The tree's layout is the program's: `segments`, each a
period of layer kinds stacked over its repeats; the reference walks it in
layer order (_layers) and checks each layer's kind against the pattern.

Its lower-precision twin, the negative control: the same forward pass
with every layer's matrices (projections, conv taps, experts; not norms,
A_log, dt_bias, D, the conv bias, the router, embedding or head) rounded
to float8 e4m3, the nearest precision below the served bf16.

The costs price what a decode step NEEDS, whatever program serves it: the
SSM and conv state of the slots that hold a request read and written
once, the mixers', attentions', shared experts' and head's weights once,
the routed experts the live rows touched among the held (as the unit
counted them, `touched`; else a uniform router's expectation), KV of the
attention layers at the live context.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

CONTROL = "float8 e4m3 grid"
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
# The program's seeded rule for dt (transformer._init_params_patterned).
TIME_STEP = {"time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001}


# -- the configuration's keys as the program's ModelConfig -------------------

def layer_types(cfg: Dict) -> list:
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig. Every value is
    what a JSON round trip of the program's config gives back
    (layer_types a list), which is how run.check_metadata compares."""
    serving = cfg.get("serving", {})
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern must name num_hidden_layers layers")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias"):
        if cfg.get(key):
            raise ValueError(f"the program's projections have no bias ({key})")
    if not cfg.get("use_conv_bias", True):
        raise ValueError("the program's Mamba-2 convolution has its bias (use_conv_bias)")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("the program's router has no group limit (n_group, topk_group)")
    if cfg.get("mlp_hidden_act") != "relu2" or cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("this family's experts are relu2 and its mixer's gate silu")
    if cfg.get("sliding_window"):
        raise ValueError("the program's attention here has no window")
    for key, want in TIME_STEP.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"the program seeds dt with {key} = {want}")
    if serving.get("ssm_state_dtype", "float32") != "float32":
        raise ValueError("the program keeps the SSM state in float32")
    width, held = int(cfg["router_width"]), int(cfg["n_routed_experts"])
    return dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=int(cfg["head_dim"]),
        rotary=False,
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["layer_norm_epsilon"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
        layer_types=layer_types(cfg),
        conv_kernel=int(cfg["conv_kernel"]),
        ssm_heads=int(cfg["mamba_num_heads"]),
        ssm_head_dim=int(cfg["mamba_head_dim"]),
        ssm_groups=int(cfg["n_groups"]),
        ssm_state=int(cfg["ssm_state_size"]),
        ssm_chunk=int(cfg["chunk_size"]),
        n_experts=width,
        n_experts_held=held if held < width else 0,
        expert_first=int(serving.get("experts_held_from", 0)),
        n_experts_per_token=int(cfg["num_experts_per_tok"]),
        d_ff_expert=int(cfg["moe_intermediate_size"]),
        d_ff_shared=int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        ff_act="relu2",
        router="sigmoid",
        router_bias=True,
        router_norm_topk=bool(cfg["norm_topk_prob"]),
        router_scale=float(cfg["routed_scaling_factor"]),
        router_norm_eps=1e-20,
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
    TPU compiler is free to drop it as excess precision (PERF.md, PR 27)."""
    w = w.astype(jnp.float32)
    if control:
        exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -20)))
        step = jnp.exp2(jnp.maximum(exp, -6.0) - 3.0)
        w = jnp.clip(jnp.round(w / step) * step, -448.0, 448.0)
    return w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2_block(h, up, down):
    """down(relu(up h) ** 2), up [D, F] and down [F, D]."""
    return jnp.square(jax.nn.relu(h @ up)) @ down


def _attention(x, lw, dims, control):
    n_heads, n_kv, dh, eps = dims
    s = x.shape[0]
    h = _rms(x, lw["op_norm"], eps)
    q = (h @ _mat(lw["wq"], control)).reshape(s, n_heads, dh)
    k = (h @ _mat(lw["wk"], control)).reshape(s, n_kv, dh)
    v = (h @ _mat(lw["wv"], control)).reshape(s, n_kv, dh)
    rep = n_heads // n_kv  # grouped-query attention: each kv head serves rep q heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", p, v).reshape(s, n_heads * dh)
    return x + out @ _mat(lw["wo"], control)


def _mamba(x, lw, dims, control):
    """The mixer as its recurrence, one position at a time."""
    heads, p, groups, n, eps = dims
    s, di = x.shape[0], heads * p
    h = _rms(x, lw["op_norm"], eps)
    cd = di + 2 * groups * n
    zxbc = h @ _mat(lw["ssm_in"], control)       # in_proj's [z | xBC] columns
    dt = h @ _mat(lw["ssm_dt_in"], control).T    # and its dt columns, stored [H, D]
    z, xbc = zxbc[:, :di], zxbc[:, di:]
    w = _mat(lw["ssm_conv_w"], control)  # [K, C]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cd), jnp.float32), xbc], axis=0)
    conv = lw["ssm_conv_b"].astype(jnp.float32)[None, :] + sum(
        w[j][None, :] * padded[j:j + s] for j in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, heads, p)
    bs = jnp.repeat(xbc[:, di:di + groups * n].reshape(s, groups, n), heads // groups, axis=1)
    cs = jnp.repeat(xbc[:, di + groups * n:].reshape(s, groups, n), heads // groups, axis=1)
    dts = jax.nn.softplus(dt + lw["ssm_dt_bias"][None, :])  # [S, H]
    a = -jnp.exp(lw["ssm_A_log"].astype(jnp.float32))       # [H]

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, ys = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32), (xs, bs, cs, dts))
    y = ys + lw["ssm_D"][None, :, None] * xs
    y = y.reshape(s, di) * jax.nn.silu(z)
    yg = y.reshape(s, groups, di // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    y = yg.reshape(s, di) * lw["ssm_norm"][None, :]
    return x + y @ _mat(lw["ssm_out"], control)


def _route(x, lw, eps, top_k, norm_topk, scale):
    """NemotronHTopkRouter: sigmoid scores over every expert the router
    knows, selection on score + correction bias, weights from the
    unbiased scores over all the chosen. Returns the normed input, the
    chosen experts and their weights."""
    h = _rms(x, lw["op_norm"], eps)
    scores = jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32))
    _, top_idx = jax.lax.top_k(scores + lw["router_bias"][None, :], top_k)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return h, top_idx, top_w * scale


def _expert_add(acc, h, top_idx, top_w, e, up, down, control):
    """acc += (weight of expert e for each token, 0 where not chosen) * expert_e(h)."""
    w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)
    # a routed expert's up matrix is stored as its down matrix is, [F, D]
    return acc + w_e[:, None] * _relu2_block(h, _mat(up, control).T, _mat(down, control))


def _shared(h, lw, control):
    return _relu2_block(h, _mat(lw["shared_up"], control), _mat(lw["shared_down"], control))


def forward_logits(params, tokens, cfg: Dict, control: bool = False):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under the
    bf16 tree `params`. control: the layers' matrices on the float8 e4m3
    grid."""
    _need_jax()
    eps = float(cfg["layer_norm_epsilon"])
    adims = (cfg["num_attention_heads"], cfg["num_key_value_heads"], int(cfg["head_dim"]), eps)
    mdims = (int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]), int(cfg["n_groups"]),
             int(cfg["ssm_state_size"]), eps)
    first = int(cfg.get("serving", {}).get("experts_held_from", 0))
    held = int(cfg["n_routed_experts"])
    kinds = layer_types(cfg)
    attention = jax.jit(_attention, static_argnums=(2, 3))
    mamba = jax.jit(_mamba, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3, 4, 5))
    expert_add = jax.jit(_expert_add, static_argnums=(7,))
    shared = jax.jit(_shared, static_argnums=(2,))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        n = 0
        for li, lw in enumerate(_layers(params)):
            kind = kinds[li]
            has = "mamba" if "ssm_in" in lw else "moe" if "router" in lw else "attention"
            if kind != has:
                raise ValueError(f"layer {li} of the tree is {has!r}, the pattern says {kind!r}")
            if kind == "mamba":
                x = mamba(x, lw, mdims, control)
            elif kind == "attention":
                x = attention(x, lw, adims, control)
            else:
                if lw["w_up"].shape[0] != held:
                    raise ValueError(f"layer {li} holds {lw['w_up'].shape[0]} experts, "
                                     f"the file says {held}")
                h, top_idx, top_w = route(
                    x, lw, eps, int(cfg["num_experts_per_tok"]),
                    bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]))
                acc = shared(h, lw, control) if "shared_up" in lw else jnp.zeros_like(x)
                for e in range(held):  # the tree's expert e is the router's first + e
                    acc = expert_add(acc, h, top_idx, top_w, first + e,
                                     lw["w_up"][e], lw["w_down"][e], control)
                x = x + acc
            n = li + 1
        if n != len(kinds):
            raise ValueError(f"the tree has {n} layers, the pattern {len(kinds)}")
        x = _rms(x, params["final_norm"], eps)
        return x @ params["lm_head"].astype(jnp.float32)


# -- what a decode step needs -------------------------------------------------

_BYTES = {"bf16": 2, "float32": 4}


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind the configuration runs."""
    kinds = layer_types(cfg)
    return {k: kinds.count(k) for k in ("mamba", "attention", "moe")}


def ssm_inner(cfg: Dict) -> int:
    return int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"])


def ssm_conv_dim(cfg: Dict) -> int:
    return ssm_inner(cfg) + 2 * int(cfg["n_groups"]) * int(cfg["ssm_state_size"])


def mamba_params(cfg: Dict) -> int:
    """in_proj (D x (2 Di + 2 G N + H)), out_proj (Di x D) and the K taps and bias of the conv."""
    d, di, cd = cfg["hidden_size"], ssm_inner(cfg), ssm_conv_dim(cfg)
    return (d * (di + cd + int(cfg["mamba_num_heads"])) + di * d
            + (int(cfg["conv_kernel"]) + 1) * cd)


def attn_params(cfg: Dict) -> int:
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], int(cfg["head_dim"]))
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def expert_params(cfg: Dict) -> int:
    """One routed expert's pair (up, down): no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Dict) -> int:
    return (2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]
            * int(cfg["n_shared_experts"]))


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * int(cfg["router_width"])


def held_share(cfg: Dict) -> float:
    return int(cfg["n_routed_experts"]) / int(cfg["router_width"])


def experts_touched(cfg: Dict, rows: float) -> float:
    """Expected number of distinct HELD experts per sparse layer that
    `rows` tokens route to, for a uniform router (top-k of router_width)."""
    k, width = cfg["num_experts_per_tok"], int(cfg["router_width"])
    return int(cfg["n_routed_experts"]) * (1.0 - (1.0 - k / width) ** max(rows, 0.0))


def flops_per_token(cfg: Dict) -> float:
    """Matmul FLOPs one token needs outside attention's score/value
    products and the SSM update: 2 per weight it multiplies through (the
    chosen experts that are held here, k x the share on average; the
    shared expert, the router, the head's rows held here)."""
    n = layer_counts(cfg)
    k = cfg["num_experts_per_tok"] * held_share(cfg)
    weights = (n["mamba"] * mamba_params(cfg) + n["attention"] * attn_params(cfg)
               + n["moe"] * (k * expert_params(cfg) + shared_params(cfg) + router_params(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])
    return 2.0 * weights


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position, in the layers that hold KV."""
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    return (2 * layer_counts(cfg)["attention"] * cfg["num_key_value_heads"]
            * int(cfg["head_dim"]) * b)


def ssm_state_bytes_per_slot(cfg: Dict) -> int:
    """One slot's SSM state over the Mamba-2 layers: H x P x N float32."""
    return (layer_counts(cfg)["mamba"] * ssm_inner(cfg) * int(cfg["ssm_state_size"])
            * _BYTES[cfg["serving"]["ssm_state_dtype"]])


def conv_state_bytes_per_slot(cfg: Dict) -> int:
    """One slot's conv state over the Mamba-2 layers: K - 1 inputs of [x | B | C], bf16."""
    return layer_counts(cfg)["mamba"] * (int(cfg["conv_kernel"]) - 1) * ssm_conv_dim(cfg) * 2


def weight_bytes(cfg: Dict, touched: Optional[float] = None) -> float:
    """Bytes of the weights one step has to read: the mixers, attentions
    and shared experts once, `touched` routed experts per sparse layer
    (all the held by default), the routers (float32) and the head (the
    embedding's gathered rows are noise)."""
    n = layer_counts(cfg)
    b = _BYTES[cfg["serving"]["weight_dtype"]]
    touched = int(cfg["n_routed_experts"]) if touched is None else touched
    body = (n["mamba"] * mamba_params(cfg) + n["attention"] * attn_params(cfg)
            + n["moe"] * (touched * expert_params(cfg) + shared_params(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])
    return b * body + 4 * n["moe"] * router_params(cfg)


def ssm_update_cost(cfg: Dict, slots: int) -> Tuple[float, float]:
    """(flops, bytes) ONE Mamba-2 layer's state update needs in one decode
    step over `slots` slots: the state read and written once (float32),
    x, B, C (bf16) and dt in, y out; per state element a multiply by the
    decay, a multiply-add of dt x B, and a multiply-add into y."""
    h, p, n = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]), int(cfg["ssm_state_size"])
    g = int(cfg["n_groups"])
    state = h * p * n
    flops = slots * 5.0 * state
    bytes_ = slots * (2 * state * _BYTES[cfg["serving"]["ssm_state_dtype"]]
                      + (h * p + 2 * g * n) * 2 + h * 4 + h * p * 4)
    return flops, bytes_


def decode_step_cost(cfg: Dict, rows: float, context: float,
                     touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each. A live row is a live
    slot: its SSM and conv state are read and written once a step, as its
    KV is read at its live context; the state of a slot that holds no
    request is no part of the need, however many the slab has. `touched`:
    the distinct HELD experts a sparse layer read a step, as the unit
    counted them; absent (a unit that counts none), a uniform router's
    expectation at `rows`."""
    n = layer_counts(cfg)
    h, dh = cfg["num_attention_heads"], int(cfg["head_dim"])
    uf, _ = ssm_update_cost(cfg, rows)
    touched = experts_touched(cfg, rows) if touched is None else touched
    flops = rows * (flops_per_token(cfg) + n["attention"] * h * 4.0 * dh * context) \
        + n["mamba"] * uf
    bytes_ = (weight_bytes(cfg, touched)
              + rows * (context + 1) * kv_bytes_per_token(cfg)
              + 2 * rows * (ssm_state_bytes_per_slot(cfg) + conv_state_bytes_per_slot(cfg)))
    return flops, bytes_


# -- what the grouped expert products need ------------------------------------

def grouped_product_cost(cfg: Dict, rows: float,
                         touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) ONE grouped product (up or down of one sparse layer)
    needs for `rows` live rows: the assignments that land on held experts
    (rows x k x the share) through one D x F matrix each, and that matrix
    of the `touched` held experts read once, activations counted too."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["num_experts_per_tok"] * held_share(cfg)
    touched = experts_touched(cfg, rows) if touched is None else touched
    flops = 2.0 * rows * k * d * f
    bytes_ = touched * d * f * _BYTES[cfg["serving"]["weight_dtype"]] \
        + rows * k * (d + f) * 2
    return flops, bytes_
