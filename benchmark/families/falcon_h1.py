"""The family of Falcon-H1-34B-Instruct (`model_type` falcon_h1): every
layer is the same block, a grouped-query attention and a Mamba-2 mixer that
read the same normed input IN PARALLEL and are summed into the residual,
then a dense SwiGLU; fixed scalar multipliers (muP) on the embedding, the
logits and nearly every projection; untied head. Served from the program's
seeded bf16 tree.

Keys of a configuration file of this family (Hugging Face names, values as
run): hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, intermediate_size, vocab_size,
max_position_embeddings, rms_norm_eps, rope_theta (rope_scaling must be
null), tie_word_embeddings, mamba_n_heads, mamba_d_head, mamba_n_groups,
mamba_d_state, mamba_d_conv, mamba_chunk_size, mamba_d_ssm (must be
mamba_n_heads x mamba_d_head; mamba_expand is not read by the mixer),
mamba_conv_bias / mamba_rms_norm (must be true), mamba_norm_before_gate
(must be false), every *_bias false, hidden_act silu, and the multipliers:
embedding_multiplier, lm_head_multiplier, attention_in_multiplier,
attention_out_multiplier, key_multiplier, ssm_in_multiplier,
ssm_out_multiplier, ssm_multipliers (five: the z, x, B, C, dt segments of
the mixer's input projection), mlp_multipliers (two: gate, down);
`serving` weight_dtype / kv_cache_dtype (bf16), ssm_state_dtype (float32),
kv_budget_tokens, window_tokens.

The layer equations (x is [S, D]; eps = rms_norm_eps; c the file's keys),
as FalconH1DecoderLayer / FalconH1Mixer / FalconH1Attention / FalconH1MLP:

  x0        embed[tokens] * c.embedding_multiplier
  layer     h = RMSNorm(x; op_norm)
  attention a = h * c.attention_in_multiplier;
            q = a Wq, k = (a Wk) * c.key_multiplier, v = a Wv (no bias);
            q, k <- rope (pair i with i + head_dim / 2, theta rope_theta,
            all head_dim dims); causal softmax at head_dim ** -0.5, each
            KV head serving num_attention_heads / num_key_value_heads
            query heads; A = (.. Wo) * c.attention_out_multiplier
  mixer     s = h * c.ssm_in_multiplier;
            [z | x | B | C | dt] = (s W_in) * mup_vector, widths Di | Di |
            G N | G N | H with Di = mamba_n_heads x mamba_d_head, the
            vector c.ssm_multipliers[0..4] over those five segments;
            [x | B | C] = silu(conv_K([x | B | C]) + b), causal, depthwise;
            dt = softplus(dt + dt_bias); A = -exp(A_log); g(h) = h // (H / G);
            S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)];
            y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h];
            y = GroupRMSNorm(y * silu(z)) (gate first, then each of the G
            groups of Di / G channels normed, times the weight);
            M = (y W_out) * c.ssm_out_multiplier
  sum       x = x + A + M
  SwiGLU    g = RMSNorm(x; ff_norm);
            x = x + ((silu((g W_gate) * c.mlp_multipliers[0]) * (g W_up))
                     W_down) * c.mlp_multipliers[1]
  head      (RMSNorm(x; final_norm) @ lm_head) * c.lm_head_multiplier

The reference follows these to the letter in straightforward jax.numpy:
float32 under jax.default_matmul_precision("highest"), a Python loop over
layers, the mixer as the recurrence above one token at a time (a lax.scan
over positions: no chunks, no carried cache), no kernels, no code of
seldon_tpu/models/transformer.py. Departures from the published model:
weights are the program's seeded tree (as served, bf16, read here in
float32), in which every matrix whose input or output a multiplier scales
is drawn at the usual scale divided by that multiplier (the file's
`assumed`); conv taps are stored [K, C] (published [C, 1, K]); in_proj is
stored as two matrices, its [z | x | B | C] columns [D, 2 Di + 2 G N] and
its dt columns [H, D]. The tree's layout is the program's: `segments`,
each a period of layer kinds stacked over its repeats; the reference walks
it in layer order (_layers).

Its lower-precision twin, the negative control: the same forward pass with
every layer's matrices (the attention's, the mixer's projections and conv
taps, the SwiGLU's; not norms, A_log, dt_bias, D, the conv bias, embedding
or head) rounded to float8 e4m3, the nearest precision below the served
bf16.

The costs price what a decode step NEEDS, whatever program serves it:
every layer's weights and the head once, the SSM and conv state of the
slots that hold a request read and written once in every layer, KV of
every layer at the live context.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

CONTROL = "float8 e4m3 grid"
KIND = "attention_mamba"
HEAD_BLOCK = 32768  # columns of the head the reference multiplies through at a time


# -- the configuration's keys as the program's ModelConfig -------------------

def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig. Every value is what
    a JSON round trip of the program's config gives back (layer_types and
    ssm_mults lists, multipliers floats), which is how run.check_metadata
    compares."""
    serving = cfg.get("serving", {})
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "projectors_bias"):
        if cfg.get(key):
            raise ValueError(f"the program's projections have no bias ({key})")
    if not cfg.get("mamba_conv_bias", True):
        raise ValueError("the program's Mamba-2 convolution has its bias (mamba_conv_bias)")
    if not cfg.get("mamba_rms_norm", True) or cfg.get("mamba_norm_before_gate", False):
        raise ValueError("the program's mixer gates, then norms by group "
                         "(mamba_rms_norm true, mamba_norm_before_gate false)")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("this family's feed-forward and gate are silu")
    if cfg.get("rope_scaling") or cfg.get("attn_layer_indices"):
        raise ValueError("the program runs no rope_scaling here, and attention in every layer")
    heads, width = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if int(cfg.get("mamba_d_ssm", heads * width)) != heads * width:
        raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head")
    if serving.get("ssm_state_dtype", "float32") != "float32":
        raise ValueError("the program keeps the SSM state in float32")
    if len(cfg["ssm_multipliers"]) != 5 or len(cfg["mlp_multipliers"]) != 2:
        raise ValueError("ssm_multipliers has five values, mlp_multipliers two")
    return dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=int(cfg["head_dim"]),
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
        layer_types=[KIND] * int(cfg["num_hidden_layers"]),
        conv_kernel=int(cfg["mamba_d_conv"]),
        ssm_heads=heads,
        ssm_head_dim=width,
        ssm_groups=int(cfg["mamba_n_groups"]),
        ssm_state=int(cfg["mamba_d_state"]),
        ssm_chunk=int(cfg["mamba_chunk_size"]),
        embed_mult=float(cfg["embedding_multiplier"]),
        logits_mult=float(cfg["lm_head_multiplier"]),
        attn_in_mult=float(cfg["attention_in_multiplier"]),
        attn_out_mult=float(cfg["attention_out_multiplier"]),
        key_mult=float(cfg["key_multiplier"]),
        ssm_in_mult=float(cfg["ssm_in_multiplier"]),
        ssm_out_mult=float(cfg["ssm_out_multiplier"]),
        ssm_mults=[float(m) for m in cfg["ssm_multipliers"]],
        mlp_gate_mult=float(cfg["mlp_multipliers"][0]),
        mlp_down_mult=float(cfg["mlp_multipliers"][1]),
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


def _rope(x, theta):
    """x: [S, H, Dh]; position s rotates pair (i, i + Dh/2) by s * theta^(-2i/Dh)."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, lw, dims, mults, control):
    """The attention branch on the normed input h [S, D]: A [S, D]."""
    n_heads, n_kv, dh, theta = dims
    m_in, m_out, m_key = mults
    s = h.shape[0]
    a = h * m_in
    q = (a @ _mat(lw["wq"], control)).reshape(s, n_heads, dh)
    k = ((a @ _mat(lw["wk"], control)) * m_key).reshape(s, n_kv, dh)
    v = (a @ _mat(lw["wv"], control)).reshape(s, n_kv, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv  # grouped-query attention: each kv head serves rep q heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", p, v).reshape(s, n_heads * dh)
    return (out @ _mat(lw["wo"], control)) * m_out


def _mixer(h, lw, dims, mults, control):
    """The Mamba-2 branch on the normed input h [S, D], as its recurrence,
    one position at a time: M [S, D]."""
    heads, p, groups, n, eps = dims
    m_in, m_out, (m_z, m_x, m_b, m_c, m_dt) = mults
    s, di, gn = h.shape[0], heads * p, groups * n
    cd = di + 2 * gn
    u = h * m_in
    zxbc = u @ _mat(lw["ssm_in"], control)       # in_proj's [z | x | B | C] columns
    dt = (u @ _mat(lw["ssm_dt_in"], control).T) * m_dt  # and its dt columns, stored [H, D]
    z = zxbc[:, :di] * m_z
    xbc = jnp.concatenate([zxbc[:, di:2 * di] * m_x, zxbc[:, 2 * di:2 * di + gn] * m_b,
                           zxbc[:, 2 * di + gn:] * m_c], axis=-1)
    w = _mat(lw["ssm_conv_w"], control)  # [K, C]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cd), jnp.float32), xbc], axis=0)
    conv = lw["ssm_conv_b"].astype(jnp.float32)[None, :] + sum(
        w[j][None, :] * padded[j:j + s] for j in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, heads, p)
    bs = jnp.repeat(xbc[:, di:di + gn].reshape(s, groups, n), heads // groups, axis=1)
    cs = jnp.repeat(xbc[:, di + gn:].reshape(s, groups, n), heads // groups, axis=1)
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
    return (y @ _mat(lw["ssm_out"], control)) * m_out


def _layer(x, lw, adims, amults, mdims, mmults, fmults, eps, control, branches):
    """One decoder layer. `branches` = (attention on, mixer on): the tests
    switch one off to see that the other is not dead."""
    h = _rms(x, lw["op_norm"], eps)
    if branches[0]:
        x = x + _attention(h, lw, adims, amults, control)
    if branches[1]:
        x = x + _mixer(h, lw, mdims, mmults, control)
    g = _rms(x, lw["ff_norm"], eps)
    hidden = jax.nn.silu((g @ _mat(lw["w_gate"], control)) * fmults[0]) \
        * (g @ _mat(lw["w_up"], control))
    return x + (hidden @ _mat(lw["w_down"], control)) * fmults[1]


def forward_logits(params, tokens, cfg: Dict, control: bool = False,
                   branches: Tuple[bool, bool] = (True, True)):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under the
    bf16 tree `params`. control: the layers' matrices on the float8 e4m3
    grid."""
    _need_jax()
    eps = float(cfg["rms_norm_eps"])
    adims = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
             int(cfg["head_dim"]), float(cfg["rope_theta"]))
    amults = (float(cfg["attention_in_multiplier"]), float(cfg["attention_out_multiplier"]),
              float(cfg["key_multiplier"]))
    mdims = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_n_groups"]),
             int(cfg["mamba_d_state"]), eps)
    mmults = (float(cfg["ssm_in_multiplier"]), float(cfg["ssm_out_multiplier"]),
              tuple(float(m) for m in cfg["ssm_multipliers"]))
    fmults = tuple(float(m) for m in cfg["mlp_multipliers"])
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32) \
            * float(cfg["embedding_multiplier"])
        n = 0
        for n, lw in enumerate(_layers(params), 1):
            if not ("wq" in lw and "ssm_in" in lw and "w_gate" in lw):
                raise ValueError(f"layer {n - 1} of the tree is not an attention + mixer "
                                 f"+ SwiGLU block: {sorted(lw)}")
            x = layer(x, lw, adims, amults, mdims, mmults, fmults, eps, control,
                      tuple(branches))
        if n != int(cfg["num_hidden_layers"]):
            raise ValueError(f"the tree has {n} layers, the file {cfg['num_hidden_layers']}")
        x = _rms(x, params["final_norm"], eps)
        # the head in blocks of columns: in float32 the whole of it is 5.3 GB
        # at the published 5120 x 261120, beside the tree on the same chip
        head, cols = params["lm_head"], params["lm_head"].shape[1]
        logits = jnp.concatenate(
            [x @ head[:, c:c + HEAD_BLOCK].astype(jnp.float32)
             for c in range(0, cols, HEAD_BLOCK)], axis=-1)
        return logits * float(cfg["lm_head_multiplier"])


# -- what a decode step needs -------------------------------------------------

_BYTES = {"bf16": 2, "float32": 4}


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers hold each kind of block: every layer holds all
    three, so a layer counts once under "attention" (KV), once under
    "mamba" (SSM and conv state) and once under "dense"."""
    n = int(cfg["num_hidden_layers"])
    return {"mamba": n, "attention": n, "dense": n}


def ssm_inner(cfg: Dict) -> int:
    return int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])


def ssm_conv_dim(cfg: Dict) -> int:
    return ssm_inner(cfg) + 2 * int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])


def mamba_params(cfg: Dict) -> int:
    """in_proj (D x (2 Di + 2 G N + H)), out_proj (Di x D) and the K taps and bias of the conv."""
    d, di, cd = cfg["hidden_size"], ssm_inner(cfg), ssm_conv_dim(cfg)
    return (d * (di + cd + int(cfg["mamba_n_heads"])) + di * d
            + (int(cfg["mamba_d_conv"]) + 1) * cd)


def attn_params(cfg: Dict) -> int:
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], int(cfg["head_dim"]))
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: Dict) -> int:
    """Matrix weights of one layer: attention, mixer, SwiGLU."""
    return attn_params(cfg) + mamba_params(cfg) + mlp_params(cfg)


def head_params(cfg: Dict) -> int:
    """The untied head (the embedding is as large again; a step gathers a
    few of its rows, which is noise)."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def flops_per_token(cfg: Dict) -> float:
    """Matmul FLOPs one token needs outside attention's score/value
    products and the SSM update: 2 per weight it multiplies through."""
    return 2.0 * (int(cfg["num_hidden_layers"]) * layer_params(cfg) + head_params(cfg))


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position, in every layer."""
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    return (2 * layer_counts(cfg)["attention"] * cfg["num_key_value_heads"]
            * int(cfg["head_dim"]) * b)


def ssm_state_bytes_per_slot(cfg: Dict) -> int:
    """One slot's SSM state over the layers: H x P x N float32 each."""
    return (layer_counts(cfg)["mamba"] * ssm_inner(cfg) * int(cfg["mamba_d_state"])
            * _BYTES[cfg["serving"]["ssm_state_dtype"]])


def conv_state_bytes_per_slot(cfg: Dict) -> int:
    """One slot's conv state over the layers: K - 1 inputs of [x | B | C], bf16."""
    return layer_counts(cfg)["mamba"] * (int(cfg["mamba_d_conv"]) - 1) * ssm_conv_dim(cfg) * 2


def weight_bytes(cfg: Dict) -> float:
    """Bytes of the weights one step has to read: every layer and the head."""
    b = _BYTES[cfg["serving"]["weight_dtype"]]
    return b * (int(cfg["num_hidden_layers"]) * layer_params(cfg) + head_params(cfg))


def ssm_update_cost(cfg: Dict, slots: int) -> Tuple[float, float]:
    """(flops, bytes) ONE layer's state update needs in one decode step
    over `slots` slots: the state read and written once (float32), x, B, C
    (bf16) and dt in, y out; per state element a multiply by the decay, a
    multiply-add of dt x B, and a multiply-add into y. The same work as
    families/nemotron_h.py counts, at this family's key names."""
    h, p, n = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_d_state"])
    g = int(cfg["mamba_n_groups"])
    state = h * p * n
    flops = slots * 5.0 * state
    bytes_ = slots * (2 * state * _BYTES[cfg["serving"]["ssm_state_dtype"]]
                      + (h * p + 2 * g * n) * 2 + h * 4 + h * p * 4)
    return flops, bytes_


def decode_step_cost(cfg: Dict, rows: float, context: float) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each. A live row is a live
    slot: its SSM and conv state are read and written once a step, as its
    KV is read at its live context; the state of a slot that holds no
    request is no part of the need, however many the slab has."""
    n = layer_counts(cfg)
    h, dh = cfg["num_attention_heads"], int(cfg["head_dim"])
    uf, _ = ssm_update_cost(cfg, rows)
    flops = rows * (flops_per_token(cfg) + n["attention"] * h * 4.0 * dh * context) \
        + n["mamba"] * uf
    bytes_ = (weight_bytes(cfg)
              + rows * (context + 1) * kv_bytes_per_token(cfg)
              + 2 * rows * (ssm_state_bytes_per_slot(cfg) + conv_state_bytes_per_slot(cfg)))
    return flops, bytes_
