"""The family of mistral-7b-v0.3 and mixtral-8x7b (MistralForCausalLM,
MixtralForCausalLM): attention and a SwiGLU MLP in every layer, the MLP
dense or a sparse block of experts routed by softmax over the top-k
router logits; served from the program's seeded int8 tree.

Three parts, each as benchmark/family.py sets out: the key map onto the
program's ModelConfig; the plain reference; the operations and bytes a
decode step NEEDS. `cfg` is the configuration file as a dict.

The reference is written from the published descriptions in
straightforward jax.numpy: float32 under
jax.default_matmul_precision("highest"), a Python loop over layers
(dequantising one layer, for Mixtral one expert, at a time), no cache, no
scan, no batching, no kernels. It shares no code with
seldon_tpu/models/transformer.py. Departures from the published models:
weights are the seeded int8 tree the unit serves (dequantised here), and
rotary embedding pairs dimension i with i + head_dim/2 (the Hugging Face
layout of these checkpoints). Its lower-precision twin, the negative
control, is the same forward pass with the layers' weights on an int4
grid.

The costs are a copy of the closed-form arithmetic of
seldon_tpu/servers/cost_model.py (flops_per_token, weight/KV bytes), kept
here so that no later PR can move the yardstick, with one difference: the
program's table prices what the dense-slab engine dispatches (every slot,
the whole window, every expert); this one prices what the requests need
(live rows, live context, the experts routed to). The gap between the two
is waste, and a roofline share has to show it.

run.py loads this file and never imports JAX, so JAX is imported by the
functions that compute (_need_jax), not by the module."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

CONTROL_BITS = 4
CONTROL = f"int{CONTROL_BITS} grid"


# -- the configuration's keys as the program's ModelConfig -------------------

def model_config_kwargs(cfg: Dict) -> Dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig."""
    serving = cfg.get("serving", {})
    kw = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        n_experts=int(cfg.get("num_local_experts", 0) or 0),
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
    )
    if kw["n_experts"]:
        kw["n_experts_per_token"] = int(cfg["num_experts_per_tok"])
    head_dim = cfg.get("head_dim")
    if head_dim and head_dim * kw["n_heads"] != kw["d_model"]:
        raise ValueError("the program derives head_dim as d_model / n_heads")
    return kw


# -- the plain reference ------------------------------------------------------

def _need_jax() -> None:
    global jax, jnp
    import jax
    import jax.numpy as jnp


def build_params(cfg: Dict, seed: int):
    """The tree the unit serves: the program's seeded initialiser for
    serving.weight_dtype int8, the only one this family is served in."""
    _need_jax()
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import init_params_int8

    if cfg["serving"]["weight_dtype"] != "int8":
        raise ValueError("the reference of this family reads an int8 tree")
    model = ModelConfig(**model_config_kwargs(cfg)).validate()
    return init_params_int8(model, jax.random.key(int(seed)))


def _deq(w, scale, bits=8):
    """int8 weights times their scales; bits < 8 puts them on the coarser
    grid of that many bits first (same scales): the negative control."""
    w = w.astype(jnp.float32)
    if bits < 8:
        step = float(2 ** (8 - bits))
        w = jnp.round(w / step) * step
    return w * scale.astype(jnp.float32)


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


def _attention(x, lw, dims):
    n_heads, n_kv, dh, theta, eps, bits = dims
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ _deq(lw["wq"], lw["wq_scale"], bits)).reshape(s, n_heads, dh)
    k = (h @ _deq(lw["wk"], lw["wk_scale"], bits)).reshape(s, n_kv, dh)
    v = (h @ _deq(lw["wv"], lw["wv_scale"], bits)).reshape(s, n_kv, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv  # grouped-query attention: each kv head serves rep q heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", p, v).reshape(s, n_heads * dh)
    return x + out @ _deq(lw["wo"], lw["wo_scale"], bits)


def _dense_mlp(x, lw, eps, bits):
    h = _rms(x, lw["mlp_norm"], eps)
    return x + _swiglu(h, _deq(lw["w_gate"], lw["w_gate_scale"], bits),
                       _deq(lw["w_up"], lw["w_up_scale"], bits),
                       _deq(lw["w_down"], lw["w_down_scale"], bits))


def _route(x, lw, eps, top_k):
    """Router of MixtralSparseMoeBlock: top-k of the router logits,
    softmax over those k. Returns the normed input, indices and weights."""
    h = _rms(x, lw["mlp_norm"], eps)
    top_vals, top_idx = jax.lax.top_k(h @ lw["router"], top_k)
    return h, top_idx, jax.nn.softmax(top_vals, axis=-1)


def _expert_add(acc, h, top_idx, top_w, e, gate, gs, up, us, down, ds, bits):
    """acc += (weight of expert e for each token, 0 where not routed) * expert_e(h)."""
    w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)
    return acc + w_e[:, None] * _swiglu(h, _deq(gate, gs, bits), _deq(up, us, bits),
                                        _deq(down, ds, bits))


def forward_logits(params, tokens, cfg: Dict, control: bool = False):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under
    the int8 tree `params`. control: the layers' weights on the grid of
    CONTROL_BITS bits."""
    _need_jax()
    bits = CONTROL_BITS if control else 8
    n_layers, n_heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    n_experts = int(cfg.get("num_local_experts", 0) or 0)
    eps = float(cfg["rms_norm_eps"])
    dims = (n_heads, cfg["num_key_value_heads"], cfg["hidden_size"] // n_heads,
            float(cfg["rope_theta"]), eps, bits)
    attention = jax.jit(_attention, static_argnums=(2,))
    dense_mlp = jax.jit(_dense_mlp, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3))
    expert_add = jax.jit(_expert_add, static_argnums=(11,))
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32) \
            * params["embed_scale"].astype(jnp.float32)[0]
        for li in range(n_layers):
            lw = {k: v[li] for k, v in blocks.items()
                  if not (n_experts and k.startswith(("w_gate", "w_up", "w_down")))}
            x = attention(x, lw, dims)
            if not n_experts:
                x = dense_mlp(x, lw, eps, bits)
                continue
            h, top_idx, top_w = route(x, lw, eps, int(cfg["num_experts_per_tok"]))
            acc = jnp.zeros_like(x)
            for e in range(n_experts):
                acc = expert_add(
                    acc, h, top_idx, top_w, e,
                    blocks["w_gate"][li, e], blocks["w_gate_scale"][li, e],
                    blocks["w_up"][li, e], blocks["w_up_scale"][li, e],
                    blocks["w_down"][li, e], blocks["w_down_scale"][li, e], bits)
            x = x + acc
        x = _rms(x, params["final_norm"], eps)
        if "lm_head" in params:
            return x @ _deq(params["lm_head"], params["lm_head_scale"])
        return x @ _deq(params["embed"], params["embed_scale"]).T


# -- what a decode step needs -------------------------------------------------

_BYTES = {"bf16": 2, "int8": 1}


def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return (d, h, hkv, dh, cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def attn_params_per_layer(cfg: Dict) -> int:
    d, h, hkv, dh, _, _, _ = _dims(cfg)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def expert_params(cfg: Dict) -> int:
    """One SwiGLU triple (one expert of an MoE layer, or the dense MLP)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def flops_per_token(cfg: Dict) -> float:
    """Matmul FLOPs one token needs outside attention's score/value
    products: 2 per weight it multiplies through (the experts routed to,
    not all of them), plus the output head."""
    _, _, _, _, _, layers, vocab = _dims(cfg)
    k = cfg.get("num_experts_per_tok", 1) if cfg.get("num_local_experts") else 1
    per_layer = attn_params_per_layer(cfg) + k * expert_params(cfg)
    return 2.0 * (layers * per_layer + cfg["hidden_size"] * vocab)


def kv_bytes_per_token(cfg: Dict) -> int:
    _, _, hkv, dh, _, layers, _ = _dims(cfg)
    b = _BYTES[cfg["serving"]["kv_cache_dtype"]]
    scales = 2 if b == 1 else 0  # one bf16 scale per (token, head)
    return 2 * layers * hkv * (dh * b + scales)


def weight_bytes(cfg: Dict, experts_touched: float = None) -> float:
    """Bytes of the weights one step has to read: everything outside the
    experts once, and `experts_touched` experts per layer (all of them by
    default). Scales are noise and left out."""
    d, _, _, _, _, layers, vocab = _dims(cfg)
    b = _BYTES[cfg["serving"]["weight_dtype"]]
    n_exp = cfg.get("num_local_experts") or 1
    touched = n_exp if experts_touched is None else experts_touched
    per_layer = attn_params_per_layer(cfg) + touched * expert_params(cfg)
    # the head is read whole; of the embedding only the gathered rows
    return b * (layers * per_layer + d * vocab)


def experts_touched(cfg: Dict, rows: float) -> float:
    """Expected number of distinct experts per layer that `rows` tokens
    route to, for a uniform router (top-k of E)."""
    n_exp = cfg.get("num_local_experts") or 1
    if n_exp == 1:
        return 1.0
    k = cfg["num_experts_per_tok"]
    return n_exp * (1.0 - (1.0 - k / n_exp) ** max(rows, 0.0))


def decode_step_cost(cfg: Dict, rows: float, context: float,
                     touched: Optional[float] = None) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each. `touched`: the distinct
    experts a sparse layer read a step, as the unit counted them; absent
    (a unit that counts none, a dense model), a uniform router's
    expectation at `rows`."""
    _, h, _, dh, _, layers, _ = _dims(cfg)
    if touched is None or not cfg.get("num_local_experts"):
        touched = experts_touched(cfg, rows)
    flops = rows * (flops_per_token(cfg) + layers * h * 4.0 * dh * context)
    bytes_ = (weight_bytes(cfg, touched)
              + rows * (context + 1) * kv_bytes_per_token(cfg))
    return flops, bytes_
