"""Plain reference of the Mistral / Mixtral forward pass, and the parity
check that `correct` depends on.

The forward pass is written from the published descriptions
(MistralForCausalLM, MixtralSparseMoeBlock) in straightforward jax.numpy:
float32 under jax.default_matmul_precision("highest"), a Python loop over
layers (dequantising one layer, for Mixtral one expert, at a time), no
cache, no scan, no batching, no kernels. It shares no code with
seldon_tpu/models/transformer.py. Departures from the published models:
weights are the seeded int8 tree the unit serves (dequantised here), and
rotary embedding pairs dimension i with i + head_dim/2 (the Hugging Face
layout of these checkpoints).

As a program (a child of run.py, started only after the unit has exited
and the chip is free):

    python3 benchmark/reference.py <job.json>

rebuilds the unit's weights (quantize.init_params_int8, the same seed),
runs the forward pass over each probe's prompt + the engine's greedy
tokens, and checks position by position that the engine's token has a
reference logit within epsilon of the reference's maximum (at a stated
share of the positions, and within epsilon_all at every one: the
configuration file gives the three numbers and their reasons). As a
negative control it then runs the same forward pass with the layer
weights on an int4 grid, takes that model's greedy tokens at the same
positions and judges them by the same criterion: the marker records
whether the criterion tells the lower precision from the served one.
Writes the marker file the job names.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


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


def forward_logits(params, tokens, cfg, bits=8):
    """Logits [S, V] (float32) of the token sequence `tokens` [S] under
    the int8 tree `params`; cfg is a seldon_tpu ModelConfig (only its
    sizes are read). bits < 8: the layers' weights on that coarser grid."""
    eps = float(cfg.rms_norm_eps)
    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.d_model // cfg.n_heads,
            float(cfg.rope_theta), eps, bits)
    attention = jax.jit(_attention, static_argnums=(2,))
    dense_mlp = jax.jit(_dense_mlp, static_argnums=(2, 3))
    route = jax.jit(_route, static_argnums=(2, 3))
    expert_add = jax.jit(_expert_add, static_argnums=(11,))
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32) \
            * params["embed_scale"].astype(jnp.float32)[0]
        for li in range(cfg.n_layers):
            lw = {k: v[li] for k, v in blocks.items()
                  if not (cfg.n_experts and k.startswith(("w_gate", "w_up", "w_down")))}
            x = attention(x, lw, dims)
            if not cfg.n_experts:
                x = dense_mlp(x, lw, eps, bits)
                continue
            h, top_idx, top_w = route(x, lw, eps, cfg.n_experts_per_token)
            acc = jnp.zeros_like(x)
            for e in range(cfg.n_experts):
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


CONTROL_BITS = 4


def logit_gaps(params, cfg, probes, control_bits=0):
    """For each (prompt, engine tokens): the gap between the reference's
    largest logit and the logit of the engine's token, at every generated
    position (teacher-forced on the engine's own tokens). With
    control_bits, also the gaps of the greedy tokens of the model whose
    layers' weights lie on that coarser grid, at the same positions."""
    gaps, control = [], []
    for prompt, toks in probes:
        seq = jnp.asarray(list(prompt) + list(toks[:-1]), jnp.int32)
        at = jnp.arange(len(toks))
        logits = forward_logits(params, seq, cfg)[len(prompt) - 1:]
        top = jnp.max(logits, axis=-1)
        gaps.extend(float(g) for g in top - logits[at, jnp.asarray(toks, jnp.int32)])
        if control_bits:
            coarse = forward_logits(params, seq, cfg, control_bits)[len(prompt) - 1:]
            control.extend(float(g) for g in top - logits[at, jnp.argmax(coarse, axis=-1)])
    return gaps, control


def judge(gaps, par):
    """The configuration's criterion: a stated share of the positions
    within epsilon, every one within epsilon_all."""
    eps = float(par["epsilon"])
    share = sum(1 for g in gaps if g <= eps) / len(gaps)
    ok = (share >= float(par.get("min_share_within", 1.0))
          and max(gaps) <= float(par.get("epsilon_all", eps)))
    return bool(ok), share


def main(job_file: str) -> int:
    t0 = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    sys.path.insert(0, os.path.dirname(here))
    with open(job_file) as f:
        job = json.load(f)
    with open(job["config"]) as f:
        cfg_file = json.load(f)

    from launcher import model_config_kwargs
    from seldon_tpu import device
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import init_params_int8

    device.enable_compile_cache()
    cfg = ModelConfig(**model_config_kwargs(cfg_file)).validate()
    params = init_params_int8(cfg, jax.random.key(int(job["seed"])))
    gaps, control = logit_gaps(params, cfg, job["probes"], CONTROL_BITS)
    par = cfg_file["parity"]
    ok, share = judge(gaps, par)
    passes, control_share = judge(control, par)
    dev = jax.devices()[0]
    marker = {
        "config": cfg_file["name"], "config_sha": job["config_sha"],
        "ok": ok, "epsilon": float(par["epsilon"]), "share_within": share,
        "max_gap": max(gaps), "positions": len(gaps),
        "argmax_agree": sum(1 for g in gaps if g == 0.0),
        "control": {"weights": f"int{CONTROL_BITS} grid", "rejected": not passes,
                    "share_within": control_share, "max_gap": max(control)},
        "layers": cfg.n_layers, "seed": job["seed"],
        "device": f"{dev.platform}/{dev.device_kind}",
        "seconds": time.perf_counter() - t0,
    }
    os.makedirs(os.path.dirname(job["marker"]), exist_ok=True)
    with open(job["marker"], "w") as f:
        json.dump(marker, f)
    print(json.dumps(marker), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
