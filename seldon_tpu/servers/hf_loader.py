"""HuggingFace checkpoint loader -> stacked param tree: the Llama family
(llama, mistral) and the LFM2 family (lfm2, lfm2_moe: a layer pattern of
short-conv and attention operators, dense or sparse feed-forward; the
patterned tree of models/transformer.py, _load_patterned below).

The reference loads CPU models via joblib/xgboost/mlflow natives; the
TPU build's flagship server needs the LLM equivalent: point `modelUri`
at a HF Llama checkpoint directory (config.json + *.safetensors) and
serve it. This loader reads safetensors SHARD BY SHARD (no torch, no
whole-model host copy), transposes HF's [out, in] projection layout into
this framework's [in, out] einsum layout, and STACKS the per-layer
tensors on the leading [L, ...] axis models/transformer.py scans over.

RoPE convention matches: HF Llama applies rotate_half over a half-split
pairing, exactly models/transformer.py:apply_rope — verified by the
logit-parity test against `transformers`' own forward
(tests/test_hf_loader.py).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, Tuple

import numpy as np

from seldon_tpu.models.config import ModelConfig

logger = logging.getLogger(__name__)


def _rope_scaling_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """Map HF `rope_scaling` (Llama-3.1/3.2 long-context checkpoints)
    onto ModelConfig's flat rope_scaling_* fields. Unknown schemes raise
    rather than silently producing wrong logits at every position."""
    rs = hf.get("rope_scaling")
    if not rs:
        return {}
    # HF renamed "type" -> "rope_type" across versions; accept both.
    rtype = rs.get("rope_type", rs.get("type"))
    if rtype == "default":
        return {}
    if rtype == "linear":
        return {
            "rope_scaling_type": "linear",
            "rope_scaling_factor": float(rs["factor"]),
        }
    if rtype == "llama3":
        return {
            "rope_scaling_type": "llama3",
            "rope_scaling_factor": float(rs["factor"]),
            "rope_scaling_low_freq_factor": float(
                rs.get("low_freq_factor", 1.0)
            ),
            "rope_scaling_high_freq_factor": float(
                rs.get("high_freq_factor", 4.0)
            ),
            "rope_scaling_original_max_position": int(
                rs.get("original_max_position_embeddings", 8192)
            ),
        }
    raise ValueError(
        f"unsupported rope_scaling {rs!r}; this loader implements "
        "'linear' and 'llama3' frequency scaling"
    )


def config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """ModelConfig from an HF llama config.json dict."""
    mt = hf.get("model_type", "llama")
    if mt in ("lfm2", "lfm2_moe"):
        return _lfm2_config(hf)
    if mt not in ("llama", "mistral"):
        raise ValueError(
            f"unsupported model_type {mt!r}; this loader handles the "
            "Llama family (llama, mistral) and the LFM2 family (lfm2, "
            "lfm2_moe)"
        )
    return ModelConfig(
        **_rope_scaling_fields(hf),
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_ff=hf["intermediate_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        eos_token_id=_eos(hf, 2),
        pad_token_id=hf.get("pad_token_id") or 0,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )


def _eos(hf: Dict[str, Any], default: int) -> int:
    eos = hf.get("eos_token_id", default)
    return eos[0] if isinstance(eos, list) else eos


def _lfm2_config(hf: Dict[str, Any]) -> ModelConfig:
    """ModelConfig (patterned stack) from an lfm2 / lfm2_moe config.json:
    layer_types (or the older full_attn_idxs), conv_L_cache, and for
    lfm2_moe the leading dense layers, the expert width and the sigmoid
    router's switches. QK-norm and tied embeddings are the model code's
    (Lfm2Attention's q/k_layernorm; tie_word_embeddings defaults true)."""
    if hf.get("conv_bias"):
        raise ValueError("conv_bias=true is not supported (no conv or "
                         "projection bias in the program's short conv)")
    L = hf["num_hidden_layers"]
    types = hf.get("layer_types")
    if types is None:
        full = set(hf.get("full_attn_idxs") or range(L))
        types = ["full_attention" if i in full else "conv" for i in range(L)]
    d_ff = hf["intermediate_size"]
    moe = hf.get("model_type") == "lfm2_moe"
    if not moe and hf.get("block_auto_adjust_ff_dim", True):
        # Lfm2MLP: the declared width is cut to 2/3, scaled and rounded
        # up to block_multiple_of.
        d_ff = int(2 * d_ff / 3)
        mult = hf.get("block_ffn_dim_multiplier", 1.0)
        if mult is not None:
            d_ff = int(mult * d_ff)
            of = hf.get("block_multiple_of", 256)
            d_ff = of * ((d_ff + of - 1) // of)
    rope = hf.get("rope_parameters") or {}
    kw: Dict[str, Any] = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=L,
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads",
                          hf["num_attention_heads"]),
        d_ff=d_ff,
        max_seq_len=hf.get("max_position_embeddings", 128000),
        rope_theta=float(rope.get("rope_theta",
                                  hf.get("rope_theta", 1000000.0))),
        rms_norm_eps=float(hf.get("norm_eps", 1e-5)),
        eos_token_id=_eos(hf, 2),
        pad_token_id=hf.get("pad_token_id") or 0,
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        layer_types=tuple(types),
        qk_norm=True,
        conv_kernel=int(hf.get("conv_L_cache", 3)),
    )
    if moe:
        kw.update(
            n_experts=int(hf["num_experts"]),
            n_experts_per_token=int(hf["num_experts_per_tok"]),
            n_dense_layers=int(hf.get("num_dense_layers", 0)),
            d_ff_expert=int(hf["moe_intermediate_size"]),
            router="sigmoid",
            router_bias=bool(hf.get("use_expert_bias", False)),
            router_norm_topk=bool(hf.get("norm_topk_prob", True)),
            router_scale=float(hf.get("routed_scaling_factor", 1.0)),
        )
    return ModelConfig(**kw)


# LFM2 tensor name (after "model.layers.<i>.") -> (slot, transpose?, norm?)
_LFM2_LAYER_MAP = {
    "operator_norm.weight": ("op_norm", False, True),
    "ffn_norm.weight": ("ff_norm", False, True),
    "conv.in_proj.weight": ("conv_in", True, False),
    "conv.out_proj.weight": ("conv_out", True, False),
    "self_attn.q_proj.weight": ("wq", True, False),
    "self_attn.k_proj.weight": ("wk", True, False),
    "self_attn.v_proj.weight": ("wv", True, False),
    "self_attn.out_proj.weight": ("wo", True, False),
    "self_attn.q_layernorm.weight": ("q_norm", False, True),
    "self_attn.k_layernorm.weight": ("k_norm", False, True),
    "feed_forward.w1.weight": ("w_gate", True, False),
    "feed_forward.w3.weight": ("w_up", True, False),
    "feed_forward.w2.weight": ("w_down", True, False),
}
_LFM2_EXPERT = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}


def _load_patterned(path: str, cfg: ModelConfig, np_dtype, place):
    """The patterned tree (transformer.init_params' layout for
    cfg.layer_types) from an lfm2 / lfm2_moe checkpoint: per-layer
    tensors by the published names, experts stacked [E, ...], the conv
    taps [D, 1, K] as [K, D], the router (`feed_forward.gate`, [E, D]) as
    float32 [D, E]; then layers stacked by segment and period position
    (transformer.layer_plan)."""
    import ml_dtypes

    from seldon_tpu.models import transformer

    def convert(arr, transpose=False, dtype=np_dtype):
        arr = np.asarray(arr)
        if arr.dtype == np.dtype("V2"):  # raw bf16 view
            arr = arr.view(ml_dtypes.bfloat16)
        return (arr.T if transpose else arr).astype(dtype)

    layers = [dict() for _ in range(cfg.n_layers)]
    experts = [dict() for _ in range(cfg.n_layers)]  # slot -> {e: array}
    top: Dict[str, Any] = {}
    for name, arr in _open_shards(path):
        if name == "model.embed_tokens.weight":
            top["embed"] = convert(arr)
        elif name == "model.embedding_norm.weight":
            top["final_norm"] = convert(arr, dtype=np.float32)
        elif name == "lm_head.weight":
            top["lm_head"] = convert(arr, True)
        elif name.startswith("model.layers."):
            idx_s, _, sub = name[len("model.layers."):].partition(".")
            lp, parts = layers[int(idx_s)], sub.split(".")
            if sub in _LFM2_LAYER_MAP:
                key, tr, norm = _LFM2_LAYER_MAP[sub]
                lp[key] = convert(arr, tr, np.float32 if norm else np_dtype)
            elif sub == "conv.conv.weight":
                lp["conv_w"] = convert(np.asarray(arr)[:, 0, :], True)
            elif sub == "feed_forward.gate.weight":
                lp["router"] = convert(arr, True, np.float32)
            elif sub == "feed_forward.expert_bias":
                lp["router_bias"] = convert(arr, dtype=np.float32)
            elif (len(parts) == 5 and parts[:2] == ["feed_forward", "experts"]
                  and parts[3] in _LFM2_EXPERT and parts[4] == "weight"):
                experts[int(idx_s)].setdefault(
                    _LFM2_EXPERT[parts[3]], {})[int(parts[2])] = \
                    convert(arr, True)
            else:
                logger.warning("skipping unmapped tensor %s", name)
        else:
            logger.warning("skipping unmapped tensor %s", name)
    for i, (lp, ex) in enumerate(zip(layers, experts)):
        for key, by_e in ex.items():
            if sorted(by_e) != list(range(cfg.n_experts)):
                raise ValueError(f"layer {i}: {key} has experts "
                                 f"{sorted(by_e)[:4]}..., not 0..E-1")
            lp[key] = np.stack([by_e[e] for e in range(cfg.n_experts)])
        if cfg.ff_sparse(i) and cfg.router_bias:
            lp.setdefault("router_bias",
                          np.zeros((cfg.n_experts,), np.float32))
    if "embed" not in top or "final_norm" not in top:
        raise ValueError("checkpoint lacks model.embed_tokens.weight or "
                         "model.embedding_norm.weight")
    segments = []
    for seg in transformer.layer_plan(cfg):
        p, period = len(seg.kinds), []
        for j, (op, sparse) in enumerate(seg.kinds):
            members = [layers[seg.first_layer + r * p + j]
                       for r in range(seg.reps)]
            need = {"op_norm", "ff_norm", "w_gate", "w_up", "w_down"}
            need |= ({"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
                     if op == "full_attention"
                     else {"conv_in", "conv_w", "conv_out"})
            if sparse:
                need |= {"router"} | ({"router_bias"} if cfg.router_bias
                                      else set())
            for r, lp in enumerate(members):
                lack = sorted(need - set(lp))
                if lack:
                    raise ValueError(
                        f"checkpoint incomplete: layer "
                        f"{seg.first_layer + r * p + j} ({op}) lacks {lack}")
            period.append({k: place(np.stack([lp[k] for lp in members]))
                           for k in sorted(need)})
        segments.append(tuple(period))
    params: Dict[str, Any] = {
        "embed": place(top["embed"]),
        "segments": tuple(segments),
        "final_norm": place(top["final_norm"]),
    }
    if not cfg.tie_embeddings:
        if "lm_head" not in top:
            raise ValueError(
                "config has tie_word_embeddings=false but no lm_head.weight")
        params["lm_head"] = place(top["lm_head"])
    return params


def _open_shards(path: str):
    """Yield (tensor_name, numpy array) from all safetensors shards."""
    from safetensors import safe_open

    index_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as f:
            weight_map = json.load(f)["weight_map"]
        shards = sorted(set(weight_map.values()))
    else:
        shards = [
            f for f in sorted(os.listdir(path)) if f.endswith(".safetensors")
        ]
        if not shards:
            raise FileNotFoundError(f"no *.safetensors under {path}")

    for shard in shards:
        with safe_open(os.path.join(path, shard), framework="np") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


def load_hf_checkpoint(path: str, dtype: str = "bfloat16",
                       make_shardings=None,
                       ) -> Tuple[Dict[str, Any], ModelConfig]:
    """(params, cfg) from a local HF Llama checkpoint directory.

    `make_shardings(cfg) -> pytree of NamedSharding` (optional): each
    stacked tensor is device_put DIRECTLY to its sharding as it's built,
    so a model larger than one chip's HBM loads onto a mesh without ever
    materializing whole on device 0."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    cfg = config_from_hf(hf_cfg).validate()
    L = cfg.n_layers
    np_dtype = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float32
    if cfg.patterned:
        # Served on one chip (the engine refuses tp > 1): every leaf
        # replicated on whatever mesh make_shardings was built for.
        sh = None
        if make_shardings is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            some = jax.tree_util.tree_leaves(make_shardings(cfg))[0]
            sh = NamedSharding(some.mesh, PartitionSpec())
        params = _load_patterned(
            path, cfg, np_dtype,
            (lambda a: jnp.asarray(a)) if sh is None
            else (lambda a: jax.device_put(a, sh)))
        logger.info(
            "loaded HF checkpoint: %d layers (%d conv, %d attention), "
            "d_model=%d, vocab=%d (%s)", cfg.n_layers, cfg.n_conv_layers,
            cfg.n_attn_layers, cfg.d_model, cfg.vocab_size, dtype)
        return params, cfg

    # Per-layer slots filled as shards stream by; stacked at the end.
    per_layer: Dict[str, list] = {
        k: [None] * L
        for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                  "w_gate", "w_up", "w_down")
    }
    top: Dict[str, Any] = {}

    # HF name -> (slot, transpose?, is_norm)
    layer_map = {
        "input_layernorm.weight": ("attn_norm", False, True),
        "self_attn.q_proj.weight": ("wq", True, False),
        "self_attn.k_proj.weight": ("wk", True, False),
        "self_attn.v_proj.weight": ("wv", True, False),
        "self_attn.o_proj.weight": ("wo", True, False),
        "post_attention_layernorm.weight": ("mlp_norm", False, True),
        "mlp.gate_proj.weight": ("w_gate", True, False),
        "mlp.up_proj.weight": ("w_up", True, False),
        "mlp.down_proj.weight": ("w_down", True, False),
    }

    def convert(arr: np.ndarray, transpose: bool, norm: bool) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.dtype == np.dtype("V2"):  # raw bf16 view
            arr = arr.view(ml_dtypes.bfloat16)
        if transpose:
            arr = arr.T  # HF [out, in] -> einsum [in, out]
        return arr.astype(np.float32 if norm else np_dtype)

    n_seen = 0
    for name, arr in _open_shards(path):
        n_seen += 1
        if name == "model.embed_tokens.weight":
            top["embed"] = convert(arr, False, False)
        elif name == "model.norm.weight":
            top["final_norm"] = convert(arr, False, True)
        elif name == "lm_head.weight":
            top["lm_head"] = convert(arr, True, False)
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_s, _, sub = rest.partition(".")
            slot = layer_map.get(sub)
            if slot is None:
                logger.warning("skipping unmapped tensor %s", name)
                continue
            key, tr, norm = slot
            per_layer[key][int(idx_s)] = convert(arr, tr, norm)
        else:
            logger.warning("skipping unmapped tensor %s", name)

    missing = [
        f"layer {i}.{k}"
        for k, slots in per_layer.items()
        for i, v in enumerate(slots)
        if v is None
    ]
    if missing:
        raise ValueError(
            f"checkpoint incomplete ({n_seen} tensors read); missing: "
            + ", ".join(missing[:8])
        )
    if "embed" not in top:
        raise ValueError("checkpoint has no model.embed_tokens.weight")

    shardings = make_shardings(cfg) if make_shardings is not None else None

    def place(arr: np.ndarray, *path):
        if shardings is None:
            return jnp.asarray(arr)
        ns = shardings
        for key in path:
            ns = ns[key]
        return jax.device_put(arr, ns)

    blocks = {
        k: place(np.stack(v), "blocks", k) for k, v in per_layer.items()
    }
    params: Dict[str, Any] = {
        "embed": place(top["embed"], "embed"),
        "blocks": blocks,
        "final_norm": place(top["final_norm"], "final_norm"),
    }
    if cfg.tie_embeddings:
        if "lm_head" in top:
            logger.warning("tie_word_embeddings set; ignoring lm_head")
    else:
        if "lm_head" not in top:
            raise ValueError(
                "config has tie_word_embeddings=false but no lm_head.weight"
            )
        params["lm_head"] = place(top["lm_head"], "lm_head")
    logger.info(
        "loaded HF checkpoint: %d layers, d_model=%d, vocab=%d (%s)",
        cfg.n_layers, cfg.d_model, cfg.vocab_size, dtype,
    )
    return params, cfg
