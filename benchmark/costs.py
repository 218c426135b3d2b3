"""Operations and bytes the algorithm NEEDS for one call, from shapes.

A copy of the closed-form arithmetic of seldon_tpu/servers/cost_model.py
(flops_per_token, weight/KV bytes), kept here so that
no later PR can move the yardstick, with one difference: the program's
table prices what the dense-slab engine dispatches (every slot, the whole
window, every expert); this one prices what the requests need (live rows,
live context, the experts routed to). The gap between the two is waste,
and a roofline share has to show it.

`cfg` is the benchmark's configuration file as a dict (HF key names)."""

from __future__ import annotations

from typing import Dict, Tuple

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


def decode_step_cost(cfg: Dict, rows: float, context: float) -> Tuple[float, float]:
    """(flops, bytes) one decode step needs for `rows` live rows with a
    mean live context of `context` tokens each."""
    _, h, _, dh, _, layers, _ = _dims(cfg)
    flops = rows * (flops_per_token(cfg) + layers * h * 4.0 * dh * context)
    bytes_ = (weight_bytes(cfg, experts_touched(cfg, rows))
              + rows * (context + 1) * kv_bytes_per_token(cfg))
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: Dict[str, float]) -> Tuple[float, str]:
    """Roofline time and the side that binds."""
    tc = flops / peaks["bf16_flops"]  # activations are bf16: the bf16 peak
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
