"""GSPMD sharding rules for the transformer param/activation trees.

Megatron-style tensor parallelism expressed as PartitionSpecs: attention
heads and FFN hidden dim shard over 'tp' (column-parallel in, row-parallel
out → one psum per block, inserted by XLA); vocab shards over 'tp' for
embed/lm_head; batch over 'dp'; sequence over 'sp' (training/long-context);
MoE experts over 'ep'. Pipeline ('pp') is handled by shard_map microbatching
in parallel/pipeline.py, not by a weight spec.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def param_pspecs(cfg, quantized: bool = False) -> Dict[str, Any]:
    """PartitionSpec pytree matching models.transformer.init_params
    (quantized=True adds the `*_scale` specs models.quantize emits: a
    scale has the weight's shape with axis -2 reduced to 1, so its spec
    is the weight spec with that component un-sharded)."""
    blocks = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
    }
    if cfg.n_experts:
        blocks.update(
            {
                "router": P(None, None, None),
                "w_gate": P(None, "ep", None, "tp"),
                "w_up": P(None, "ep", None, "tp"),
                "w_down": P(None, "ep", "tp", None),
            }
        )
    else:
        blocks.update(
            {
                "w_gate": P(None, None, "tp"),
                "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None),
            }
        )
    specs = {
        "embed": P("tp", None),
        "blocks": blocks,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    if quantized:
        from seldon_tpu.models.quantize import _BLOCK_WEIGHTS

        def scale_spec(spec: P) -> P:
            parts = list(spec)
            parts[-2] = None  # reduced (size-1) axis can't be sharded
            return P(*parts)

        for name in _BLOCK_WEIGHTS:
            if name in blocks:
                blocks[f"{name}_scale"] = scale_spec(blocks[name])
        specs["embed_scale"] = scale_spec(specs["embed"])
        if "lm_head" in specs:
            specs["lm_head_scale"] = scale_spec(specs["lm_head"])
    return specs


def cache_pspec(cfg=None) -> Any:
    """KV-cache shardings: batch over dp, kv heads over tp.

    Returns a spec DICT matching transformer.init_cache's leaves: k/v
    [L, B, 1, T, Hkv * Dh], a token's heads side by side in a row, so
    'tp' takes the row's lanes (whole heads a device while tp divides
    Hkv) (+ k_scale/v_scale [L, B, Hkv, T] for kv_cache_dtype == "int8"
    configs). Apply with `jax.tree.map(..., cache, cache_pspec(cfg))`."""
    kv = P(None, "dp", None, None, "tp")
    specs = {"k": kv, "v": kv}
    if cfg is not None and getattr(cfg, "kv_cache_dtype", "bf16") == "int8":
        scale = P(None, "dp", "tp", None)
        specs.update({"k_scale": scale, "v_scale": scale})
    return specs


def batch_pspec(seq_sharded: bool = False) -> P:
    """Token batch [B, S]."""
    return P("dp", "sp" if seq_sharded else None)


def activation_pspec(seq_sharded: bool = False) -> P:
    """Hidden activations [B, S, D]."""
    return P("dp", "sp" if seq_sharded else None, None)


def named_shardings(mesh: Mesh, pspec_tree: Any) -> Any:
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_tree(tree: Any, pspec_tree: Any, mesh: Mesh) -> Any:
    """Commit a pytree to the mesh under the given specs."""
    return jax.device_put(tree, named_shardings(mesh, pspec_tree))
