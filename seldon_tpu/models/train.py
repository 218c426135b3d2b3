"""Sharded training step (fine-tuning path + the driver's multichip dryrun).

The reference is serving-only (SURVEY.md §2.9) — this is green-field
TPU-native capability: a pjit'd next-token cross-entropy step with optax,
params/grads/optimizer-state all sharded by the same GSPMD specs as
inference (dp batch, sp sequence, tp weights, ep experts), rematerialized
blocks (`jax.checkpoint`) to trade FLOPs for HBM. The init fn is jitted
with explicit out-shardings so full-size params materialize directly
sharded — they never exist whole on one host.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_unflatten

from seldon_tpu.models import transformer
from seldon_tpu.models.config import ModelConfig
from seldon_tpu.parallel import sharding as shd


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def _decay_mask(params):
    """Decay matrices only — norm gains are [L, D] in the layer-stacked
    layout, so an ndim test would wrongly decay them; go by name."""
    leaves, treedef = tree_flatten_with_path(params)
    out = [
        leaf.ndim >= 2 and not any("norm" in str(k) for k in path)
        for path, leaf in leaves
    ]
    return tree_unflatten(treedef, out)


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                   warmup: int = 100, total_steps: int = 10000):
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), end_value=lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay,
                    mask=_decay_mask),
    )


MOE_AUX_WEIGHT = 0.01


def loss_fn(params, tokens, loss_mask, cfg: ModelConfig, act_spec=None,
            forward_fn=None, ring_mesh=None):
    """Next-token CE (+ router load-balance aux for MoE configs).
    tokens [B,S]; loss_mask [B,S] (0 on pad/prompt).
    forward_fn overrides the dense forward (pipeline-parallel path);
    ring_mesh activates ring attention (attn_impl == "ring")."""
    # No load-balance loss, no remat and no gradient of the grouped
    # kernel there: refuse by name, do not train something else.
    transformer.refuse_patterned(cfg, "training (models/train.py)")
    if forward_fn is not None:
        logits, aux = forward_fn(params, tokens)
    else:
        logits, aux = transformer.forward(params, tokens, cfg,
                                          act_spec=act_spec,
                                          remat=True, return_aux=True,
                                          ring_mesh=ring_mesh)
    targets = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    mask = loss_mask[:, 1:].astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    if cfg.n_experts:
        ce = ce + MOE_AUX_WEIGHT * aux["moe_lb_loss"]
    return ce


def _shardings_like(shape_tree, params_ns_tree, repl: NamedSharding):
    """Sharding tree for an arbitrary state pytree: any leaf whose key-path
    SUFFIX matches a param leaf (optax moments mirror the param tree
    structure) inherits that param's sharding; everything else replicates."""
    pleaves, _ = tree_flatten_with_path(
        params_ns_tree, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    pmap = {tuple(str(k) for k in path): ns for path, ns in pleaves}

    leaves, treedef = tree_flatten_with_path(shape_tree)
    out = []
    for path, leaf in leaves:
        keys = tuple(str(k) for k in path)
        ns = repl
        for i in range(len(keys)):
            hit = pmap.get(keys[i:])
            if hit is not None:
                ns = hit
                break
        out.append(ns)
    return tree_unflatten(treedef, out)


def make_sharded_train_step(mesh: Mesh, cfg: ModelConfig, optimizer,
                            seq_sharded: bool = True,
                            n_microbatches: int = 4):
    """Returns (init_fn, step_fn).

    init_fn(key) -> TrainState, materialized sharded on `mesh`.
    step_fn(state, tokens, loss_mask) -> (state, metrics); donates state.

    If the mesh has a pp axis > 1, the layer stack is pipeline-parallel:
    weights shard their layer axis over 'pp' and the forward runs the
    GPipe microbatch schedule (parallel/pipeline.py); dp/sp/tp/ep compose
    unchanged.
    """
    cfg = cfg.validate()
    pp = mesh.shape.get("pp", 1)
    forward_fn = None
    if pp > 1:
        from seldon_tpu.parallel import pipeline

        forward_fn = pipeline.make_pipeline_forward(
            mesh, cfg, n_microbatches=n_microbatches, remat=True
        )
        param_specs = pipeline.pp_param_pspecs(cfg)
    else:
        param_specs = shd.param_pspecs(cfg)
    act_spec = NamedSharding(mesh, shd.activation_pspec(seq_sharded))
    params_ns = shd.named_shardings(mesh, param_specs)
    repl = NamedSharding(mesh, P())
    batch_ns = NamedSharding(mesh, shd.batch_pspec(seq_sharded))

    def _init(key):
        params = transformer.init_params(cfg, key)
        return TrainState(
            jnp.zeros((), jnp.int32), params, optimizer.init(params)
        )

    state_shape = jax.eval_shape(_init, jax.random.key(0))
    state_ns = _shardings_like(state_shape, params_ns, repl)

    init_fn = jax.jit(_init, out_shardings=state_ns)

    ring_mesh = (
        mesh if (cfg.attn_impl == "ring" and mesh.shape.get("sp", 1) > 1)
        else None
    )

    def _step(state: TrainState, tokens, loss_mask):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, tokens, loss_mask, cfg,
            None if forward_fn is not None else act_spec, forward_fn,
            ring_mesh,
        )
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
        return TrainState(state.step + 1, params, opt_state), metrics

    step_fn = jax.jit(
        _step,
        in_shardings=(state_ns, batch_ns, batch_ns),
        out_shardings=(state_ns, repl),
        donate_argnums=(0,),
    )
    return init_fn, step_fn
