"""graftmesh sharding tables: EXACT tensor parallelism over the 'tp' axis.

The serving engine's contract is bit-identical greedy output in every
configuration pair it ships (paged vs dense, spec on/off, chunked vs
one-shot prefill) — so the TP scheme must be exact too, not
Megatron-exact-ish.
Classic Megatron TP partitions the CONTRACTION dimension of the second
matmul in each pair (wo, w_down) and psums partial products; float
addition is not associative, so the reduction order differs from tp=1
and a greedy argmax can flip on near-ties. That would break
`make mesh-audit`'s parity gate, the bench BENCH_MESH assert, and the
whole bit-exact testing discipline the repo leans on.

Instead, graftmesh shards only OUTPUT dimensions and never a
contraction:

 * ``wq`` / ``wk`` / ``wv`` are partitioned on their head output axis
   ('tp' on the last dim): every device computes the FULL ``d_model``
   contraction for its own disjoint slice of heads — K-reduction order
   per output element is identical to tp=1.
 * attention runs per-KV-head with heads sharded on 'tp' (GQA groups
   stay device-local since tp | n_kv_heads); softmax reduces over the
   TOKEN axis, which is never sharded.
 * the attention output is ALL-GATHERED (a pure data movement — exact
   in any dtype) and ``wo`` is kept REPLICATED: the wo matmul runs
   redundantly on every device, bit-identically to tp=1.
 * ``w_gate`` / ``w_up`` shard on the ``d_ff`` output axis; the SwiGLU
   hidden is all-gathered and ``w_down`` (the contraction over d_ff)
   is replicated-redundant, same argument.
 * embeddings / lm_head / norms are replicated; logits, samples and
   every host-visible output are therefore replicated and identical
   across the TP group by construction.
 * the KV cache shards by KV head: the paged pool on its ``Hkv`` axis,
   the dense slab, whose rows hold a token's heads side by side
   (``Hkv * Dh`` lanes), on the row, so that each device holds the
   contiguous lanes of its own ``Hkv / tp`` heads and the decode
   attention contracts over that part of the row alone (``rows``): a
   whole head group a device, never a contraction across devices;
   block tables stay host-side int32 and replicated.

W8A8 stays exact for the same reason: the per-token activation scale is
a max over the (unsharded) feature axis, int8 x int8 -> int32
accumulation is exact integer math, and the sharded weights' per-output
-channel scales ride with their output slice.

The price is redundant wo/w_down/lm_head compute and their full weight
replica per device — the Nitsum-style tradeoff for small TP groups,
where the sharded 2/3 of the matmul stack (qkv + gate/up) dominates.
The cost model prices exactly this split (cost_model.py, tp= params).

MoE blocks are deliberately NOT sharded on 'tp' (their expert_out
matmul contracts d_ff, which would need a psum): expert weights stay
replicated and MoE configs serve tp>1 with attention-only sharding.

Divisibility contract (``validate``): tp | n_kv_heads (and hence
tp | n_heads via GQA) and tp | d_ff.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seldon_tpu.parallel.mesh import AXES

TP_AXIS = AXES[-1]  # "tp" — the innermost axis of the mesh vocabulary

# Block weights whose OUTPUT dim shards on 'tp' (dense MLP only; MoE
# weights replicate — see module docstring).
_SHARDED_BLOCK_WEIGHTS = ("wq", "wk", "wv", "w_gate", "w_up")


def validate(cfg, tp: int) -> None:
    """Raise ValueError unless the config admits an exact tp-way split."""
    tp = int(tp)
    if tp <= 1:
        return
    if getattr(cfg, "patterned", False):
        raise ValueError(
            f"tp={tp}: the exact-TP table knows the homogeneous stack "
            "only; a patterned stack (layer_types) has conv and expert "
            "weights it gives no sharding for")
    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_kv_heads={cfg.n_kv_heads} "
            "(KV heads shard on 'tp')")
    if cfg.n_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} "
            "(query heads shard on 'tp')")
    if cfg.d_ff % tp:
        raise ValueError(
            f"tp={tp} must divide d_ff={cfg.d_ff} "
            "(the SwiGLU hidden shards on 'tp')")


def mesh_tp(mesh: Optional[Mesh]) -> int:
    """Size of the mesh's 'tp' axis (1 when absent/None)."""
    if mesh is None:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get(TP_AXIS, 1))


# -- partition-spec tables ---------------------------------------------------


def _block_spec(name: str, ndim: int, moe: bool) -> P:
    """Spec for one entry of params["blocks"]. Quantization scales
    (``<w>_scale``, shaped like the weight with the contraction dim
    collapsed to 1) shard exactly like their weight: the sharded dim is
    the LAST dim for weight and scale alike."""
    base = name[:-6] if name.endswith("_scale") else name
    if not moe and base in _SHARDED_BLOCK_WEIGHTS:
        return P(*([None] * (ndim - 1) + [TP_AXIS]))
    return P()


def param_pspecs(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    """Exact-TP PartitionSpec tree matching ``params``' structure.

    Everything outside the blocks (embed, final_norm, lm_head, their
    scales) replicates; inside the blocks only the qkv / gate / up
    projections (and their scales) shard, on their output dim.
    """
    moe = bool(getattr(cfg, "n_experts", 0))
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name == "blocks":
            out[name] = {
                bn: _block_spec(bn, np.ndim(bl), moe)
                for bn, bl in leaf.items()
            }
        else:
            out[name] = P()
    return out


def state_leaf_spec(leaf) -> P:
    """Spec for one engine-state leaf, by rank: 5D KV shards its heads
    on 'tp', the paged pool [L, NB, Hkv, block, Dh] on the Hkv axis and
    the slab [L, B, 1, T, Hkv * Dh] (one row a token: axis 2 is 1) on
    the row's lanes, a contiguous group of whole heads a device; the 4D
    int8 scales [L, B|NB, Hkv, T|block] on Hkv; everything else (the
    [B] per-slot scalars) replicates."""
    nd = np.ndim(leaf)
    if nd == 5:
        if np.shape(leaf)[2] == 1:
            return P(None, None, None, None, TP_AXIS)
        return P(None, None, TP_AXIS, None, None)
    if nd == 4:
        return P(None, None, TP_AXIS, None)
    return P()


def state_pspecs(state) -> Any:
    return jax.tree_util.tree_map(state_leaf_spec, state)


def shard_params(mesh: Mesh, cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    """Commit a params tree onto the mesh under the exact-TP table."""
    specs = param_pspecs(cfg, params)
    return jax.device_put(
        params,
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)),
    )


def shard_state(mesh: Mesh, state) -> Any:
    """Commit an engine state tree (cache + per-slot scalars) onto the
    mesh: KV leaves shard on Hkv, scalars replicate."""
    return jax.device_put(
        state,
        jax.tree_util.tree_map(
            lambda leaf: NamedSharding(mesh, state_leaf_spec(leaf)), state),
    )


# -- in-jit constraint hints -------------------------------------------------


@dataclass(frozen=True)
class TpHints:
    """Sharding-constraint helper threaded through the transformer's
    serving paths (``tp=`` kwarg). Carries the mesh so constraints can
    be NamedSharding-pinned from inside jit without global mesh context.

    The constraint points are the whole exactness argument in four
    verbs: ``heads``/``flat`` keep the sharded two-thirds of each block
    sharded (so GSPMD cannot back-propagate replication into the qkv /
    gate / up matmuls), ``gather`` inserts the exact bf16 all-gather in
    front of the replicated wo / w_down contractions, and
    ``constrain_state`` pins the donated cache's output sharding so the
    jit cache key never drifts (a drifted donation sharding would
    retrace on the next dispatch — the compile ledger's zero-live-
    retrace gate would catch it, loudly).
    """

    mesh: Mesh
    tp: int

    def _pin(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def heads(self, x):
        """[B, S, H|Hkv, Dh] with the head axis sharded."""
        return self._pin(x, P(None, None, TP_AXIS, None))

    def flat(self, x):
        """[B, S, H*Dh] or [B, S, F]: head-major flattened / hidden
        features sharded contiguously on the last axis."""
        return self._pin(x, P(None, None, TP_AXIS))

    def rows(self, c):
        """A layer of the slab [B, 1, T, Hkv * Dh], lanes sharded, as
        rows of the device's own heads [B, tp, T, (Hkv / tp) * Dh] with
        the group axis sharded: the same bytes on every device."""
        B, one, T, C = c.shape
        assert one == 1, c.shape
        groups = c.reshape(B, T, self.tp, C // self.tp).transpose(0, 2, 1, 3)
        return self._pin(groups, P(None, TP_AXIS, None, None))

    def gather(self, x):
        """Exact all-gather to replicated — pure data movement, placed
        immediately before a replicated-weight contraction."""
        return self._pin(x, P())

    def constrain_state(self, state):
        """Pin every state leaf to its committed sharding (rank rule of
        state_leaf_spec) at the end of a donating impl."""
        return jax.tree_util.tree_map(
            lambda leaf: self._pin(leaf, state_leaf_spec(leaf)), state)


def hints(mesh: Optional[Mesh], tp: int) -> Optional[TpHints]:
    """TpHints iff tp > 1 (the EngineConfig.tp gate); None otherwise —
    callers keep a None attribute and the unconstrained trace, so the
    tp=1 path stays byte-identical to a build without graftmesh."""
    tp = int(tp)
    if tp <= 1:
        return None
    if mesh is None:
        raise ValueError("EngineConfig.tp > 1 requires a mesh with a "
                         "'tp' axis (servers/mesh_engine.build_tp_mesh)")
    have = mesh_tp(mesh)
    if have != tp:
        raise ValueError(
            f"EngineConfig.tp={tp} but the mesh carries a {have}-way "
            f"'{TP_AXIS}' axis")
    return TpHints(mesh=mesh, tp=tp)
