"""Token -> expert dispatch: the sparse feed-forward block computed for
the experts that hold tokens, not for every expert.

`moe_block` (models/transformer.py) runs every expert on every token and
mixes with a sparsified weight matrix: E/k times the FLOPs that are
needed and, in decode, a read of every expert's weights whatever the
rows route to. Here each (token, selected expert) pair is one row of a
flat assignment list, sorted by expert; the three expert products are
GROUPED matmuls over that list (rows of group e times expert e's
matrix), so FLOPs are tokens x k, not tokens x E, and a group with no
rows costs neither FLOPs nor a read of its weights. Shapes are static:
the list always has tokens x k rows; rows that are not live (dead slab
slots in decode, right-padding in prefill) are routed to no expert: they
sort behind the last group, no group covers them, and they come back as
zeros.

Two routers (`route`): "softmax" is Mixtral's (top-k of the logits,
softmax over those k, times a scale where the model has one); "sigmoid" scores each expert by sigmoid(logit),
SELECTS on score + bias, WEIGHTS by the unbiased score, optionally
renormalised over the k, times a scale.

Who runs it: the patterned stack's sparse layers (transformer._sparse_ff:
lfm2, nemotron_h; bf16 experts) and, on the default engine's two runners
with the stack whole on one device, the homogeneous stack's sparse block
(transformer._mlp_res handed the expert stacks: Mixtral, int8 or bf16
experts). moe_block keeps training's forward, tp > 1, a mesh of several
devices and the paged, prefix, chunked and speculative runners.

`grouped_matmul` is the one product, chosen by what it observes. On a
TPU a bf16 or float32 stack goes to the Pallas megablox grouped matmul
(jax.experimental.pallas.ops.tpu.megablox.gmm, which a device trace
shows under GROUPED_MATMUL_NAME) and an int8 stack, which megablox
refuses, with its per-output-channel scales to ops/gmm_int8 (the same
walk; a tile crosses HBM as int8 and is widened in VMEM). Elsewhere
`jax.lax.ragged_dot`, on the dequantised stack if it is int8. PERF.md §5
has the chip readings the choices were made from
(tools/probe_moe_dispatch.py takes them).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_tpu.ops import gmm_int8

# What the expert products are called in a device trace on a TPU: the
# name of megablox's pallas_call, which XLA keeps as the instruction's
# name ("%gmm.3 = ... custom-call(...)"; benchmark/layer_metrics reads
# it). Checked on the chip with benchmark/xplane.py (PERF.md §5).
GROUPED_MATMUL_NAME = "gmm"

# megablox tiles (m, k, n), from readings on a v5e at E=64, D=2048,
# F=1536, top-4 (tools/probe_moe_dispatch.py; PERF.md §5). k is whole:
# with one k-step the weight tile of a group stays put while the grid
# walks that group's m-tiles, so an expert's weights stream from HBM
# once per call however many rows it holds (k 1024 cost 1.6x at 8192
# tokens). m is rows of the assignment list: 128 where a few rows an
# expert is the rule (decode: one touched expert costs one m-tile of MXU
# work, which the weight read hides), 256 for prefill-sized lists (512
# was slower at 1024 and at 8192 tokens). n is sized so that one grid
# step streams a weight tile of ~2 MB.
_GMM_TILE_M_SMALL = 128
# int8 weights (ops/gmm_int8; readings at E=8, D=4096, F=14336, top-2,
# PERF.md §5): 128 rows against a tile of one byte an element are 256
# FLOPs a byte, the chip's ridge, so the matrix unit's pass over a tile
# no longer hides behind its read: 64 rows read 675 GB/s where 128 read
# 600 (2 live rows of 64). k and n tiles are twice as long (4 MB of
# weights a grid step): half the k steps' passes over the float32 sum
# cost prefill 5 % less at 1024 and 8192 tokens, decode nothing.
_GMM_TILE_M_SMALL_INT8 = 64
_GMM_TILE_M_LARGE = 256
_GMM_LARGE_ROWS = 1024
_GMM_TILE_K = 2048
_GMM_TILE_N = 512


def route(
    x: jnp.ndarray,  # [N, D]
    router_w: jnp.ndarray,  # [D, E] float32
    bias: Optional[jnp.ndarray],  # [E] float32 or None
    *,
    top_k: int,
    router: str,
    norm_topk: bool = True,
    scale: float = 1.0,
    norm_eps: float = 1e-6,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(expert ids [N, k] int32, weights [N, k] float32)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), router_w)
    if router == "softmax":
        # the softmax over all E renormalised over the chosen k is the
        # softmax over those k
        top_vals, top_idx = jax.lax.top_k(logits, top_k)
        w = jax.nn.softmax(top_vals, axis=-1)
        return top_idx, w if scale == 1.0 else w * scale
    scores = jax.nn.sigmoid(logits)
    select = scores if bias is None else scores + bias[None, :]
    _, top_idx = jax.lax.top_k(select, top_k)
    w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return top_idx, w * scale


def _gmm_tiles(m: int, k: int, n: int,
               int8: bool = False) -> Tuple[int, int, int]:
    """(m, k, n) tiles; `int8` for weights of one byte an element."""
    def fit(dim, tile):
        # the largest multiple of 128 that divides dim and is <= tile,
        # else the whole dim (megablox masks an irregular k remainder,
        # but not an n remainder)
        for t in range(min(tile, dim) // 128 * 128, 0, -128):
            if dim % t == 0:
                return t
        return dim
    tm = _GMM_TILE_M_LARGE if m >= _GMM_LARGE_ROWS else \
        _GMM_TILE_M_SMALL_INT8 if int8 else _GMM_TILE_M_SMALL
    wider = 2 if int8 else 1
    return (min(tm, m), fit(k, _GMM_TILE_K * wider),
            fit(n, _GMM_TILE_N * wider))


def _ragged_dot(lhs, rhs, group_sizes, transpose_rhs=False, rhs_scale=None):
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    if rhs.dtype == jnp.int8:  # the dequantised stack: off a TPU only
        # graftlint: allow(num-barrier) weight dequant of constant
        # weights, as models/quantize.dequant: fusing it into the
        # product is the point, and no second leg materialises it
        rhs = rhs.astype(lhs.dtype) * rhs_scale.astype(lhs.dtype)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def _megablox(lhs, rhs, group_sizes, transpose_rhs=False, rhs_scale=None):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m = lhs.shape[0]
    # rhs stored [E, N, K] (transpose_rhs): tiles by the logical (m, k, n)
    k, n = rhs.shape[2:0:-1] if transpose_rhs else rhs.shape[1:]
    int8 = rhs.dtype == jnp.int8
    tm = _gmm_tiles(m, k, n, int8)[0]
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # The rows no expert owns form one more group, which has no weights:
    # gmm is told rhs holds the first E of E + 1 groups and skips it.
    rest = (m + pad) - jnp.sum(group_sizes)
    sizes = jnp.concatenate(
        [group_sizes.astype(jnp.int32), rest[None].astype(jnp.int32)])
    if int8:
        out = gmm_int8.gmm(
            lhs, rhs, rhs_scale, sizes,
            tiling=_gmm_tiles(m + pad, k, n, int8),
            transpose_rhs=transpose_rhs)
    else:
        out = gmm(
            lhs, rhs, sizes, preferred_element_type=lhs.dtype,
            tiling=_gmm_tiles(m + pad, k, n),
            group_offset=jnp.zeros((), jnp.int32),
            transpose_rhs=transpose_rhs,
        )
    return out[:m] if pad else out


def grouped_matmul(
    lhs: jnp.ndarray,  # [M, K] rows sorted by group
    rhs: jnp.ndarray,  # [E, K, N]; [E, N, K] with transpose_rhs
    group_sizes: jnp.ndarray,  # [E] int32, sum <= M
    transpose_rhs: bool = False,
    rhs_scale: Optional[jnp.ndarray] = None,  # [E, 1, N] float32: rhs int8
) -> jnp.ndarray:
    """out[r] = lhs[r] @ rhs[group of r]; rows past sum(group_sizes)
    belong to no group (callers mask them: their value is unspecified).
    int8 weights come with their per-output-channel scales
    (models/quantize.py) and are never a bf16 array in HBM on a TPU:
    ops/gmm_int8 widens a tile where it multiplies it."""
    product = _megablox if jax.default_backend() == "tpu" else _ragged_dot
    return product(lhs, rhs, group_sizes, transpose_rhs, rhs_scale)


def dispatch_experts(
    x: jnp.ndarray,  # [N, D]
    top_idx: jnp.ndarray,  # [N, k] int32
    top_w: jnp.ndarray,  # [N, k] float32
    w_gate: Optional[jnp.ndarray],  # [L * E, D, F]; None: no gate
    w_up: jnp.ndarray,  # [L * E, D, F]; without a gate [L * E, F, D]
    w_down: jnp.ndarray,  # [L * E, F, D]
    live: Optional[jnp.ndarray] = None,  # [N] bool; None = every row
    *,
    n_experts: int,
    layer: Optional[jnp.ndarray] = None,  # int32 scalar in [0, L)
    first: Optional[int] = None,
    scales: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """sum_j top_w[:, j] * SwiGLU_{top_idx[:, j]}(x) for live rows, zeros
    for the others. Also what routing did: `touched` (experts that hold
    at least one live row) and `assignments` (live rows x k).

    Without w_gate an expert is down(relu(up x) ** 2), and its up matrix
    is stored as the down matrix is, [F, D], and applied transposed: an
    expert width that is no multiple of the TPU's 128 lanes (1856) would
    otherwise be the stack's minor dimension, which the device stores
    D-minor and the compiled chunk relays out for the kernel, every
    expert's matrix on every chunk. With `first` the
    weights are a SHARE of the experts the router scored: n_experts of
    them, ids [first, first + n_experts). An assignment to an expert
    that is not held goes nowhere, exactly as a dead row's does: its
    term of the sum is left out (top_w is over all the chosen, held or
    not). `touched` then counts held experts, `assignments` stays what
    live rows chose and `held` says how many of those were held here.

    The weights may be those of L stacked layers with the layer and
    expert axes merged ([L * E, ...], a reshape that moves nothing) and
    `layer` saying which E of them this call uses: the groups of the
    other layers are empty, so the kernel never touches them. That is
    how a scan over layers hands the kernel its layer: a slice of the
    stack would be a copy of every expert's weights each step, because a
    custom call reads operands that exist in memory.

    int8 weights come with `scales`: {"w_gate", "w_up", "w_down"} ->
    float32 [L * E, 1, columns], what models/quantize.py stores beside
    them, merged the same way."""
    N, D = x.shape
    K = top_idx.shape[1]
    E = n_experts
    A = N * K
    with jax.named_scope("moe/dispatch"):
        eids = top_idx.reshape(A).astype(jnp.int32)
        if first is not None:
            eids = eids - first
            eids = jnp.where((eids >= 0) & (eids < E), eids, E)
        if live is not None:
            # expert id E = nowhere: sorts behind every real group
            eids = jnp.where(jnp.repeat(live, K), eids, E)
        order = jnp.argsort(eids, stable=True)  # [A] assignment ids
        group_sizes = jnp.sum(
            eids[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)  # [E]
        n_routed = jnp.sum(group_sizes)
        xs = jnp.take(x, order // K, axis=0)  # [A, D] sorted by expert
        sizes = group_sizes
        if w_up.shape[0] != E:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((w_up.shape[0],), jnp.int32), group_sizes,
                (layer.astype(jnp.int32) * E,))
    with jax.named_scope("moe/experts"):
        def product(rows, name, w, **kw):
            if scales is not None:
                kw["rhs_scale"] = scales[name]
            return grouped_matmul(rows, w, sizes, **kw)

        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(
                product(xs, "w_up", w_up, transpose_rhs=True)))
        else:
            hidden = jax.nn.silu(product(xs, "w_gate", w_gate)) \
                * product(xs, "w_up", w_up)
        ys = product(hidden, "w_down", w_down)  # [A, D]
    with jax.named_scope("moe/combine"):
        ys = jnp.where((jnp.arange(A) < n_routed)[:, None], ys, 0)
        # back to (token, j) order: position of assignment a in `order`
        inv = jnp.zeros((A,), jnp.int32).at[order].set(
            jnp.arange(A, dtype=jnp.int32), unique_indices=True)
        y = jnp.take(ys, inv, axis=0).reshape(N, K, D)
        out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), top_w)
    stats = {
        "touched": jnp.sum(group_sizes > 0, dtype=jnp.int32),
        "assignments": n_routed.astype(jnp.int32),
    }
    if first is not None:
        stats["held"] = stats["assignments"]
        stats["assignments"] = (
            jnp.asarray(A, jnp.int32) if live is None
            else K * jnp.sum(live, dtype=jnp.int32))
    return out.astype(x.dtype), stats
