"""One decode step of a Mamba-2 layer's state, for every slot, where the
state lies.

    S[b, h] <- keep[b, h] * S[b, h] + dtx[b, h] (x) B[b, g(h)]
    y[b, h]  = S[b, h] . C[b, g(h)]

The state of ALL the stack's Mamba-2 layers is one array, [Lm, B, H, P, N]
float32 (models/transformer.cache_spec), and a step reads and writes one
layer of it for every slot: 134 MB each way at 64 slots x 64 heads x 64 x
128, where everything else the layer touches is a few MB. Written in
jax.numpy the TPU compiler makes two fusions of it, the update in place
and, reading the old state a second time, the reduction to y: three
passes over the layer's state where two are needed. The kernel here
holds one slot's [H, P, N] block in VMEM, updates it, reduces it while it
is held and writes it back to where it came from (the whole state is
aliased to the output; `layer` picks the block by scalar prefetch, so no
slice of the state is ever an operand). N is the lane axis, so B and C
and the decay are rows, broadcast down the sublanes; dt * x has to be a
column per head, so it comes in transposed, [B, P, H], and y goes out
that way.

Like the other kernels of seldon_tpu/ops it never chooses interpret mode
itself: off a TPU `update` is the same arithmetic in jax.numpy (`_xla`),
and tests run the kernel through tests/pallas_interpret.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, state_ref, keep_ref, dtx_ref, b_ref, c_ref,
            out_ref, y_ref, *, heads: int, per_group: int):
    del layer_ref  # read by the index maps
    for h in range(heads):  # static: a column of dtx is a static lane slice
        g = h // per_group
        new = keep_ref[0, h:h + 1, :] * state_ref[0, 0, h] \
            + dtx_ref[0, :, h:h + 1] * b_ref[0, g:g + 1, :]  # [P, N]
        out_ref[0, 0, h] = new
        y_ref[0, :, h:h + 1] = jnp.sum(
            new * c_ref[0, g:g + 1, :], axis=-1, keepdims=True)


def _pallas(state, layer, keep, dtx, b, c):
    Lm, B, H, P, N = state.shape
    G = b.shape[1]
    f32 = jnp.float32
    block = pl.BlockSpec((1, 1, H, P, N), lambda i, l: (l[0], i, 0, 0, 0))

    def per_slot(*dims):
        return pl.BlockSpec((1,) + dims, lambda i, l: (i, 0, 0))

    new, y_t = pl.pallas_call(
        functools.partial(_kernel, heads=H, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[block, per_slot(H, N), per_slot(P, H),
                      per_slot(G, N), per_slot(G, N)],
            out_specs=[block, per_slot(P, H)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((B, P, H), f32)],
        input_output_aliases={1: 0},  # the state, past the prefetched layer
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # one slot's block in and out, each double-buffered
            vmem_limit_bytes=6 * H * P * N * 4),
        cost_estimate=pl.CostEstimate(
            flops=5 * B * H * P * N, transcendentals=0,
            bytes_accessed=2 * B * H * P * N * 4),
        name="ssm_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state,
      jnp.broadcast_to(keep[..., None], (B, H, N)),
      jnp.swapaxes(dtx, 1, 2), b.astype(f32), c.astype(f32))
    return jnp.swapaxes(y_t, 1, 2), new


def _xla(state, layer, keep, dtx, b, c):
    per_group = state.shape[2] // b.shape[1]
    bh = jnp.repeat(b.astype(jnp.float32), per_group, axis=1)  # [B, H, N]
    ch = jnp.repeat(c.astype(jnp.float32), per_group, axis=1)
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new = keep[..., None, None] * old + dtx[..., None] * bh[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", new, ch), \
        jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)


def update(
    state: jnp.ndarray,  # [Lm, B, H, P, N] float32: every layer's state
    layer: jnp.ndarray,  # int32 scalar in [0, Lm): the layer stepped
    keep: jnp.ndarray,  # [B, H] float32: exp(dt * A)
    dtx: jnp.ndarray,  # [B, H, P] float32: dt * x
    b: jnp.ndarray,  # [B, G, N]; head h reads group h // (H / G)
    c: jnp.ndarray,  # [B, G, N]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y [B, H, P] float32, the state with layer `layer` stepped)."""
    if jax.default_backend() == "tpu":
        return _pallas(state, layer, keep, dtx, b, c)
    return _xla(state, layer, keep, dtx, b, c)
