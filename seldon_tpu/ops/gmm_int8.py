"""Grouped matmul over int8 weights: out[r] = (lhs[r] @ w_q[g(r)]) * scale[g(r)].

jax's megablox `gmm` (the kernel ops/moe_dispatch.grouped_matmul calls
for bf16 and float32 expert stacks) refuses int8. This is its forward
kernel for an int8 right-hand side with per-output-channel scales, the
form models/quantize.py stores: a weight tile crosses HBM as int8, one
byte an element, is widened to the rows' dtype in VMEM, the products
add up in float32, and the group's scale row multiplies the sum once,
at the last k step, before the rows of the group are stored. That is
x @ (w_q * scale) with the scale applied after the sum; no activation
is quantised.

The walk is megablox's (its make_group_metadata is imported): the rows
are sorted by group, a grid step is one (m-tile, group) pair that share
rows, groups without rows are never visited, so their weights are never
read, and rows of a tile that belong to another group keep what they
hold (the store mask). Two differences. The rows' tile is the whole of
k, [tm, k], and the kernel slices it by k step: a tile of rows is
fetched once per visit, not once per k step (k = 14336 takes four).
And k and n tiles
divide k and n (moe_dispatch._gmm_tiles picks them so): no remainder is
masked.

Like the other kernels of seldon_tpu/ops it never chooses interpret mode
itself; tests run it through tests/pallas_interpret.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import (
    _get_store_mask,
    make_group_metadata,
)

# What the call is named in a device trace ("%gmm_int8.3 = ...").
NAME = "gmm_int8"


def _kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref, rhs_ref,
            scale_ref, out_ref, acc_ref, *, tiles: Tuple[int, int, int],
            tiles_k: int, transpose_rhs: bool):
    tm, tk, tn = tiles
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = lhs_ref[:, pl.ds(pl.multiple_of(k_i * tk, tk), tk)]  # [tm, tk]
    w = rhs_ref[...].astype(x.dtype)  # int8 -> the rows' dtype, in VMEM
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1 if transpose_rhs else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        mask = _get_store_mask(
            grid_id=grid_id,
            group_metadata=(offsets_ref, group_ids_ref, m_tile_ids_ref),
            tm=tm, tn=tn)
        out_ref[...] = jnp.where(
            mask, acc_ref[...] * scale_ref[...],
            out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "tm", "groups"))
def _walk(group_sizes, *, m: int, tm: int, groups: int):
    """megablox's group metadata and the number of (m-tile, group) visits.
    Jitted so that the three products of one expert block, which walk
    the same rows, trace and lower it once: its few dozen small ops are
    a third of what a program with this kernel costs to trace, and a
    server's warm-up traces a dozen such programs."""
    return make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=groups,
        visit_empty_groups=False)


@functools.partial(jax.jit, static_argnames=("tiling", "transpose_rhs"))
def gmm(
    lhs: jnp.ndarray,  # [m, k] bf16 or float32, rows sorted by group
    rhs: jnp.ndarray,  # int8 [G, k, n]; [G, n, k] with transpose_rhs
    rhs_scale: jnp.ndarray,  # float32 [G, 1, n]
    group_sizes: jnp.ndarray,  # int32 [>= G], summing to m
    tiling: Tuple[int, int, int],
    transpose_rhs: bool = False,
) -> jnp.ndarray:
    """[m, n] in lhs's dtype. group_sizes may name more groups than rhs
    holds (the rows of those, and of no group, are left unwritten: their
    value is unspecified). m is whole tiles; the k and n tiles divide k
    and n. Jitted for the same reason as _walk: a gated block's gate and
    up products have one shape, so the kernel is traced and lowered for
    two calls of a program's three (traced anew at each call, the kernel
    and its walk added 10 s to the first dispatches of mixtral.chat's
    13 programs, a tenth of a warm set-up: PERF.md section 6, PR 39)."""
    m, k = lhs.shape
    G = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiles {tiling} do not divide (m, k, n) = {(m, k, n)}")
    if rhs.dtype != jnp.int8 or rhs_scale.shape != (G, 1, n):
        raise ValueError(
            f"expected int8 weights with scales [{G}, 1, {n}], got "
            f"{rhs.dtype} and {rhs_scale.shape}")
    tiles_k, tiles_n = k // tk, n // tn
    metadata, num_active_tiles = _walk(group_sizes, m=m, tm=tm, groups=G)

    def rows(n_i, g, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[g], 0

    def weights(n_i, g, k_i, offsets, group_ids, m_tile_ids):
        return (group_ids[g], n_i, k_i) if transpose_rhs \
            else (group_ids[g], k_i, n_i)

    def scales(n_i, g, k_i, offsets, group_ids, m_tile_ids):
        return group_ids[g], 0, n_i

    def out(n_i, g, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[g], n_i

    item = lhs.dtype.itemsize
    # rows, weights and output double-buffered; the widened weight tile
    # and the float32 sum once; room for the compiler's own temporaries
    vmem = (2 * tm * k * item + 2 * tk * tn + tk * tn * item
            + tm * tn * (4 + 4 + 2 * item) + (8 << 20))
    return pl.pallas_call(
        functools.partial(_kernel, tiles=tiling, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, k), rows),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), weights),
                pl.BlockSpec((None, 1, tn), scales),
            ],
            out_specs=pl.BlockSpec((tm, tn), out),
            grid=(tiles_n, num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            # an upper bound, as megablox's: every visit reads a whole
            # [k, n] of weights, every n tile the rows
            bytes_accessed=(m * k * item * tiles_n
                            + k * n * metadata[1].size + m * n * item)),
        name=NAME,
    )(*metadata, lhs, rhs, rhs_scale)
