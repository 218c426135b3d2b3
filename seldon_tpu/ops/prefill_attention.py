"""Prefill attention by blocks of keys: causal, or causal inside a window.

A prefill's einsum attention (models/transformer.gqa_attention) holds the
scores of every (query, key) pair at once, [G, H, S, S] float32: 4.3 GB
for ONE row at 64 heads and S = 4096, so admission groups at 2048 and
4096 positions never compiled. Here the keys go by in blocks with a
running softmax (ops/flash_attention.py's schedule) and nothing S x S
exists, under three things that kernel does not know:

 * a head count that is the CALL's (q carries it): layers of one stack
   differ in it. q, k and v arrive as the projections leave them,
   [G, S, H * Dh] / [G, S, Hkv * Dh], a token's heads side by side, and
   are read as they lie: one grid step takes the Gq = H / Hkv query
   heads of one KV head (lane-aligned slices of the row) against that
   head's block of keys, so no head is transposed out of a row on the
   way in or out and a block of K and V crosses HBM once for all Gq;
 * a `window`: query i sees key j when j <= i and i - j < window
   (0: every j <= i). Key blocks outside the band are neither copied nor
   multiplied: at S = 4096 and a window of 512 a layer's products are a
   quarter of the causal ones;
 * the rows' prompt lengths: blocks of queries past a row's own length
   (the right-padding of an admission group's bucket) are written as
   zeros and cost a grid step each, no product.

`attend` picks by what it observes, as the other ops of this package do:
on a TPU the Pallas kernel (`prefill_attention` in a device trace), for
heads of whole 128-lane tiles; elsewhere `blocked`, the same arithmetic
as a lax.scan over blocks of queries in XLA, each against the keys of its
band (or of all its past) only. Neither chooses interpret mode: tests run
the kernel through tests/pallas_interpret.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
KERNEL_NAME = "prefill_attention"
# Queries and keys a grid step takes. 256 x 256 scores a head keep a
# step's products (Gq x 4 x 256 x 256 x 128 FLOP: 1.4 us of the v5e's
# matrix unit at 8 heads) well above what a step costs beside them, and a
# band of 512 covers 3 key blocks a query block (768 keys read for the
# ~640 a query sees on average) where blocks of 512 would read 1024.
BLOCK = 256


def _span(qi, block_q: int, block_k: int, window: int):
    """First and last key block a block of queries sees."""
    first_key = jnp.maximum(qi * block_q - window + 1, 0) if window else 0
    return first_key // block_k, (qi * block_q + block_q - 1) // block_k


def _steps(S: int, block_q: int, block_k: int, window: int) -> int:
    """Key blocks the widest block of queries sees (the grid's last axis)."""
    return max(
        (q * block_q + block_q - 1) // block_k
        - (max(q * block_q - window + 1, 0) // block_k if window else 0) + 1
        for q in range(S // block_q))


def _kernel(plens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            heads: int, head_dim: int, block_q: int, block_k: int,
            window: int, scale: float):
    g, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    lo, hi = _span(qi, block_q, block_k, window)
    live = qi * block_q < plens_ref[g]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live & (lo + j <= hi))
    def _accumulate():
        k, v = k_ref[0], v_ref[0]  # [block_k, Dh]
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = (lo + j) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        seen = cols <= rows
        if window:
            seen &= rows - cols < window
        for h in range(heads):
            q = q_ref[0, :, h * head_dim:(h + 1) * head_dim]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row that sees nothing of this block keeps m = NEG_INF:
            # exp(s - m) would be 1 there
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        # queries past the row's own length: zeros, not whatever the
        # buffer held (a key of theirs is masked by exp() = 0, and
        # 0 x NaN would still be NaN)
        for h in range(heads):
            o_ref[0, :, h * head_dim:(h + 1) * head_dim] = (
                acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            ).astype(o_ref.dtype)


def fits(S: int, head_dim: int) -> bool:
    """The kernel reads heads of whole 128-lane tiles in a sequence that
    whole blocks cover."""
    b = min(BLOCK, S)
    return head_dim % LANES == 0 and S % b == 0 and b % 8 == 0


def kernel(q, k, v, plens, *, head_dim: int, window: int = 0,
           block: int = BLOCK):
    """q [G, S, H * Dh], k, v [G, S, Hkv * Dh], plens [G] int32 ->
    [G, S, H * Dh]: the Pallas kernel (a TPU, or interpreted in tests)."""
    G, S, HD = q.shape
    Hkv = k.shape[2] // head_dim
    heads = HD // head_dim // Hkv  # query heads a KV head
    bq = bk = min(block, S)
    wide = heads * head_dim

    def q_map(g, kv, i, j, plens):
        return g, i, kv

    def k_map(g, kv, i, j, plens):
        lo, hi = _span(i, bq, bk, window)
        return g, jnp.minimum(lo + j, hi), kv

    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, head_dim=head_dim,
                          block_q=bq, block_k=bk, window=window,
                          scale=head_dim ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, Hkv, S // bq, _steps(S, bq, bk, window)),
            in_specs=[pl.BlockSpec((1, bq, wide), q_map),
                      pl.BlockSpec((1, bk, head_dim), k_map),
                      pl.BlockSpec((1, bk, head_dim), k_map)],
            out_specs=pl.BlockSpec((1, bq, wide), q_map),
            scratch_shapes=[pltpu.VMEM((heads, bq, 1), jnp.float32),
                            pltpu.VMEM((heads, bq, 1), jnp.float32),
                            pltpu.VMEM((heads, bq, head_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        name=KERNEL_NAME,
    )(plens.astype(jnp.int32), q, k, v)


def blocked(q, k, v, *, head_dim: int, window: int = 0, block: int = BLOCK):
    """The same attention in XLA: a scan over blocks of queries, each
    against the keys it can see: `window + block` of them where there is
    a window, else all S, masked. Scores are [G, H, block, keys] a step."""
    G, S, HD = q.shape
    Hkv = k.shape[2] // head_dim
    Gq = HD // head_dim // Hkv
    bq = min(block, S)
    if S % bq:
        bq = S
    span = min(S, bq + window) if window else S
    qb = jnp.moveaxis(q.reshape(G, S // bq, bq, Hkv, Gq, head_dim), 1, 0)
    k4, v4 = (t.reshape(G, S, Hkv, head_dim) for t in (k, v))

    def one(_, xs):
        qi, qblk = xs
        start = jnp.clip(qi * bq + bq - span, 0, S - span)
        kb = jax.lax.dynamic_slice_in_dim(k4, start, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v4, start, span, axis=1)
        s = jnp.einsum("bskgd,btkd->bkgst", qblk, kb,
                       preferred_element_type=jnp.float32) * head_dim ** -0.5
        rows = (qi * bq + jnp.arange(bq))[:, None]
        cols = (start + jnp.arange(span))[None, :]
        seen = cols <= rows
        if window:
            seen &= rows - cols < window
        w = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
        return None, jnp.einsum("bkgst,btkd->bskgd", w.astype(q.dtype), vb)

    _, out = jax.lax.scan(one, None, (jnp.arange(S // bq), qb))
    return jnp.moveaxis(out, 0, 1).reshape(G, S, HD)


def applies(S: int, head_dim: int) -> bool:
    """The prefill takes the kernel: on a TPU, at a shape it reads."""
    return jax.default_backend() == "tpu" and fits(S, head_dim)


def attend(q, k, v, plens, *, head_dim: int, window: int = 0):
    """Causal (or banded causal) attention of whole sequences from
    position 0. q [G, S, H, Dh], k, v [G, S, Hkv, Dh]; plens [G] or None
    (every row whole). Returns [G, S, H * Dh]."""
    G, S, H, Dh = q.shape
    flat = lambda t: t.reshape(G, S, -1)
    if applies(S, head_dim):
        if plens is None:
            plens = jnp.full((G,), S, jnp.int32)
        return kernel(flat(q), flat(k), flat(v), plens, head_dim=head_dim,
                      window=window)
    return blocked(flat(q), flat(k), flat(v), head_dim=head_dim,
                   window=window)
