"""graftkern — block-sparse ragged paged-attention partials.

Why: the ragged wave (models/ragged_attention.py) reads its resident
context through ``paged_prefix_view`` / ``paged_gather_kv`` at the FULL
table width — every row pays ``max_seq_len`` of gather + score traffic
and a ``-1e30`` mask throws the tail away. Bit-neutral, but the wave's
cost scales with capacity instead of occupancy (the documented 0.63x
BENCH_RAGGED loss regime). This module walks the per-slot block table
instead and touches only LIVE KV blocks — ``ceil(context / kv_block)``
blocks per row — with flash-style online softmax across blocks and the
int8 scales (rank-4 twins, models/transformer._quantize_kv) fused into
the block loop, never widening the 1-byte HBM read.

The op computes attention PARTIALS, not outputs: ``(m, l, acc)`` —
running max, exp-sum and unnormalized value accumulator of every query
row against the pool positions ``t < bound[b, s]``. Callers fold their
own fresh columns (prefill's causal suffix, decode's exact bf16 column,
verify's suffix + diagonal) into the partials with one more max/exp
combine, so one kernel serves all three wave legs. Layouts follow the
engine's attention einsums: q ``[B, Sq, Hkv, G, Dh]`` grouped, partials
``[B, Hkv, G, Sq, ...]`` f32.

Three legs, per the ops/ pattern (flash_attention.py):

 * :func:`partials_reference` — full-width gather + closed-form
   softmax partials. The masked engine arithmetic rearranged to the
   partials contract; the parity oracle for the walkers.
 * :func:`partials_sparse` — pure-jnp ``lax.fori_loop`` over block
   columns with a TRACED trip count ``ceil(max(bound) / block)``: the
   loop walks only as many columns as the wave's longest live row, so
   CPU cost scales with occupancy too (the leg tier-1 exercises and
   BENCH_RAGGED's ``kernel=sparse`` axis measures). Static shapes per
   iteration — the trip count is a traced scalar, never a shape — so
   the ragged compile lattice stays at ≤ 2 variants with zero live
   retraces.
 * :func:`partials_pallas` — the Pallas/Mosaic kernel: grid
   ``(B * Hkv, num_blocks)``, the block table rides as a
   scalar-prefetch operand and the K/V BlockSpec index maps read it
   (``pltpu.PrefetchScalarGridSpec``), so the DMA engine fetches
   exactly the addressed pool block per grid step — dead columns
   re-address the trash block (table tails are 0) and their compute is
   ``pl.when``-skipped. Always compiled; the CPU parity tests run it
   interpreted (tests/pallas_interpret.py).

Numerics: the partials legs share one f32 accumulation formula
(scores bf16 x bf16 -> f32, scales factored OUT of the einsums exactly
like ``gqa_attention_decode``, value dot in f32), so they agree with
each other to f32 roundoff — but they are MORE accurate than the
masked kernels, which round softmax weights to the activation dtype
before the value dot, and that ~1e-3 drift flips near-tied greedy
argmaxes on flat-logit models. The ``sparse`` wave leg therefore uses
the masked-MATCHED two-pass walk (:func:`sparse_max_sum` +
:func:`sparse_weighted_value`, "Masked-matched" section below): the
masked kernels' exact term set, differing only in f32 summation order,
so greedy outputs stay token-identical to ``masked`` by construction
(pinned by tests/test_ragged_kernel.py) and raw logits agree within
:data:`RAGGED_LOGITS_ATOL`. The pallas leg keeps the fused one-pass
partials (atol contract only); ``masked`` stays the bit-exact leg.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Documented |logits_pallas - logits_masked| bound (f32 logits, tiny/CI
# geometries). The sparse leg needs no tolerance — its two-pass walk is
# bit-exact against the masked kernels — so this bounds only the pallas
# leg's fused one-pass f32 partials, whose online-softmax reassociation
# and f32-vs-bf16 value mix sit at ~3e-3 on the CI fixtures. Pinned by
# tests/test_ragged_kernel.py::test_prefill_logits_within_atol.
RAGGED_LOGITS_ATOL = 1e-2

MODES = ("reference", "sparse", "pallas")

Partials = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]


def _grouped(q: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """[B, Sq, H, Dh] -> [B, Sq, Hkv, G, Dh] (no copy)."""
    B, Sq, H, Dh = q.shape
    return q.reshape(B, Sq, n_kv_heads, H // n_kv_heads, Dh)


def _block_scores(qr, kb, k_scale_b, mask):
    """One block column's masked scores [B, Hkv, G, Sq, block] f32:
    int8 keys are exact in bf16 and the rank-4 scale twin multiplies
    the f32 scores AFTER the einsum (gqa_attention_decode's factoring
    — the HBM read stays 1 byte/element)."""
    Dh = qr.shape[-1]
    s = jnp.einsum(
        "bskgd,bktd->bkgst", qr, kb.astype(qr.dtype),
        preferred_element_type=jnp.float32,
    ) / (Dh**0.5)
    if k_scale_b is not None:
        s = s * k_scale_b[:, :, None, None, :]
    return jnp.where(mask[:, None, None, :, :], s, NEG_INF)


def _block_accumulate(carry: Partials, s, p_mask, vb, v_scale_b) -> Partials:
    """Online-softmax fold of one block column into (m, l, acc). The
    explicit ``where`` on p guards the all-masked prefix (m still at
    NEG_INF would make exp(s - m) == 1 on dead lanes)."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(p_mask[:, None, None, :, :], jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    pw = p if v_scale_b is None else p * v_scale_b[:, :, None, None, :]
    acc = acc * alpha + jnp.einsum(
        "bkgst,bktd->bkgsd", pw, vb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, l, acc


def _init_partials(B, Hkv, G, Sq, Dh) -> Partials:
    return (
        jnp.full((B, Hkv, G, Sq, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, Hkv, G, Sq, 1), jnp.float32),
        jnp.zeros((B, Hkv, G, Sq, Dh), jnp.float32),
    )


def combine_fresh(partials: Partials, s_fresh: jnp.ndarray,
                  v_fresh: jnp.ndarray,
                  p_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Fold fresh score columns into pool partials and normalize.

    partials: (m, l, acc) from a walker below; s_fresh
    [B, Hkv, G, Sq, F] f32 scores of F fresh columns (already masked to
    NEG_INF where invisible; at least one column per row must be live —
    every wave leg guarantees its diagonal); v_fresh [B, Hkv, F, Dh]
    values in any dtype exact under f32. p_mask (same shape as s_fresh)
    re-zeroes masked fresh lanes explicitly when a row can have ALL
    fresh columns dead (verify row 0's empty suffix) — exp(NEG_INF - m)
    underflows to 0 for finite m, so it is only load-bearing when m
    itself sits at NEG_INF. Returns [B, Sq, Hkv*G*Dh] f32 un-cast."""
    m, l, acc = partials
    m_t = jnp.maximum(m, jnp.max(s_fresh, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_t)
    p_f = jnp.exp(s_fresh - m_t)
    if p_mask is not None:
        p_f = jnp.where(p_mask, p_f, 0.0)
    l_t = l * alpha + jnp.sum(p_f, axis=-1, keepdims=True)
    out = acc * alpha + jnp.einsum(
        "bkgsf,bkfd->bkgsd", p_f, v_fresh.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = out / jnp.maximum(l_t, 1e-30)
    B, Hkv, G, Sq, Dh = out.shape
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hkv * G * Dh)


# ---------------------------------------------------------------------------
# Reference (full-width gather) — the parity oracle
# ---------------------------------------------------------------------------


def partials_reference(q: jnp.ndarray, pool_layer: Dict[str, jnp.ndarray],
                       table: jnp.ndarray, bound: jnp.ndarray) -> Partials:
    """Full-width gather + closed-form partials — the masked engine
    gather (paged_gather_kv) with the softmax left unnormalized.

    q [B, Sq, Hkv, G, Dh]; pool_layer {"k","v"[,"k_scale","v_scale"]}
    [NB, Hkv, block, (Dh)]; table [B, nbs] int32; bound [B, Sq] int32 —
    query row s of slot b attends pool positions t < bound[b, s]."""
    B, Sq = bound.shape
    nbs = table.shape[1]
    block = pool_layer["k"].shape[2]

    def gather(key):
        g = pool_layer[key][table]          # [B, nbs, Hkv, block, (Dh)]
        g = jnp.moveaxis(g, 1, 2)           # [B, Hkv, nbs, block, (Dh)]
        return g.reshape(g.shape[0], g.shape[1],
                         g.shape[2] * g.shape[3], *g.shape[4:])

    ck, cv = gather("k"), gather("v")
    ks = gather("k_scale") if "k_scale" in pool_layer else None
    vs = gather("v_scale") if "v_scale" in pool_layer else None
    mask = jnp.arange(nbs * block)[None, None, :] < bound[:, :, None]
    s = _block_scores(q, ck, ks, mask)
    init = _init_partials(B, q.shape[2], q.shape[3], Sq, q.shape[4])
    return _block_accumulate(init, s, mask, cv, vs)


# ---------------------------------------------------------------------------
# Block-sparse jnp walker — the CPU leg
# ---------------------------------------------------------------------------


def partials_sparse(q: jnp.ndarray, pool_layer: Dict[str, jnp.ndarray],
                    table: jnp.ndarray, bound: jnp.ndarray) -> Partials:
    """Walk only live block columns: ``lax.fori_loop`` with the TRACED
    trip count ``ceil(max(bound) / block)`` — per-iteration shapes are
    static ([B] one table column, [B, Hkv, block, (Dh)] one gathered
    block), so the wave's compile key never sees the mix; XLA lowers
    the dynamic trip count to a while loop inside the one variant.
    Rows shorter than the longest one mask their dead tail lanes; rows
    past their own table prefix gather the trash block (table tails
    are 0) and mask it the same way."""
    B, Sq = bound.shape
    nbs = table.shape[1]
    block = pool_layer["k"].shape[2]
    quantized = "k_scale" in pool_layer
    offs = jnp.arange(block)

    def body(j, carry):
        bids = jax.lax.dynamic_index_in_dim(table, j, axis=1,
                                            keepdims=False)  # [B]
        kb = pool_layer["k"][bids]          # [B, Hkv, block, Dh]
        vb = pool_layer["v"][bids]
        ks = pool_layer["k_scale"][bids] if quantized else None
        vs = pool_layer["v_scale"][bids] if quantized else None
        t_abs = j * block + offs
        mask = t_abs[None, None, :] < bound[:, :, None]  # [B, Sq, block]
        s = _block_scores(q, kb, ks, mask)
        return _block_accumulate(carry, s, mask, vb, vs)

    n_live = jnp.clip(
        (jnp.max(bound) + block - 1) // block, 0, nbs
    ).astype(jnp.int32)
    init = _init_partials(B, q.shape[2], q.shape[3], Sq, q.shape[4])
    return jax.lax.fori_loop(0, n_live, body, init)


# ---------------------------------------------------------------------------
# Masked-matched two-pass walk — the greedy-parity leg
# ---------------------------------------------------------------------------
#
# The one-pass partials above keep the softmax weights in f32 end to
# end — strictly MORE accurate than the masked engine kernels, which
# round the normalized weights to the activation dtype before the value
# einsum (gqa_attention's ``w.astype(q.dtype)``, gqa_attention_decode's
# ``wc.astype(qr.dtype)``). More accurate is still DIFFERENT: on
# flat-logit models a ~1e-3 drift flips near-tied greedy argmaxes. The
# two-pass walk below reproduces the masked term set exactly — every
# weight is normalized in f32, scaled, then rounded to the query dtype
# before multiplying the same-dtype value block, accumulated in f32
# across blocks with ONE final cast — so sparse-vs-masked differences
# reduce to f32 summation order (~1 ulp), and greedy token identity
# becomes an engineering property instead of a margin bet. The sparse
# wave legs use this pair; ``partials_sparse`` remains as the jnp twin
# of the pallas leg's one-pass arithmetic and for the oracle tests.
#
# ``dequant`` selects which masked kernel is being matched: False for
# the factored-scale decode/verify path (scores x k_scale in f32 after
# the einsum, weights x v_scale in f32 before the cast); True for the
# prefill path, which dequantizes int8 prefix KV into the activation
# dtype FIRST (_run_blocks_prefill_prefix's ``pk * k_scale``) and runs
# unscaled attention over it.


def _sparse_block(pool_layer, table, j, dtype, dequant):
    """Gather block column j: (kb, vb, k_scale, v_scale) with the
    dequant-vs-factored convention applied.

    The optimization_barrier pins the DEQUANTIZED block to its
    materialized (rounded) activation-dtype value — the same hazard
    class as models/transformer._quantize_kv: bf16 math inside an XLA
    fusion runs in f32 and only rounds at materialization boundaries.
    The masked twin (_run_blocks_prefill_prefix) rounds its dequant at
    the prefix‖fresh concat boundary; without the barrier the walker's
    dequant fuses straight into the score/value dots unrounded and the
    two legs' logits drift apart (greedy flips at ~2e-3 under int8)."""
    bids = jax.lax.dynamic_index_in_dim(table, j, axis=1, keepdims=False)
    kb = pool_layer["k"][bids]
    vb = pool_layer["v"][bids]
    ks = pool_layer["k_scale"][bids] if "k_scale" in pool_layer else None
    vs = pool_layer["v_scale"][bids] if "v_scale" in pool_layer else None
    if dequant and ks is not None:
        kb = jax.lax.optimization_barrier(
            kb.astype(dtype) * ks[..., None].astype(dtype))
        vb = jax.lax.optimization_barrier(
            vb.astype(dtype) * vs[..., None].astype(dtype))
        ks = vs = None
    return kb, vb, ks, vs


def sparse_max_sum(q: jnp.ndarray, pool_layer: Dict[str, jnp.ndarray],
                   table: jnp.ndarray, bound: jnp.ndarray,
                   dequant: bool = False) -> Tuple[jnp.ndarray,
                                                   jnp.ndarray]:
    """Pass 1 of the matched walk: running max ``m`` and exp-sum ``l``
    (relative to m) of the live pool scores — no value traffic. Shapes
    as in _init_partials; dead rows stay (NEG_INF, 0)."""
    B, Sq = bound.shape
    nbs = table.shape[1]
    block = pool_layer["k"].shape[2]
    offs = jnp.arange(block)

    def body(j, carry):
        m, l = carry
        kb, _, ks, _ = _sparse_block(pool_layer, table, j, q.dtype,
                                     dequant)
        t_abs = j * block + offs
        mask = t_abs[None, None, :] < bound[:, :, None]
        s = _block_scores(q, kb, ks, mask)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[:, None, None, :, :], jnp.exp(s - m_new), 0.0)
        l = l * jnp.exp(m - m_new) + jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l

    n_live = jnp.clip(
        (jnp.max(bound) + block - 1) // block, 0, nbs
    ).astype(jnp.int32)
    init = (
        jnp.full((B, q.shape[2], q.shape[3], Sq, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, q.shape[2], q.shape[3], Sq, 1), jnp.float32),
    )
    return jax.lax.fori_loop(0, n_live, body, init)


def sparse_weighted_value(q: jnp.ndarray,
                          pool_layer: Dict[str, jnp.ndarray],
                          table: jnp.ndarray, bound: jnp.ndarray,
                          m_t: jnp.ndarray,
                          l_t: jnp.ndarray,
                          dequant: bool = False) -> jnp.ndarray:
    """Pass 2 of the matched walk: ``sum_t round(exp(s_t - m_t) / l_t
    [* v_scale]) . v_t`` over live pool columns, f32 accumulation
    across blocks. ``m_t``/``l_t`` are the GLOBAL max / exp-sum after
    the caller folded its fresh columns in, so each weight is the very
    number the masked kernel rounds to the query dtype. Returns
    [B, Hkv, G, Sq, Dh] f32 — cast once, by the caller, next to the
    masked leg's single einsum output cast."""
    B, Sq = bound.shape
    nbs = table.shape[1]
    block = pool_layer["k"].shape[2]
    offs = jnp.arange(block)
    l_safe = jnp.maximum(l_t, 1e-30)

    def body(j, acc):
        kb, vb, ks, vs = _sparse_block(pool_layer, table, j, q.dtype,
                                       dequant)
        t_abs = j * block + offs
        mask = t_abs[None, None, :] < bound[:, :, None]
        s = _block_scores(q, kb, ks, mask)
        # Mask BEFORE dividing: a fully-dead row has m_t finite only
        # via its fresh columns, but dead lanes at s = NEG_INF already
        # underflow; the where guards the bound = 0, m_t = NEG_INF case
        # where exp(s - m_t) would be exp(0) on every lane.
        w = jnp.where(mask[:, None, None, :, :],
                      jnp.exp(s - m_t), 0.0) / l_safe
        if vs is not None:
            w = w * vs[:, :, None, None, :]
        return acc + jnp.einsum(
            "bkgst,bktd->bkgsd", w.astype(q.dtype), vb.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )

    n_live = jnp.clip(
        (jnp.max(bound) + block - 1) // block, 0, nbs
    ).astype(jnp.int32)
    init = jnp.zeros((B, q.shape[2], q.shape[3], Sq, q.shape[4]),
                     jnp.float32)
    return jax.lax.fori_loop(0, n_live, body, init)


# ---------------------------------------------------------------------------
# Pallas kernel — scalar-prefetched block tables, one DMA per live block
# ---------------------------------------------------------------------------


def _rpa_kernel(table_ref, maxb_ref, bound_ref, q_ref, k_ref, v_ref, *rest,
                quantized, block, n_kv_heads, scale):
    """Grid (B * Hkv, nbs). Scalar-prefetch arg 0 is the block table —
    consumed by the K/V index maps, unused here; arg 1 is each slot's
    largest bound, so the dead-column test is pure scalar arithmetic
    (a vector reduce cannot gate ``pl.when``). Scratch carries the
    (m, l, acc) accumulators across the block-column axis; dead columns
    (past every query row's bound) skip their FLOPs under pl.when while
    their index maps re-address the trash block, so neither DMA nor MXU
    pays for the padded tail."""
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block < maxb_ref[pl.program_id(0) // n_kv_heads])
    def _accumulate():
        q = q_ref[0]                        # [R, Dh]
        k = k_ref[0, 0]                     # [block, Dh] int8/bf16
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                           # [R, block]
        if quantized:
            s = s * ks_ref[0, 0].astype(jnp.float32)    # [1, block]
        R = s.shape[0]
        cols = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (R, block), 1
        )
        mask = cols < bound_ref[0]          # [R, 1] broadcasts over lanes
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        if quantized:
            pw = p * vs_ref[0, 0].astype(jnp.float32)
        else:
            pw = p
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pw, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]
        acc_ref[0] = acc_scr[:]


def partials_pallas(q: jnp.ndarray, pool_layer: Dict[str, jnp.ndarray],
                    table: jnp.ndarray, bound: jnp.ndarray) -> Partials:
    """Pallas/Mosaic walker: same (m, l, acc) contract as the jnp legs.

    The block table rides as a scalar-prefetch operand so the K/V
    BlockSpec index maps address pool blocks DIRECTLY —
    ``(table[b, j], h, 0, 0)`` — one block-sized DMA per grid step,
    never a full-width gather. Always compiled: off a TPU only a test
    runs it, interpreted (tests/pallas_interpret.py).

    Every blocked operand keeps its last two dims equal to the array's
    own (the (8, 128) tiling rule's escape): bounds ride as a
    ``[B, R, 1]`` column and the int8 scales as ``[NB, Hkv, 1, block]``
    rows, both free reshapes of what the engine holds."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hkv, G, Dh = q.shape
    nbs = table.shape[1]
    NB, _, block, _ = pool_layer["k"].shape
    quantized = "k_scale" in pool_layer
    R = G * Sq
    # Fold (G, Sq) onto one row axis; bound broadcasts per group.
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * Hkv, R, Dh)
    bound = bound.astype(jnp.int32)
    bound_r = jnp.broadcast_to(
        bound[:, None, :], (B, G, Sq)
    ).reshape(B, R, 1)

    def kv_index(bh, j, tref, mref):
        return (tref[bh // Hkv, j], bh % Hkv, 0, 0)

    def row_index(bh, j, tref, mref):
        return (bh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, R, 1), lambda bh, j, tref, mref: (bh // Hkv, 0, 0)),
        pl.BlockSpec((1, R, Dh), row_index),
        pl.BlockSpec((1, 1, block, Dh), kv_index),
        pl.BlockSpec((1, 1, block, Dh), kv_index),
    ]
    args = [bound_r, qf, pool_layer["k"], pool_layer["v"]]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, 1, block), kv_index),
            pl.BlockSpec((1, 1, 1, block), kv_index),
        ]
        args += [pool_layer["k_scale"].reshape(NB, Hkv, 1, block),
                 pool_layer["v_scale"].reshape(NB, Hkv, 1, block)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, nbs),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, R, 1), row_index),
            pl.BlockSpec((1, R, 1), row_index),
            pl.BlockSpec((1, R, Dh), row_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _rpa_kernel,
        quantized=quantized,
        block=block,
        n_kv_heads=Hkv,
        scale=Dh**-0.5,
    )
    m, l, acc = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, R, Dh), jnp.float32),
        ],
        grid_spec=grid_spec,
        name="ragged_paged_partials",
    )(table.astype(jnp.int32), jnp.max(bound, axis=1), *args)
    unfold = lambda t: t.reshape(B, Hkv, G, Sq, t.shape[-1])
    return unfold(m), unfold(l), unfold(acc)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def ragged_paged_partials(
    q: jnp.ndarray,          # [B, Sq, Hkv, G, Dh] grouped queries
    pool_layer: Dict[str, jnp.ndarray],  # one layer's paged pool slice
    table: jnp.ndarray,      # [B, nbs] int32 block tables
    bound: jnp.ndarray,      # [B, Sq] int32 — attend pool t < bound
    mode: str = "sparse",
) -> Partials:
    """The block-sparse partials (m, l, acc) by the leg the caller
    named: "sparse" — jnp fori_loop walker; "pallas" — the Mosaic
    kernel; "reference" — full-width oracle. A leg that cannot run
    where it is asked to raises: no leg stands in for another."""
    if mode == "reference":
        return partials_reference(q, pool_layer, table, bound)
    if mode == "pallas":
        return partials_pallas(q, pool_layer, table, bound)
    if mode != "sparse":
        raise ValueError(f"unknown ragged kernel mode {mode!r}")
    return partials_sparse(q, pool_layer, table, bound)
