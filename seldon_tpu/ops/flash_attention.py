"""Flash attention (blockwise online-softmax) — pallas TPU kernel.

Why: XLA materializes the [B, H, S, S] score tensor for naive attention;
at S=8192 that's 2 GB per head-batch in f32 — HBM-bound and cache-hostile.
The flash kernel streams K/V blocks through VMEM with running max/sum
accumulators, never materializing scores, trading HBM traffic for VMEM
reuse (the standard FlashAttention-2 schedule laid onto the MXU).

Layout: q [BH, Sq, Dh], k/v [BH, Skv, Dh] — callers fold batch x heads
(GQA callers expand kv heads to q heads first; the repeat is free under
XLA's gather fusion and keeps the kernel simple). `causal=True` masks with
the global positions q_offset + i >= j.

`flash_attention` IS the kernel: it compiles for the TPU or raises.
`attention_reference` is what tests compare it against (tests/test_ops.py
runs the kernel on CPU interpreted, tests/pallas_interpret.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference (XLA) implementation — the parity oracle
# ---------------------------------------------------------------------------


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True,
    q_offset: int = 0,
) -> jnp.ndarray:
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        qi = jnp.arange(Sq)[:, None] + q_offset
        kj = jnp.arange(Sk)[None, :]
        scores = jnp.where(qi >= kj, scores, NEG_INF)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", w, v)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal, block_q, block_k, scale, q_offset):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)  # query block index
    kj = pl.program_id(2)  # kv block index

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: a kv block strictly above the diagonal is fully masked — skip
    # its FLOPs entirely (≈2x saving over the full grid).
    if causal:
        visible = kj * block_k <= qi * block_q + (block_q - 1) + q_offset
    else:
        visible = True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0]  # [block_q, Dh]
        k = k_ref[0]  # [block_k, Dh]
        v = v_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]

        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + q_offset
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[:]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention(
    q: jnp.ndarray,  # [B*H, Sq, Dh]
    k: jnp.ndarray,  # [B*Hkv, Skv, Dh] (Hkv == H / q_per_kv)
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int = 0,
    q_per_kv: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jnp.ndarray:
    """Blockwise attention through the Pallas kernel. GQA is native
    (kv block index_map). Raises on shapes the grid cannot cover and on
    anything Mosaic refuses — there is no second implementation behind
    it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Sq, Dh = q.shape
    Skv = k.shape[1]
    if k.shape[0] * q_per_kv != BH:
        raise ValueError(
            f"kv rows {k.shape[0]} x group {q_per_kv} != q rows {BH}"
        )
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    if Sq % block_q or Skv % block_k:
        # A truncated grid would silently drop attention over the tail.
        raise ValueError(
            f"flash kernel needs divisible blocks: Sq={Sq}%{block_q}, "
            f"Skv={Skv}%{block_k}"
        )
    scale = Dh**-0.5

    grid = (BH, Sq // block_q, Skv // block_k)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        q_offset=q_offset,
    )
    # GQA: kv stays [B*Hkv, S, Dh]; the index_map folds each group of
    # q_per_kv query heads onto its shared kv row — no jnp.repeat, no
    # HBM duplication of K/V.
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, Dh), lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, Dh),
                lambda b, i, j: (b // q_per_kv, j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, Dh),
                lambda b, i, j: (b // q_per_kv, j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, Dh), lambda b, i, j: (b, i, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        name="flash_attention",
    )(q, k, v)
