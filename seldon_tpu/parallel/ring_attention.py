"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

Long-context attention where no single device ever holds the full K/V:
each device keeps its local sequence block and the K/V blocks rotate
around the ring via `ppermute` (ICI neighbor hops — bandwidth-optimal on
the torus), with blockwise online-softmax accumulation so the result is
exactly full attention (same math as ops/flash_attention.py, distributed).

The reference has nothing in this space (SURVEY.md §5.7 — its payloads are
tabular); this is first-class TPU capability for long-sequence serving and
training. Built on shard_map so it composes with GSPMD: 'sp' is manual
here, every other mesh axis stays automatic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attention_update(q, k, v, m, l, acc, q_pos, k_off, causal, scale):
    """One online-softmax accumulation step of q against a k/v block.
    q [BH, s, D]; k,v [BH, t, D]; m,l [BH, s, 1]; acc [BH, s, D] f32.
    q_pos [s] — global sequence position of each q row (rows need not be
    contiguous: the GQA fold interleaves G query groups per kv head)."""
    s_scores = jnp.einsum(
        "bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        rows = q_pos[:, None]
        cols = k_off + jnp.arange(k.shape[1])[None, :]
        s_scores = jnp.where(rows >= cols, s_scores, NEG_INF)
    m_cur = jnp.max(s_scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s_scores - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bqk,bkd->bqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, Dh] (global view, S sharded over `axis`)
    k: jnp.ndarray,  # [B, S, Hkv, Dh] — Hkv may be < H (GQA)
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
) -> jnp.ndarray:
    """Exact full attention with S sharded over `axis`. Returns [B,S,H,Dh]
    sharded the same way.

    GQA is native: with Hkv < H query heads group as H = Hkv * G
    (head h attends kv head h // G, matching gqa_attention), only the
    Hkv-head K/V blocks rotate around the ring — G× less ICI traffic and
    G× less resident K/V per device than pre-expanding to H heads — and
    each rotation's block update batches the G query groups per kv head
    into one [B*Hkv, G*s, t] matmul."""

    def local(q_loc, k_loc, v_loc):
        # q_loc [B, s, H, Dh]; k_loc/v_loc [B, s, Hkv, Dh] — this
        # device's sequence block.
        B, s, H, Dh = q_loc.shape
        Hkv = k_loc.shape[2]
        G = H // Hkv
        n = jax.lax.psum(1, axis)
        idx = jax.lax.axis_index(axis)
        scale = Dh**-0.5

        def fold_q(x):  # [B, s, H, Dh] -> [B*Hkv, G*s, Dh]
            return (x.reshape(B, s, Hkv, G, Dh)
                    .transpose(0, 2, 3, 1, 4)
                    .reshape(B * Hkv, G * s, Dh))

        def fold_kv(x):  # [B, s, Hkv, Dh] -> [B*Hkv, s, Dh]
            return x.transpose(0, 2, 1, 3).reshape(B * Hkv, s, Dh)

        qf = fold_q(q_loc)
        # Row r of the fold is query position r % s (group r // s).
        q_pos = idx * s + jnp.arange(G * s) % s

        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(step, carry):
            m, l, acc, k_cur, v_cur = carry
            src = (idx - step) % n  # which global block k_cur came from

            def update(args):
                m, l, acc = args
                return _block_attention_update(
                    qf, fold_kv(k_cur), fold_kv(v_cur), m, l, acc,
                    q_pos, src * s, causal, scale,
                )

            if causal:
                # Blocks strictly above the diagonal are fully masked —
                # skip their matmuls (~half the ring FLOPs). The predicate
                # is per-device and the branch has no collectives, so
                # divergence is safe.
                m, l, acc = jax.lax.cond(
                    src <= idx, update, lambda args: args, (m, l, acc)
                )
            else:
                m, l, acc = update((m, l, acc))
            # The final rotation's result is discarded by fori_loop — skip
            # the ICI hop (predicate is uniform across devices).
            k_nxt, v_nxt = jax.lax.cond(
                step < n - 1,
                lambda kv: (
                    jax.lax.ppermute(kv[0], axis, perm),
                    jax.lax.ppermute(kv[1], axis, perm),
                ),
                lambda kv: kv,
                (k_cur, v_cur),
            )
            return m, l, acc, k_nxt, v_nxt

        init = (
            jnp.full((B * Hkv, G * s, 1), NEG_INF, jnp.float32),
            jnp.zeros((B * Hkv, G * s, 1), jnp.float32),
            jnp.zeros((B * Hkv, G * s, Dh), jnp.float32),
            k_loc,
            v_loc,
        )
        m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, init)
        out = (acc / jnp.maximum(l, 1e-30)).astype(q_loc.dtype)
        return (out.reshape(B, Hkv, G, s, Dh)
                .transpose(0, 3, 1, 2, 4)
                .reshape(B, s, H, Dh))

    spec = P(None, axis, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
