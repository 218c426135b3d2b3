"""graftragged — shape-stable ragged unified-batch attention wave.

One kernel, one compiled dispatch, no bucket lattice. Every scheduler
wave runs this single fused function over ALL slots: mixed cold
prefills, chunked prefill continuations, prefix-warm resumes and decode
steps ride the same dispatch, so the engine compiles exactly ONE
variant — key ``("ragged", chunk)`` — instead of one per
(prefix bucket, suffix bucket, pow2 group) cell (the Ragged Paged
Attention design, PAPERS.md).

Wave layout (all shapes are config constants — nothing about the live
mix appears in any array shape):

 * ``tokens``: the flat ``[max_tokens]`` token buffer with
   ``max_tokens = max_slots * chunk`` — slot ``s`` owns the fixed
   segment ``[s * chunk, (s + 1) * chunk)`` (fixed stride keeps the
   buffer shape-stable AND makes the per-slot view a free reshape; a
   packed variable-stride buffer would need a gather keyed on the mix).
 * per-slot descriptors, each ``[max_slots]``: ``starts`` (tokens of
   the request already KV-resident — prior chunks plus any zero-copy
   prefix-trie hit; this wave's segment lands at absolute positions
   ``start + i``), ``plens`` (full prompt length, so
   ``kv_len = min(plens, starts + chunk)`` after the wave), sampling
   knobs (seed/temp/top_k/top_p/max_new), ``finals`` (this wave
   completes the row's prompt: sample its first token), and
   ``is_prefill`` (the occupancy mask — rows NOT prefilling this wave
   keep their state bit-for-bit and their KV writes route to the
   trash block).
 * ``table``: the ``[max_slots, max_seq_len // kv_block]`` paged block
   tables — block tables are the wave's only KV currency, which is why
   ragged requires the paged engine.

The math is deliberately the engine's proven paged kernels composed
into one trace: the prefill phase is ``_paged_admit_chunk_impl`` with
the resident-prefix width pinned to the FULL table (masking, not
shape, hides the tail — f32 softmax with the -1e30 mask makes wider
padding bit-neutral) and per-row occupancy masking; the decode phase
is ``_paged_chunk_impl`` with one step. A slot's keys, termination and
arming are the engine's own (models/slot.py), int8 KV scales ride along
unchanged, so greedy outputs are bit-identical to the ragged-off engine
— the migration gate tests/test_ragged.py pins.

Kernel legs (``RAGGED_KERNEL`` / EngineConfig.ragged_kernel —
graftkern): the paragraph above describes ``kernel="masked"``, the
bit-exact baseline. ``"sparse"`` / ``"pallas"`` swap the full-width
reads for the block-sparse walkers in ops/ragged_paged_attention.py —
per row only ``ceil(context / kv_block)`` live pool blocks are
touched, with online softmax across blocks and int8 dequant fused into
the walk — and additionally skip the ENTIRE prefill leg under a traced
``lax.cond(any(is_prefill))`` on decode-only waves (the dominant CPU
cost of the masked wave was a dead full-width prefill on ~5 of every 6
waves). Both stay inside the single ``("ragged", C)`` variant: the
kernel choice is a config constant closed over at jit time, the cond
predicates are traced scalars, and the walkers' per-iteration shapes
are static — zero new variants, zero live retraces (compile-audit runs
the RAGGED leg once per kernel). Numerics: the sparse leg runs the
masked-MATCHED two-pass walk (ops/ragged_paged_attention
"Masked-matched") — the masked kernels' exact term set, softmax
weights rounded to the activation dtype before the value dot, so
sparse-vs-masked differences reduce to f32 summation order and greedy
outputs stay token-identical (the contract
tests/test_ragged_kernel.py pins; raw logits within
ops/ragged_paged_attention.RAGGED_LOGITS_ATOL). The pallas leg keeps
the fused one-pass f32 partials (atol contract only). Non-greedy
sampling may diverge in ulps, so ``masked`` remains the
any-temperature exactness leg. A wave
whose longest live row exceeds ``block_budget`` blocks (> 0) falls
back to the masked leg IN-TRACE via ``lax.cond`` — never truncates,
never retraces.

Capacity is NOT padding: a wave's unused token-slots cost the real
ragged TPU kernel nothing (it walks per-request token counts, the
whole point), so the sched ledger accounts a wave as
``useful == packed tokens`` with zero bucket/group pad — see
docs/benchmarking.md "Ragged dispatch" for the sizing formula and the
tiny-batch crossover where the dense path still wins.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from seldon_tpu.models import slot, transformer
from seldon_tpu.models.config import ModelConfig
from seldon_tpu.ops import ragged_paged_attention as rpa

Cache = Dict[str, jnp.ndarray]
State = Dict[str, Any]

RAGGED_KERNELS = ("masked", "sparse", "pallas")


def token_buffer_size(max_slots: int, chunk: int) -> int:
    """The wave's fixed token capacity: ``max_slots * chunk``. Sizing
    formula (docs/benchmarking.md): chunk bounds per-wave prefill
    progress per slot, so TTFT under load ~ ceil(prompt / chunk) waves;
    HBM workspace and host-array traffic scale with the product."""
    return max_slots * chunk


def _prefill_logits_sparse(
    params: Any,
    toks: jnp.ndarray,    # [B, Sc] this wave's suffix segments
    plens: jnp.ndarray,
    starts: jnp.ndarray,  # [B] raw descriptor starts (idle = Smax)
    bound: jnp.ndarray,   # [B] pool visibility (idle rows clamped to 0)
    pool: Cache,
    table: jnp.ndarray,
    cfg: ModelConfig,
    mode: str,
    tp=None,
) -> Tuple[jnp.ndarray, Cache]:
    """Block-sparse twin of paged_prefix_view + prefill_with_prefix:
    per layer, the walker covers only the LIVE pool blocks combined
    with the causal fresh suffix — no full-width gather, no
    [B, Sc, Smax] score slab. Same (logits, fresh-KV ys) contract as
    prefill_with_prefix; idle rows' pool walk is clamped to zero
    blocks via `bound` (their outputs are discarded by slot.arm's mask, so
    only live rows pin parity). mode "sparse" runs the masked-MATCHED
    two-pass walk in gqa_attention's convention — int8 pool KV
    dequantized into the query dtype first, softmax weights rounded to
    the query dtype over pool AND suffix alike, one f32 accumulation
    with one output cast — so the term set is prefill_with_prefix's
    exactly; "pallas" keeps the fused one-pass partials."""
    B, Sc = toks.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    x = transformer._embed_rows(params, toks, transformer._dtype(cfg))
    positions = starts[:, None] + jnp.arange(Sc)[None, :]
    inv_freq = transformer.rope_frequencies(cfg)
    bound2 = jnp.broadcast_to(bound[:, None], (B, Sc)).astype(jnp.int32)
    smask = jnp.broadcast_to(
        jnp.tril(jnp.ones((Sc, Sc), dtype=bool))[None], (B, Sc, Sc)
    )

    def body(carry, xs):
        bp, pl = xs
        h = transformer.rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = transformer._qkv(h, bp, cfg, positions, inv_freq,
                                   tp=tp)
        qr = q.reshape(B, Sc, Hkv, -1, Dh)
        # Fresh causal suffix: the diagonal is always visible, so the
        # combine's total max is finite on every row.
        s_f = jnp.einsum(
            "bskgd,btkd->bkgst", qr, k,
            preferred_element_type=jnp.float32,
        ) / (Dh**0.5)
        s_f = jnp.where(smask[:, None, None, :, :], s_f, rpa.NEG_INF)
        if mode == "sparse":
            m_p, l_p = rpa.sparse_max_sum(qr, pl, table, bound2,
                                          dequant=True)
            m_t = jnp.maximum(m_p, jnp.max(s_f, axis=-1, keepdims=True))
            p_f = jnp.exp(s_f - m_t)
            l_t = l_p * jnp.exp(m_p - m_t) \
                + jnp.sum(p_f, axis=-1, keepdims=True)
            acc = rpa.sparse_weighted_value(qr, pl, table, bound2,
                                            m_t, l_t, dequant=True)
            acc = acc + jnp.einsum(
                "bkgst,bktd->bkgsd",
                (p_f / l_t).astype(qr.dtype),
                v.transpose(0, 2, 1, 3).astype(qr.dtype),
                preferred_element_type=jnp.float32,
            )
            attn = acc.transpose(0, 3, 1, 2, 4).reshape(B, Sc, -1)
        else:
            parts = rpa.ragged_paged_partials(qr, pl, table, bound2,
                                              mode=mode)
            attn = rpa.combine_fresh(parts, s_f, v.transpose(0, 2, 1, 3))
        attn = attn.astype(carry.dtype)
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        x = carry + transformer._qdot(attn, bp, "wo", cfg)
        x, aux = transformer._mlp_res(x, bp, cfg, None, tp=tp)
        return x, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), aux)

    x, (ks, vs, _) = jax.lax.scan(body, x, (params["blocks"], pool))
    last = jnp.clip(plens - starts - 1, 0, Sc - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return transformer._logits(params, x_last, cfg)[:, 0], {
        "k": ks, "v": vs,
    }


def _decode_step_sparse(
    params: Any,
    token: jnp.ndarray,  # [B] int32 current tokens
    pos: jnp.ndarray,    # [B] int32 positions to write at
    bound: jnp.ndarray,  # [B] pool visibility (inactive rows = 0)
    pool: Cache,
    table: jnp.ndarray,
    cfg: ModelConfig,
    mode: str,
    tp=None,
) -> Tuple[jnp.ndarray, Cache]:
    """Block-sparse twin of paged_decode_step: per layer, the walker
    covers the live pool blocks and combines with the one
    always-visible fresh column — no full-width paged_gather_kv.
    mode "sparse" runs the masked-MATCHED two-pass walk
    (ops/ragged_paged_attention "Masked-matched"): weights normalized
    in f32, scaled, rounded to the query dtype before the value dot —
    gqa_attention_decode's exact term set, so greedy argmax survives
    the block reassociation. mode "pallas" keeps the fused one-pass
    f32 partials (the TPU leg). Fresh KV lands after the scan in the
    SAME batched trash-routed scatter as _run_blocks_decode_paged
    (inactive rows write block 0)."""
    B = token.shape[0]
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    quantized = cfg.kv_cache_dtype == "int8"
    block = pool["k"].shape[3]
    x = transformer._embed_rows(params, token,
                                transformer._dtype(cfg))[:, None, :]
    positions = pos[:, None]
    inv_freq = transformer.rope_frequencies(cfg)
    bound2 = bound[:, None].astype(jnp.int32)

    def body(carry, xs):
        bp, pl = xs
        h = transformer.rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = transformer._qkv(h, bp, cfg, positions, inv_freq,
                                   tp=tp)
        qr = q.reshape(B, 1, Hkv, -1, Dh)
        s_f = jnp.einsum(
            "bskgd,bukd->bkgsu", qr, k,
            preferred_element_type=jnp.float32,
        ) / (Dh**0.5)
        if mode == "sparse":
            m_p, l_p = rpa.sparse_max_sum(qr, pl, table, bound2)
            m_t = jnp.maximum(m_p, s_f)
            p_f = jnp.exp(s_f - m_t)
            l_t = l_p * jnp.exp(m_p - m_t) + p_f
            acc = rpa.sparse_weighted_value(qr, pl, table, bound2,
                                            m_t, l_t)
            # gqa_attention_decode's two-einsum tail: pool contribution
            # cast once, fresh column in query dtype, added in it.
            out = acc.astype(qr.dtype) + jnp.einsum(
                "bkgsu,bukd->bkgsd",
                (p_f / l_t).astype(qr.dtype),
                v.astype(qr.dtype),
            )
            attn = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, -1)
        else:
            parts = rpa.ragged_paged_partials(qr, pl, table, bound2,
                                              mode=mode)
            attn = rpa.combine_fresh(parts, s_f,
                                     v.transpose(0, 2, 1, 3))
        attn = attn.astype(carry.dtype)
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        x = carry + transformer._qdot(attn, bp, "wo", cfg)
        x, aux = transformer._mlp_res(x, bp, cfg, None, tp=tp)
        if quantized:
            kq, ksc = transformer._quantize_kv(k[:, 0])
            vq, vsc = transformer._quantize_kv(v[:, 0])
            fresh = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            dt = pool["k"].dtype
            fresh = {"k": k[:, 0].astype(dt), "v": v[:, 0].astype(dt)}
        return x, (fresh, aux)

    x, (fresh, _) = jax.lax.scan(body, x, (params["blocks"], pool))
    rows = jnp.arange(B)
    idx = pos // block
    # Same OOB trash-routing as _run_blocks_decode_paged: pos at Smax
    # must not clamp into the row's last (possibly shared) block.
    bid = jnp.where(
        idx < table.shape[1],
        table[rows, jnp.minimum(idx, table.shape[1] - 1)],
        0,
    )
    off = pos % block
    new_pool = {
        key: pool[key].at[:, bid, :, off].set(
            jnp.swapaxes(fresh[key], 0, 1)
        )
        for key in pool
    }
    return transformer._logits(params, x, cfg)[:, 0], new_pool


def ragged_prefill_phase(
    params: Any,
    state: State,
    table: jnp.ndarray,   # [B, NBs] int32 block tables
    tokens: jnp.ndarray,  # [B * chunk] flat token buffer
    plens: jnp.ndarray,   # [B] full prompt lengths
    starts: jnp.ndarray,  # [B] KV-resident tokens (chunk start)
    seeds: jnp.ndarray,
    temps: jnp.ndarray,
    top_ks: jnp.ndarray,
    top_ps: jnp.ndarray,
    max_news: jnp.ndarray,
    finals: jnp.ndarray,      # [B] bool — last chunk: sample + arm
    is_prefill: jnp.ndarray,  # [B] bool occupancy mask
    cfg: ModelConfig,
    tp=None,
    kernel: str = "masked",
    block_budget: int = 0,
) -> Tuple[State, jnp.ndarray, jnp.ndarray]:
    """The wave's prefill leg: run every occupied segment of the token
    buffer through prefill_with_prefix against the FULL block-table
    gather (resident width = the whole window; the t < start mask hides
    the tail, so one static width serves every mix), scatter fresh KV
    through the tables, sample first tokens on final rows. Exactly
    ``_paged_admit_chunk_impl`` with the group axis pinned to all slots
    and non-prefill rows masked out (their descriptors trash-route the
    scatter: start = Smax puts every write past the table).

    ``kernel`` swaps the attention head for the block-sparse walkers
    (module docstring "Kernel legs"); sampling, scatter and state
    masking below are shared across legs. ``block_budget`` > 0
    bounds the sparse walk: a wave whose longest live row needs more
    blocks falls back to the masked head in-trace (lax.cond — one
    variant either way)."""
    pool = state["cache"]
    block = pool["k"].shape[3]
    nbs = table.shape[1]
    Smax = nbs * block
    B = table.shape[0]
    Sc = tokens.shape[0] // B
    toks = tokens.reshape(B, Sc)

    def masked_head():
        prefix_kv = transformer.paged_prefix_view(pool, table, nbs)
        return transformer.prefill_with_prefix(
            params, toks, plens, prefix_kv, starts, cfg, tp=tp
        )

    if kernel == "masked":
        logits, kv = masked_head()
    else:
        bound = jnp.where(is_prefill, starts, 0).astype(jnp.int32)

        def sparse_head():
            return _prefill_logits_sparse(
                params, toks, plens, starts, bound, pool, table, cfg,
                kernel, tp=tp,
            )

        if block_budget > 0:
            n_live = (jnp.max(bound) + block - 1) // block
            logits, kv = jax.lax.cond(
                n_live <= block_budget, sparse_head, masked_head
            )
        else:
            logits, kv = sparse_head()
    first, first_done = slot.first_token(
        logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
    new_pos = jnp.minimum(plens, starts + Sc)
    writes = transformer.kv_writes(kv, pool, cfg)
    spos = starts[:, None] + jnp.arange(Sc)[None, :]
    new_pool = transformer.paged_scatter_tokens(pool, writes, table,
                                                spos)
    # The KV pool is not masked: idle rows' writes are trash-routed by
    # position.
    new_state = slot.arm(
        state, mask=is_prefill, cache=new_pool, first=first,
        done=first_done, pos=new_pos, finals=finals, temps=temps,
        top_ks=top_ks, top_ps=top_ps, seeds=seeds, max_news=max_news,
    )
    return new_state, first, first_done


def ragged_decode_phase(
    params: Any,
    state: State,
    table: jnp.ndarray,
    cfg: ModelConfig,
    tp=None,
    kernel: str = "masked",
    block_budget: int = 0,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The wave's decode leg: ONE decode step over every slot, reading
    and writing KV through the block tables — ``_paged_chunk_impl``
    with n_steps = 1 (the same slot.decode_chunk, so the primitive
    sequence — and therefore greedy argmax — matches the ragged-off
    engine exactly). Rows armed by this wave's prefill leg decode
    immediately, mirroring the off path where the decode chunk follows
    the admissions inside one scheduler wave.

    ``kernel`` != "masked" swaps paged_decode_step for the block-sparse
    step (inactive rows' pool walk clamps to zero blocks — their
    outputs and KV writes are already dead by the ``run`` mask and
    trash routing). Returns slot.decode_chunk's (state, toks, valid,
    counts)."""
    block = state["cache"]["k"].shape[3]
    Smax = table.shape[1] * block

    def step_model(carry):
        def masked_step():
            return transformer.paged_decode_step(
                params, carry["last_tok"], carry["pos"], carry["cache"],
                table, cfg, tp=tp,
            )

        if kernel == "masked":
            return masked_step()
        bound = jnp.where(
            carry["active"], carry["pos"], 0).astype(jnp.int32)

        def sparse_step():
            return _decode_step_sparse(
                params, carry["last_tok"], carry["pos"], bound,
                carry["cache"], table, cfg, kernel, tp=tp,
            )

        if block_budget > 0:
            n_live = (jnp.max(bound) + block - 1) // block
            return jax.lax.cond(
                n_live <= block_budget, sparse_step, masked_step
            )
        return sparse_step()

    return slot.decode_chunk(step_model, state, 1, Smax, cfg)


def ragged_wave(
    params: Any,
    state: State,
    table: jnp.ndarray,
    tokens: jnp.ndarray,
    plens: jnp.ndarray,
    starts: jnp.ndarray,
    seeds: jnp.ndarray,
    temps: jnp.ndarray,
    top_ks: jnp.ndarray,
    top_ps: jnp.ndarray,
    max_news: jnp.ndarray,
    finals: jnp.ndarray,
    is_prefill: jnp.ndarray,
    cfg: ModelConfig,
    tp=None,
    kernel: str = "masked",
    block_budget: int = 0,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """One full unified wave: prefill leg then decode leg in a single
    trace (ONE dispatch, ONE compiled variant). Returns
    ``(state, first [B], first_done [B], toks [1, B], valid [1, B],
    counts)`` — first/first_done are slot-indexed (the caller reads row
    ``req.slot``), toks/valid/counts flow through the engine's
    chunk-boundary processing unchanged.

    Sparse/pallas kernels additionally skip the WHOLE prefill leg on
    decode-only waves via a traced ``lax.cond`` — the dominant masked-
    wave CPU cost was a dead full-width prefill on every decode-only
    wave. XLA's Conditional executes only the live branch, and the cond
    is inside the one ("ragged", C) variant, so the lattice and retrace
    counts are untouched. The masked leg keeps its original cond-free
    trace: it is the bit-exactness baseline and must not change."""
    if kernel == "masked":
        state, first, first_done = ragged_prefill_phase(
            params, state, table, tokens, plens, starts, seeds, temps,
            top_ks, top_ps, max_news, finals, is_prefill, cfg, tp=tp,
        )
    else:
        B = table.shape[0]

        def run_prefill(st):
            return ragged_prefill_phase(
                params, st, table, tokens, plens, starts, seeds, temps,
                top_ks, top_ps, max_news, finals, is_prefill, cfg,
                tp=tp, kernel=kernel, block_budget=block_budget,
            )

        def skip_prefill(st):
            return (st, jnp.zeros((B,), jnp.int32),
                    jnp.zeros((B,), bool))

        state, first, first_done = jax.lax.cond(
            jnp.any(is_prefill), run_prefill, skip_prefill, state
        )
    state, toks, valid, counts = ragged_decode_phase(
        params, state, table, cfg, tp=tp, kernel=kernel,
        block_budget=block_budget,
    )
    return state, first, first_done, toks, valid, counts
