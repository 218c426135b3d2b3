"""graftspec — speculative decoding device kernels: draft + verify.

Speculative decoding converts draft-model throughput into target-model
throughput: a cheap drafter proposes ``k`` tokens per live slot and the
target model scores all ``k + 1`` positions in ONE wide dispatch
(``verify_wave``) instead of ``k + 1`` sequential decode steps. Because
this engine's sampling is deterministic-per-row — every emitted token
is keyed by its row's seed and position (``slot.step_key``) —
verification is EXACT, not probabilistic: the wave samples the
target's own token at each position with the sequential keys and
accepts drafts only while they match, so the emitted stream is
bit-identical to the spec-off engine for ANY temperature, not just
greedy. The draft only ever decides how
many sequential steps are skipped, never what is emitted.

Numerics: sequential decode computes position ``p`` by attending
positions ``t < p`` from the CACHE (int8 caches round-trip through
quantize/dequantize) plus its OWN column as one exact bf16 fresh
column (``gqa_attention_decode``). The wide pass reproduces that
per query row: the per-layer block-table gather
(``paged_gather_kv``) yields the same dense cache view decode reads,
the wave's own suffix k/v are scattered INTO that view in cache dtype
(so query row ``i`` sees rows ``j < i`` exactly as the cache decode
step ``i`` would — already round-tripped), and
``gqa_attention_verify`` is ``gqa_attention_decode`` generalized to
``Sq`` query rows with a per-row strict mask and a DIAGONAL fresh
column. Stale pool values at positions >= a row's rewound ``pos``
(rejected drafts from an earlier wave) are always shadowed by that
in-layer view scatter before any mask exposes them, which is what
makes host-side rollback a pure block-table trim.

The commit scatter writes all ``Sq`` suffix positions through the
block tables unconditionally (non-wave rows route to the trash
block): positions past the accepted prefix are dead — every future
reader either rewrites them first (view scatter above) or masks them
(strict ``t < pos``) — so acceptance never syncs the host mid-wave.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_tpu.models import slot, transformer
from seldon_tpu.models.config import ModelConfig

Cache = Dict[str, jnp.ndarray]
State = Dict[str, Any]


def gqa_attention_verify(
    q: jnp.ndarray,  # [B, Sq, H, Dh]
    ck: jnp.ndarray,  # [B, Hkv, T, Dh] cache view (int8 if scales)
    cv: jnp.ndarray,  # [B, Hkv, T, Dh]
    k_fresh: jnp.ndarray,  # [B, Sq, Hkv, Dh] bf16 (exact, own column)
    v_fresh: jnp.ndarray,  # [B, Sq, Hkv, Dh]
    mask_lt: jnp.ndarray,  # [B, Sq, T] True where t < row position (strict)
    k_scale: Optional[jnp.ndarray] = None,  # [B, Hkv, T] (int8 cache)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``gqa_attention_decode`` generalized to Sq query rows.

    Each query row attends the cache view under its OWN strict mask
    plus a DIAGONAL fresh column (row i's exact bf16 k/v — never the
    other rows', whose cache-dtype values live in the view). Scales
    stay factored out of the einsums and the fresh column rides the
    same flash-style max/exp combine, so row i's arithmetic is the
    decode kernel's arithmetic at the same T width — the wave is a
    batch of decode steps, not an approximation of one."""
    B, S, H, Dh = q.shape
    Hkv = ck.shape[1]
    G = H // Hkv
    qr = q.reshape(B, S, Hkv, G, Dh)
    scores = jnp.einsum(
        "bskgd,bktd->bkgst", qr, ck.astype(qr.dtype),
        preferred_element_type=jnp.float32,
    ) / (Dh**0.5)
    if k_scale is not None:
        scores = scores * k_scale[:, :, None, None, :]
    # Diagonal fresh column: row i against ITS OWN k only ("bskgd,bskd"
    # contracts d and keeps s paired — the decode kernel's [s, u=1]
    # outer product collapsed onto s == u).
    s_fresh = jnp.einsum(
        "bskgd,bskd->bkgs", qr, k_fresh.astype(qr.dtype),
        preferred_element_type=jnp.float32,
    )[..., None] / (Dh**0.5)
    scores = jnp.where(mask_lt[:, None, None, :, :], scores, -1e30)
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), s_fresh)
    p = jnp.exp(scores - m)
    p_f = jnp.exp(s_fresh - m)  # [B,k,g,S,1]
    l = jnp.sum(p, axis=-1, keepdims=True) + p_f
    wc = p / l
    if v_scale is not None:
        wc = wc * v_scale[:, :, None, None, :]
    out = jnp.einsum(
        "bkgst,bktd->bskgd", wc.astype(qr.dtype), cv.astype(qr.dtype)
    ) + jnp.einsum(
        "bkgs,bskd->bskgd", (p_f / l)[..., 0].astype(qr.dtype),
        v_fresh.astype(qr.dtype),
    )
    return out.reshape(B, S, H * Dh)


def _run_blocks_verify(params, x, cfg, positions, inv_freq, mask_lt, pool,
                       table, tp=None):
    """Layer scan for the VERIFY wave: per layer, gather the dense
    cache view through the block tables, scatter this wave's own
    suffix k/v into it in CACHE DTYPE (int8 round-trip — the very
    arrays committed to the pool after the scan), and run the widened
    decode attention. The ephemeral view scatter is what lets query
    row i read rows j < i exactly as sequential decode would read them
    back from the cache."""
    quantized = cfg.kv_cache_dtype == "int8"
    B, Sq = positions.shape
    rows = jnp.arange(B)[:, None]

    def body(carry, xs):
        bp, pl = xs
        h = transformer.rms_norm(carry, bp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = transformer._qkv(h, bp, cfg, positions, inv_freq,
                                   tp=tp)
        if quantized:
            kq, ksc = transformer._quantize_kv(k)  # [B,Sq,Hkv,(Dh)]
            vq, vsc = transformer._quantize_kv(v)
            view = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            dt = pool["k"].dtype
            view = {"k": k.astype(dt), "v": v.astype(dt)}
        cl = transformer.paged_gather_kv(pl, table)  # [B,Hkv,Smax,(Dh)]
        # Advanced indices (rows, positions) broadcast to [B, Sq] and
        # land in front, so the update operand keeps the [B,Sq,Hkv,...]
        # layout; OOB rows (pos past the window) drop.
        cl = {
            key: cl[key].at[rows, :, positions].set(
                view[key], mode="drop"
            )
            for key in cl
        }
        attn = gqa_attention_verify(
            q, cl["k"], cl["v"], k, v, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"),
        )
        if tp is not None:
            attn = tp.gather(tp.flat(attn))
        x = carry + transformer._qdot(attn, bp, "wo", cfg)
        x, aux = transformer._mlp_res(x, bp, cfg, None, tp=tp)
        # ys in paged_scatter_tokens layout: [B, Hkv, Sq, (Dh)].
        fresh = {key: jnp.swapaxes(view[key], 1, 2) for key in view}
        return x, (fresh, aux)

    x, (fresh, aux) = jax.lax.scan(body, x, (params["blocks"], pool))
    return x, fresh, jnp.mean(aux)


def verify_wave(
    params: Any,
    state: State,
    table: jnp.ndarray,  # [B, NBs] int32 block tables
    drafts: jnp.ndarray,  # [B, k] int32 proposed tokens
    wave: jnp.ndarray,  # [B] bool — row participates in this wave
    cfg: ModelConfig,
    tp=None,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One speculative verify wave over all B slots.

    Inputs per wave row are ``[last_tok, d_1 .. d_k]`` at positions
    ``pos .. pos + k``; the target's token at each position is sampled
    with the sequential key (``slot.step_key`` at ``pos_i``) and
    drafts are accepted while they MATCH — so every row emits between
    1 (first draft rejected: plain decode) and k + 1 (full acceptance
    + the bonus token) tokens, all bit-identical to sequential decode.
    The per-step accept chain is unrolled host-side (k is static) and
    each link IS the decode chunk's step (``slot.decode_step``), so a
    row finishing mid-prefix truncates its acceptance chain the same
    way a finished row freezes a chunk.

    Returns (state, toks [k+1, B], valid [k+1, B], counts) — valid
    columns are True-prefixes and counts the k + 1 steps' summed, the
    _process_chunk contract.

    The layers attend by ``gqa_attention_verify`` over the gathered
    pool view (_run_blocks_verify): the one leg, and the one that
    carries the any-temperature bit-identity above."""
    k = drafts.shape[1]
    Sq = k + 1
    pool = state["cache"]
    block = pool["k"].shape[3]
    Smax = table.shape[1] * block
    pos0 = state["pos"]
    inputs = jnp.concatenate(
        [state["last_tok"][:, None], drafts], axis=1
    )  # [B, Sq]
    positions = pos0[:, None] + jnp.arange(Sq)[None, :]  # [B, Sq]
    # Strict per-row mask: query row i sees t < pos + i — the decode
    # step's t < pos at each unrolled position.
    mask_lt = (
        jnp.arange(Smax)[None, None, :] < positions[:, :, None]
    )  # [B, Sq, Smax]
    x = transformer._embed_rows(params, inputs, transformer._dtype(cfg))
    inv_freq = transformer.rope_frequencies(cfg)

    x, fresh, _ = _run_blocks_verify(
        params, x, cfg, positions, inv_freq, mask_lt, pool, table, tp=tp,
    )
    # All Sq positions project to logits: Sq = k + 1 stays small, and
    # the acceptance chain below needs every row's candidate.
    logits = transformer._logits(params, x, cfg)  # [B, Sq, V] f32
    # Commit every suffix position through the tables; non-wave rows
    # route to the trash block. Rejected-tail positions are dead by the
    # shadowing argument in the module docstring.
    spos = jnp.where(wave[:, None], positions, Smax)
    new_pool = transformer.paged_scatter_tokens(pool, fresh, table, spos)

    # Unrolled acceptance chain: decode steps over the wave's rows, the
    # chain broken at the first draft mismatch or finished row (a row
    # that ran is still active exactly when its token did not end it).
    carry = state
    run = wave & state["active"]
    toks_list = []
    valid_list = []
    counts = 0
    for i in range(Sq):
        carry, tok, ran, step_counts = slot.decode_step(
            carry, logits[:, i], new_pool, Smax, cfg, run=run)
        toks_list.append(tok)
        valid_list.append(ran)
        counts = counts + step_counts
        if i < k:
            run = run & carry["active"] & (tok == drafts[:, i])
    return carry, jnp.stack(toks_list), jnp.stack(valid_list), counts


def draft_tokens(
    params: Any,
    window: jnp.ndarray,  # [B, W] int32 right-padded history windows
    wlens: jnp.ndarray,  # [B] true window lengths (>= 1)
    cfg: ModelConfig,
    k: int,
) -> jnp.ndarray:
    """Model drafter: k greedy continuations of each row's sliding
    history window, in ONE dispatch (prefill + a k-1 step scan over a
    scratch dense cache). Stateless by design — the draft model keeps
    no KV between waves, so rollback needs no draft-side bookkeeping
    and the draft cache costs W + k tokens of scratch HBM, not a
    second resident pool. Greedy always: drafts are proposals; only
    determinism matters, acceptance is decided by the target.
    Returns drafts [B, k] int32."""
    B, W = window.shape
    cache = transformer.init_cache(cfg, B, W + k)
    logits, cache = transformer.prefill(params, window, wlens, cache, cfg)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if k == 1:
        return tok0[:, None]

    def step(carry, _):
        tok, pos, cache = carry
        logits, cache = transformer.decode_step(
            params, tok, pos, cache, cfg
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, pos + 1, cache), nxt

    (_, _, _), rest = jax.lax.scan(
        step, (tok0, wlens, cache), None, length=k - 1
    )
    return jnp.concatenate([tok0[:, None], rest.T], axis=1)
