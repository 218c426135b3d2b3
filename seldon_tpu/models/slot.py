"""A slot's life: armed by an admission, advanced by a decode step, ended
by one rule.

Every engine program that touches a slot (the dense, prefix, chunked and
paged admissions, the dense and paged decode chunks, the speculative
verify wave) calls these functions, so the paths cannot drift: the same
seed and prompt give the same completion whatever else shares the batch
and whichever path serves it.

State is a dict of [B] arrays beside the KV cache (`fresh`). Keys: the
first token of a request is drawn under key(seed) folded with the
prompt's length, the token after position `pos` under key(seed) folded
with pos + 1 — one sequence of keys per request, by absolute position.
Termination: EOS, the request's budget, or the cache window.

A model that generates by diffusion over blocks (cfg.gen_block) holds
the block in hand beside that: `blk_tok` / `blk_known` [B, Bk], the
block's tokens and which of them are decided (state, not a token
value), and `blk_skip` [B], how many of its leading positions are the
prompt's tail and are not emitted. Its step is a PASS (`block_step`):
it denoises (decides some positions, emits nothing, leaves the cache as
it was) or, once every position is decided, commits (the block's K/V
rows are written, its tokens emitted, pos + Bk). The token at absolute
position a is drawn under the same key an autoregressive row draws it,
key(seed) folded with a.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_tpu.models import sampling
from seldon_tpu.models.config import ModelConfig

State = Dict[str, Any]


def fresh(cache: Any, batch: int, gen_block: int = 0) -> State:
    """No slot holds a request."""
    block = {
        "blk_tok": jnp.zeros((batch, gen_block), jnp.int32),
        "blk_known": jnp.zeros((batch, gen_block), jnp.bool_),
        "blk_skip": jnp.zeros((batch,), jnp.int32),
    } if gen_block else {}
    return {
        **block,
        "cache": cache,
        "last_tok": jnp.zeros((batch,), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
        "active": jnp.zeros((batch,), jnp.bool_),
        "temp": jnp.ones((batch,), jnp.float32),
        "top_k": jnp.zeros((batch,), jnp.int32),
        "top_p": jnp.ones((batch,), jnp.float32),
        "seeds": jnp.zeros((batch,), jnp.uint32),
        "remaining": jnp.zeros((batch,), jnp.int32),
    }


def first_key(seeds: jnp.ndarray, plens: jnp.ndarray) -> jax.Array:
    """[G] keys of each request's first token; `plens` are FULL prompt
    lengths, whatever part of the prompt this program computes."""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p)
    )(seeds, plens)


def step_key(seeds: jnp.ndarray, pos: jnp.ndarray) -> jax.Array:
    """[B] keys of the token a decode step samples after position `pos`."""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p + 1)
    )(seeds, pos)


def first_done(first, max_news, plens, Smax: int, cfg: ModelConfig):
    """The first token already ends the request."""
    return (
        (first == cfg.eos_token_id)
        | (max_news <= 1)
        | (plens + 1 >= Smax)
    )


def first_token(logits, seeds, plens, temps, top_ks, top_ps, max_news,
                Smax: int, cfg: ModelConfig):
    """An admission's (first tokens [G], whether each ends its request),
    from the logits at every row's last prompt token."""
    first = sampling.sample_per_row(
        logits, first_key(seeds, plens), temps, top_ks, top_ps)
    return first, first_done(first, max_news, plens, Smax, cfg)


def step_done(run, tok, remaining, pos, Smax: int, cfg: ModelConfig):
    """The token a running row just emitted ends its request;
    `remaining` and `pos` are the row's values after that token."""
    return run & (
        (tok == cfg.eos_token_id)
        | (remaining <= 0)
        | (pos >= Smax - 1)
    )


def arm(
    state: State, slots: Optional[jnp.ndarray] = None, *,
    mask: Optional[jnp.ndarray] = None, cache, first, done, pos,
    finals=None, temps, top_ks, top_ps, seeds, max_news, block=None,
) -> State:
    """Write an admission into the slot state: by index (`slots` [G];
    rows that pad a group repeat a real row's slot and data, so the
    duplicate writes are well-defined) or, for a program that spans
    every slot, where `mask` [B] holds (the other rows keep every field
    bit for bit). A row decodes from here unless its first token ended
    it (`done`) or its prompt is not whole yet (`finals` False: the row
    deposited KV only and its sampled token is discarded). `block`
    (cfg.gen_block): the first block in hand, {"blk_tok", "blk_known",
    "blk_skip"}; such a row has no first token (`first` is ignored by
    its steps) and its whole budget left."""
    def put(name, value):
        old = state[name]
        if slots is None:
            return jnp.where(mask, value, old)
        return old.at[slots].set(value)

    return {
        "cache": cache,
        "last_tok": put("last_tok", first),
        "pos": put("pos", pos),
        "active": put("active", ~done if finals is None else finals & ~done),
        "temp": put("temp", temps),
        "top_k": put("top_k", top_ks),
        "top_p": put("top_p", top_ps),
        "seeds": put("seeds", seeds),
        "remaining": put("remaining",
                         max_news - 1 if block is None else max_news),
        **{name: put(name, value) for name, value in (block or {}).items()},
    }


def decode_step(
    carry: State, logits: jnp.ndarray, cache, Smax: int, cfg: ModelConfig,
    run: Optional[jnp.ndarray] = None,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """All of a decode step that is not the model call: sample each
    running row's token from `logits` [B, V] under its position's key,
    advance and terminate value-level (a finished row stops advancing
    and emits pad tokens), carry `cache` on. `run` defaults to the
    armed rows; the speculative chain narrows it. Rows that are not
    running ask nothing of the sampler (sampling.live_knobs).

    Returns (carry, tok [B], run [B], counts [3] int32: the step, whether
    its sampler drew, whether it masked — sampling.tier of the running
    rows' knobs)."""
    if run is None:
        run = carry["active"]
    keys = step_key(carry["seeds"], carry["pos"])
    knobs = sampling.live_knobs(
        run, carry["temp"], carry["top_k"], carry["top_p"])
    tok = sampling.sample_per_row(logits, keys, *knobs)
    tok = jnp.where(run, tok, cfg.pad_token_id)
    pos = carry["pos"] + run.astype(jnp.int32)
    remaining = carry["remaining"] - run.astype(jnp.int32)
    done = step_done(run, tok, remaining, pos, Smax, cfg)
    new_carry = {
        **carry,
        "cache": cache,
        "last_tok": jnp.where(run, tok, carry["last_tok"]),
        "pos": pos,
        "active": carry["active"] & ~done,
        "remaining": remaining,
    }
    counts = jnp.stack(
        (jnp.ones((), bool),) + sampling.tier(*knobs)
    ).astype(jnp.int32)
    return new_carry, tok, run, counts


def first_block(toks, plens, Bk: int) -> Dict[str, jnp.ndarray]:
    """The first block in hand of an admission under cfg.gen_block, from
    the group's right-padded prompts `toks` [G, Sb]: the prompt's tail
    past its last whole block (decided, and not emitted) followed by
    undecided positions. The slot starts at pos = plens - tail."""
    tail = plens % Bk
    at = (plens - tail)[:, None] + jnp.arange(Bk)[None, :]
    known = jnp.arange(Bk)[None, :] < tail[:, None]
    tok = jnp.take_along_axis(toks, jnp.clip(at, 0, toks.shape[1] - 1), axis=1)
    return {"blk_tok": jnp.where(known, tok, 0), "blk_known": known,
            "blk_skip": tail.astype(jnp.int32)}


def transfer(known, conf, k: int, rule: str, threshold: Optional[float]):
    """Which undecided positions a denoising pass decides: [B, Bk] bool.
    `k` of them (all that are left if fewer): the leftmost
    ("sequential"), or those of highest confidence `conf` [B, Bk] with
    ties to the left, and every one above `threshold` when at least k
    are ("low_confidence")."""
    with jax.named_scope("diff/transfer"):
        Bk = known.shape[1]
        if rule == "sequential":  # rank among the undecided, from the left
            order = jnp.where(known, Bk, jnp.cumsum(~known, axis=1) - 1)
        else:
            # rank among the undecided by confidence, highest first; a
            # stable sort keeps equal confidences in position order
            by_conf = jnp.argsort(jnp.where(known, jnp.inf, -conf), axis=1,
                                  stable=True)
            order = jnp.argsort(by_conf, axis=1, stable=True)
            order = jnp.where(known, Bk, order)
        take = order < k
        if rule != "sequential" and threshold is not None:
            above = ~known & (conf > threshold)
            many = jnp.sum(above, axis=1, keepdims=True) >= k
            take = jnp.where(many, above, take)
        return take & ~known


# A pass scores the slots that are live and hold an undecided position,
# and of all B slots at most this many unless more need it: the head's
# weights are read once whatever the rows, so up to here a pass pays for
# them alone, and past it for float32 logits [rows, V] written and read
# back. One rung, read off the chip (tools/probe_block_head.py at D 2048,
# V 151936, the engine's own chunk; the table is in CHANGES.md, PR 53):
# greedy, the head and what reads it cost 0.83 ms at 4 slots, 1.4 % more
# at 8, 3.3 % at 16, 5.4 % at 32 and 67 % more over all 64; a pass that
# draws pays 0.02 ms of Gumbel noise for every slot scored besides (9 %
# more at 8 than at 4, 27 % at 16). So the most slots that stay within a
# tenth of the fewest's in both tiers, which is also the most requests
# the REST executor's threads hold.
SCORED_SLOTS = 8


def block_step(
    carry: State, hidden: jnp.ndarray, cache, Smax: int, cfg: ModelConfig,
    head: Callable[[jnp.ndarray], jnp.ndarray],
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """decode_step for a model that generates by diffusion over blocks:
    all of a pass that is not the model call. `hidden` [B, Bk, D] are
    the block's own positions as the layers left them and `head` scores
    rows of them ([N, D] -> logits [N, V]); `cache` is what the model
    call returned (the block's rows written for the rows that commit,
    `committing`, and for no other). A running row whose block holds an undecided
    position takes x0 = the sampled token of each (the mask id
    excluded; greedy: the argmax) and decides some (`transfer`); a row
    whose block is decided commits: its tokens past the prompt's tail
    are emitted, cut after the first EOS and at the budget, pos + Bk,
    and the next block starts undecided. Only the first kind of row
    reads its scores, so the head, the sampler and the confidence run
    over those slots alone, moved to the front: over none in a pass
    where no slot needs them, over SCORED_SLOTS while so many hold them
    all, over every slot otherwise (`lax.switch` on their count: the
    logits exist inside a branch only).

    Returns (carry, toks [B, Bk], valid [B, Bk], counts [7] int32:
    decode_step's three, then the slots that ran this pass, those of
    them that committed, the tokens emitted, the rows the head
    scored)."""
    Bk = cfg.gen_block
    B, _, D = hidden.shape
    run = carry["active"]
    commit = committing(carry)
    need = run & ~commit
    known, pos = carry["blk_known"], carry["pos"]
    knobs = sampling.live_knobs(
        need, carry["temp"], carry["top_k"], carry["top_p"])

    def scored(n: int):
        """(x0, conf) [B, Bk] with the first n slots that need scores
        scored and the others 0, which nothing reads."""
        if not n:
            return jnp.zeros((B, Bk), jnp.int32), jnp.zeros((B, Bk))
        slots = jnp.argsort(~need, stable=True)[:n]
        of = (lambda a: a[slots]) if n < B else (lambda a: a)
        with jax.named_scope("diff/confidence"):
            logits = head(of(hidden).reshape(-1, D))
            logits = jnp.where(
                jnp.arange(logits.shape[-1]) == cfg.mask_token_id, -jnp.inf,
                logits)
            at = of(pos)[:, None] + jnp.arange(Bk)[None, :]  # absolute
            keys = step_key(jnp.repeat(of(carry["seeds"]), Bk),
                            at.reshape(-1) - 1)
            x0 = sampling.sample_per_row(
                logits, keys, *(jnp.repeat(of(kn), Bk) for kn in knobs))
            conf = jnp.exp(
                jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1)).reshape(n, Bk)
            x0 = x0.reshape(n, Bk)
        if n == B:
            return x0, conf
        return (jnp.zeros((B, Bk), x0.dtype).at[slots].set(x0),
                jnp.zeros((B, Bk), conf.dtype).at[slots].set(conf))

    sizes = sorted({0, min(SCORED_SLOTS, B), B})
    which = jnp.sum(jnp.sum(need) > jnp.asarray(sizes[:-1]))
    x0, conf = jax.lax.switch(
        which, [functools.partial(scored, n) for n in sizes])
    take = transfer(known, conf, Bk // cfg.denoise_steps, cfg.remask,
                    cfg.denoise_threshold) & need[:, None]
    tok = jnp.where(take, x0, carry["blk_tok"])
    # the commit: tokens past the prompt's tail, up to the budget and to
    # the first EOS among them
    i = jnp.arange(Bk)[None, :]
    out = commit[:, None] & (i >= carry["blk_skip"][:, None]) \
        & (i - carry["blk_skip"][:, None] < carry["remaining"][:, None])
    ends = out & (tok == cfg.eos_token_id)
    valid = out & (jnp.cumsum(ends, axis=1) - ends == 0)
    n_out = jnp.sum(valid, axis=1, dtype=jnp.int32)
    pos = pos + Bk * commit.astype(jnp.int32)
    remaining = carry["remaining"] - n_out
    done = commit & (
        jnp.any(valid & ends, axis=1)
        | (remaining <= 0)
        | (pos + Bk > Smax)
    )
    new_carry = {
        **carry,
        "cache": cache,
        "pos": pos,
        "active": carry["active"] & ~done,
        "remaining": remaining,
        "blk_tok": jnp.where(commit[:, None], 0, tok),
        "blk_known": ~commit[:, None] & (known | take),
        "blk_skip": jnp.where(commit, 0, carry["blk_skip"]),
    }
    counts = jnp.stack(
        (jnp.ones((), bool),) + sampling.tier(*knobs)
        + (jnp.sum(run), jnp.sum(commit), jnp.sum(n_out),
           Bk * jnp.asarray(sizes)[which])
    ).astype(jnp.int32)
    return new_carry, jnp.where(valid, tok, cfg.pad_token_id), valid, counts


def committing(carry: State) -> jnp.ndarray:
    """[B] bool: the running rows whose block in hand is decided, so
    that this pass commits it (the model call writes their K/V rows)."""
    return carry["active"] & jnp.all(carry["blk_known"], axis=1)


def tokens_after(passes: int, tail: int, cfg: ModelConfig) -> int:
    """Tokens a row that is still running has emitted after `passes`
    passes, its prompt having left `tail` tokens in its first block: the
    host's count of what is in flight (exact while no threshold fires,
    a lower bound where one does)."""
    Bk, k = cfg.gen_block, cfg.gen_block // cfg.denoise_steps
    first = -(-(Bk - tail) // k) + 1
    if passes < first:
        return 0
    return Bk - tail + (passes - first) // (cfg.denoise_steps + 1) * Bk


def whole_blocks(passes: int, cap: int, cfg: ModelConfig) -> int:
    """`passes` rounded up to whole blocks' passes (denoise_steps + 1
    each), as many blocks as `cap` passes hold and at least one: a
    block's tokens come at its commit, so a chunk that ends between two
    commits only holds them back (a slot admitted at a chunk's boundary
    commits its blocks at later boundaries)."""
    per_block = cfg.denoise_steps + 1
    return per_block * max(1, min(-(-passes // per_block), cap // per_block))


def decode_chunk(
    step_model: Callable[[State], tuple], state: State, n_steps: int,
    Smax: int, cfg: ModelConfig,
    head: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`n_steps` decode steps over every slot in one lax.scan.
    `step_model(carry)` is all that differs between paths: it runs the
    model on the carry's last tokens and returns (logits, cache), and
    after them an int32 vector of whatever else the path counts a step
    (what attention read of the slab, what routing did). Returns (state,
    toks [K, B], valid [K, B], counts): valid holds where a row emitted
    a token (an autoregressive row's column is a True-prefix: rows stop
    and stay stopped), counts the steps' counts summed, decode_step's
    three and then the path's. Under cfg.gen_block a step is a pass
    (block_step) over the carry's block in hand: step_model returns the
    block's hidden rows [B, Bk, D] in the logits' place and `head`
    scores rows of them; toks and valid are
    [K, B, Bk], and a column holds tokens at its commit passes only."""
    one_step = functools.partial(block_step, head=head) if cfg.gen_block \
        else decode_step

    def step(carry, _):
        logits, cache, *more = step_model(carry)
        carry, tok, run, counts = one_step(carry, logits, cache, Smax, cfg)
        return carry, (tok, run, jnp.concatenate([counts, *more]))

    state, (toks, valid, counts) = jax.lax.scan(
        step, state, None, length=n_steps)
    return state, toks, valid, jnp.sum(counts, axis=0)
