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
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_tpu.models import sampling
from seldon_tpu.models.config import ModelConfig

State = Dict[str, Any]


def fresh(cache: Any, batch: int) -> State:
    """No slot holds a request."""
    return {
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
    finals=None, temps, top_ks, top_ps, seeds, max_news,
) -> State:
    """Write an admission into the slot state: by index (`slots` [G];
    rows that pad a group repeat a real row's slot and data, so the
    duplicate writes are well-defined) or, for a program that spans
    every slot, where `mask` [B] holds (the other rows keep every field
    bit for bit). A row decodes from here unless its first token ended
    it (`done`) or its prompt is not whole yet (`finals` False: the row
    deposited KV only and its sampled token is discarded)."""
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
        "remaining": put("remaining", max_news - 1),
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


def decode_chunk(
    step_model: Callable[[State], tuple], state: State, n_steps: int,
    Smax: int, cfg: ModelConfig,
) -> Tuple[State, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`n_steps` decode steps over every slot in one lax.scan.
    `step_model(carry)` is all that differs between paths: it runs the
    model on the carry's last tokens and returns (logits, cache), and
    after them an int32 vector of whatever else the path counts a step
    (what attention read of the slab, what routing did). Returns (state,
    toks [K, B], valid [K, B], counts): valid is a True-prefix per
    column, counts the steps' counts summed, decode_step's three and
    then the path's."""
    def step(carry, _):
        logits, cache, *more = step_model(carry)
        carry, tok, run, counts = decode_step(carry, logits, cache, Smax, cfg)
        return carry, (tok, run, jnp.concatenate([counts, *more]))

    state, (toks, valid, counts) = jax.lax.scan(
        step, state, None, length=n_steps)
    return state, toks, valid, jnp.sum(counts, axis=0)
