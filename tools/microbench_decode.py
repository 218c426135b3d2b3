"""Microbenchmark: decode-chunk step time for weight/kv dtype combos.

Times ONE jitted decode chunk (the engine's `_chunk_impl` equivalent:
`decode_chunk` lax.scan steps over all slots) on the bench-1b serving
shape, isolating the HBM-bound hot loop from scheduler/host effects.
Usage: python tools/microbench_decode.py [--spec k] [combos...]
  combo = weights:kv[:attn] e.g. int8:bf16  int8:int8  bf16:bf16

``--spec k`` switches to the graftspec kernel pair: one paged verify
wave over k drafts (models/spec_decode.verify_wave, Sq = k + 1 query
rows) against the same wave at k = 0 — which IS a plain paged decode
step through the identical code path, so the ratio isolates the extra
width's cost. Prints the break-even emitted-tokens/wave (spec wins
when mean acceptance clears it) and the full-acceptance speedup bound.
``MB_DRAFT=<preset>`` additionally times the resident draft model's
proposal dispatch (models/spec_decode.draft_tokens); without it the
n-gram drafter's host cost (~0) is assumed.

``--roof`` adds graftroof's analytical prediction next to every
measured number (servers/cost_model.cost_of_key at this bench's exact
geometry, peaks resolved per platform env > table > microbench): the
predicted ms per decode step / per verify wave and the measured-over-
predicted ratio — the cost model's calibration check.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp

from seldon_tpu.models import get_config, init_params, transformer
from seldon_tpu.models.sampling import live_knobs, sample_per_row

import os
PRESET = os.environ.get("MB_PRESET", "bench-1b")
SLOTS = int(os.environ.get("MB_SLOTS", 160))
WINDOW = int(os.environ.get("MB_WINDOW", 257))  # prompt 128 + decode 128 + 1
CHUNK = 64


def act_for(weights: str) -> str:
    """MB_ACT mirrors BENCH_ACT/TUNE_ACT: int8 (the adopted W8A8
    serving default) unless reverted, and only when weights are int8 —
    shared by the microbench and tools/profile_decode so the profiler
    can never desynchronize from the benchmark it explains."""
    return os.environ.get("MB_ACT", "int8" if weights == "int8" else "bf16")


def chunk_impl(params, state, *, cfg, n_steps):

    def step(carry, _):
        run = carry["active"]
        logits, cache = transformer.decode_step(
            params, carry["last_tok"], carry["pos"], carry["cache"], cfg,
        )
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.key(s), p + 1)
        )(carry["seeds"], carry["pos"])
        tok = sample_per_row(
            logits, keys,
            *live_knobs(run, carry["temp"], carry["top_k"], carry["top_p"]),
        )
        tok = jnp.where(run, tok, cfg.pad_token_id)
        pos = carry["pos"] + run.astype(jnp.int32)
        new_carry = {
            **carry,
            "cache": cache,
            "last_tok": jnp.where(run, tok, carry["last_tok"]),
            "pos": pos,
        }
        return new_carry, tok

    state, toks = jax.lax.scan(step, state, None, length=n_steps)
    return state, toks


def bench(weights: str, kv: str, attn: str = "xla") -> float:
    cfg = get_config(PRESET, weight_dtype=weights, kv_cache_dtype=kv,
                     attn_impl=attn, act_dtype=act_for(weights))
    if weights == "int8":
        # Memory-aware: 8B geometry can't materialize bf16 then quantize.
        from seldon_tpu.models.quantize import init_params_int8

        params = init_params_int8(cfg, jax.random.key(0))
    else:
        params = init_params(cfg, jax.random.key(0))
    B = SLOTS
    state = {
        "cache": transformer.init_cache(cfg, B, WINDOW),
        "last_tok": jnp.ones((B,), jnp.int32),
        "pos": jnp.full((B,), 128, jnp.int32),
        "active": jnp.ones((B,), jnp.bool_),
        "temp": jnp.full((B,), 0.7, jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "top_p": jnp.ones((B,), jnp.float32),
        "seeds": jnp.arange(B, dtype=jnp.uint32),
    }
    fn = jax.jit(functools.partial(chunk_impl, cfg=cfg, n_steps=CHUNK),
                 donate_argnums=(1,))

    def one(state):
        # Reset pos each chain link so the window stays comparable.
        state = dict(state)
        state["pos"] = jnp.full((B,), 128, jnp.int32)
        state["active"] = jnp.ones((B,), jnp.bool_)
        state, toks = fn(params, state)
        return state

    from tools.timing import slope_time

    dt, _ = slope_time(one, state, k1=2, k2=6)
    ms_per_step = 1000.0 * dt / CHUNK
    toks_per_s = SLOTS * CHUNK / dt
    print(
        f"w={weights:5s} kv={kv:5s} act={cfg.act_dtype:5s} attn={attn:5s} "
        f"{ms_per_step:7.3f} ms/step  {toks_per_s:9.0f} tok/s",
        flush=True,
    )
    if ROOF:
        pred = _roof_predict_ms(("decode", CHUNK), cfg) / CHUNK
        print(
            f"  roof: predicted {pred:7.3f} ms/step  "
            f"measured/predicted {ms_per_step / pred:6.2f}x",
            flush=True,
        )
    return ms_per_step


def _roof_predict_ms(key, cfg) -> float:
    """Analytical roofline estimate of one dispatch of `key` at this
    microbench's geometry, against the platform peaks."""
    from seldon_tpu.servers import cost_model

    dev = jax.devices()[0]
    peaks = cost_model.resolve_peaks(
        getattr(dev, "device_kind", "") or dev.platform
    )
    flops, bytes_ = cost_model.cost_of_key(
        key, cfg, max_slots=SLOTS, max_seq_len=WINDOW, kv_block=64,
    )
    return cost_model.roofline_ms(flops, bytes_, peaks)


def bench_spec(k: int, weights: str, kv: str, attn: str = "xla") -> None:
    """graftspec kernel pair: verify wave at width k vs k = 0 (a plain
    paged decode step through the same code path)."""
    from seldon_tpu.models import spec_decode as spec_model

    cfg = get_config(PRESET, weight_dtype=weights, kv_cache_dtype=kv,
                     attn_impl=attn, act_dtype=act_for(weights))
    if weights == "int8":
        from seldon_tpu.models.quantize import init_params_int8

        params = init_params_int8(cfg, jax.random.key(0))
    else:
        params = init_params(cfg, jax.random.key(0))
    B = SLOTS
    block = 64
    nbs = -(-WINDOW // block)
    # Block 0 is the trash block; row i owns blocks [1 + i*nbs, ...).
    table = jnp.arange(1, B * nbs + 1, dtype=jnp.int32).reshape(B, nbs)
    wave = jnp.ones((B,), jnp.bool_)

    from tools.timing import slope_time

    # One pool for the whole pair: each width's jit donates the state
    # in and slope_time hands the final state to the next leg — the
    # idiomatic donation chain (every chain link resets pos/active, so
    # timings are width-comparable regardless of who ran before).
    state = {
        "cache": transformer.init_paged_cache(cfg, B * nbs + 1, block),
        "last_tok": jnp.ones((B,), jnp.int32),
        "pos": jnp.full((B,), 128, jnp.int32),
        "active": jnp.ones((B,), jnp.bool_),
        "remaining": jnp.full((B,), 64, jnp.int32),
        "temp": jnp.zeros((B,), jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "top_p": jnp.ones((B,), jnp.float32),
        "seeds": jnp.arange(B, dtype=jnp.uint32),
    }

    def time_width(kk: int, state: dict):
        drafts = jnp.ones((B, kk), jnp.int32)
        fn = jax.jit(functools.partial(spec_model.verify_wave, cfg=cfg),
                     donate_argnums=(1,))

        def one(st):
            st = dict(st, pos=jnp.full((B,), 128, jnp.int32),
                      remaining=jnp.full((B,), 64, jnp.int32),
                      active=jnp.ones((B,), jnp.bool_))
            st = fn(params, st, table, drafts, wave)[0]
            return st

        dt, state = slope_time(one, state, k1=2, k2=6)
        return 1000.0 * dt, state

    ms_plain, state = time_width(0, state)
    ms_verify, state = time_width(k, state)
    draft_ms = 0.0
    draft_preset = os.environ.get("MB_DRAFT", "")
    if draft_preset:
        dcfg = get_config(draft_preset, act_dtype="bf16")
        dparams = init_params(dcfg, jax.random.key(1))
        W = 64
        dfn = jax.jit(functools.partial(
            spec_model.draft_tokens, dparams, cfg=dcfg, k=k))
        window = jnp.ones((B, W), jnp.int32)
        wlens = jnp.full((B,), W, jnp.int32)
        dt, _ = slope_time(lambda s: (dfn(window, wlens), s)[1],
                           state, k1=2, k2=6)
        draft_ms = 1000.0 * dt
    wave_ms = ms_verify + draft_ms
    # Spec emits E tokens/wave; plain emits 1/dispatch. Break-even when
    # wave_ms / E == ms_plain.
    break_even = wave_ms / ms_plain
    speedup_full = (k + 1) * ms_plain / wave_ms
    print(
        f"w={weights:5s} kv={kv:5s} act={cfg.act_dtype:5s} spec k={k} "
        f"plain {ms_plain:7.3f} ms/step  verify {ms_verify:7.3f} ms/wave"
        + (f"  draft {draft_ms:7.3f} ms/wave" if draft_preset else "")
        + f"  break-even {break_even:.2f} tok/wave"
        f"  full-accept speedup {speedup_full:.2f}x",
        flush=True,
    )
    if ROOF:
        pred_plain = _roof_predict_ms(("decode", 1), cfg)
        pred_verify = _roof_predict_ms(("verify", k), cfg)
        print(
            f"  roof: predicted plain {pred_plain:7.3f} ms/step  "
            f"verify {pred_verify:7.3f} ms/wave  "
            f"measured/predicted {ms_plain / pred_plain:6.2f}x / "
            f"{ms_verify / pred_verify:6.2f}x",
            flush=True,
        )


ROOF = False

if __name__ == "__main__":
    args = sys.argv[1:]
    spec_k = 0
    if "--roof" in args:
        args.remove("--roof")
        ROOF = True
    if "--spec" in args:
        i = args.index("--spec")
        spec_k = int(args[i + 1])
        args = args[:i] + args[i + 2:]
    combos = args or ["int8:bf16", "int8:int8", "bf16:bf16", "bf16:int8"]
    for c in combos:
        parts = c.split(":")
        if spec_k:
            bench_spec(spec_k, *parts[:3])
        else:
            bench(*parts[:3])
