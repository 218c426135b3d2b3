"""The sampler does what its live rows ask (models/sampling.py `tier`):
an all-greedy batch is an argmax whatever top_k / top_p its rows carry,
a batch with a sampling row divides and draws, and only a sampling row
that asks for top-k / top-p switches the full-vocabulary sort on.

(a) every row's token is what the parent's sampler gave it; (b) the tier
taken, from the predicates and from the compiled program; (c) through
the engine: the counters, a stale sampled slot, a sampled stream beside
greedy neighbours; (d) over REST with the benchmark's own body."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import sampling
from seldon_tpu.models.sampling import SamplingParams
from test_ttft_phases import _drain, _engine, _get, _post, rest_unit  # noqa: F401  (rest_unit: fixture)


def parent_sample_per_row(logits, keys, temperature, top_k, top_p):
    """sample_per_row as it stood before the tiers (commit 4021135),
    kept as the reference: the mask behind `any row carries a knob`, the
    divide and the Gumbel draw always."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    need_mask = jnp.any(top_k > 0) | jnp.any(top_p < 1.0)
    scaled = jax.lax.cond(
        need_mask,
        lambda s: sampling._mask_top_k_top_p(s, top_k, top_p),
        lambda s: s,
        scaled,
    )
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (V,), dtype=jnp.float32)
    )(keys)
    sampled = jnp.argmax(scaled + gumbel, axis=-1)
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)


def _rows(B, pattern):
    """[B] knob arrays from a repeating pattern of (temperature, top_k,
    top_p) rows."""
    rows = [pattern[i % len(pattern)] for i in range(B)]
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


# name -> (pattern of rows, (draws, masks) the batch should read)
BATCHES = {
    "all_greedy": ([(0.0, 0, 1.0)], (False, False)),
    # what REST and gRPC hand down for {"temperature": 0.0}: top_p 0.0
    "greedy_top_p_0": ([(0.0, 0, 0.0)], (False, False)),
    "greedy_top_p_0.9": ([(0.0, 0, 0.9)], (False, False)),
    "greedy_top_k_5": ([(0.0, 5, 1.0)], (False, False)),
    "greedy_mixed_knobs": ([(0.0, 5, 0.9), (0.0, 0, 0.0), (0.0, 0, 1.0)],
                           (False, False)),
    "sampled_no_knobs": ([(0.8, 0, 1.0), (1.3, 0, 1.0)], (True, False)),
    "sampled_top_k": ([(0.8, 5, 1.0)], (True, True)),
    "sampled_top_p": ([(0.8, 0, 0.9), (1.0, 0, 0.5)], (True, True)),
    "sampled_top_k_top_p": ([(0.7, 40, 0.95)], (True, True)),
    "sampled_top_p_0": ([(0.8, 0, 0.0)], (True, True)),
    "mixed_greedy_and_sampled": ([(0.0, 0, 1.0), (0.8, 0, 1.0)],
                                 (True, False)),
    # greedy rows carry knobs, the sampling rows none: the parent masked
    # (an identity for the rows that sample), the tiers do not
    "mixed_greedy_knobs_sampled_plain": (
        [(0.0, 0, 0.0), (0.9, 0, 1.0), (0.0, 5, 0.9)], (True, False)),
    "mixed_one_sampled_row_with_top_k": (
        [(0.0, 0, 1.0)] * 7 + [(0.8, 3, 1.0)], (True, True)),
    "mixed_sampled_with_and_without": (
        [(0.8, 0, 1.0), (0.8, 50, 0.9), (0.0, 0, 0.0), (1.1, 0, 0.0)],
        (True, True)),
}
SHAPES = {"8x1024": (8, 1024), "64x32000": (64, 32000)}


def _logits_keys(B, V, seed):
    k1, k2 = jax.random.split(jax.random.key(seed))
    logits = 3.0 * jax.random.normal(k1, (B, V), jnp.float32)
    return logits, jax.random.split(k2, B)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_every_row_gets_the_parents_token(batch, shape):
    B, V = SHAPES[shape]
    knobs = _rows(B, BATCHES[batch][0])
    new = jax.jit(sampling.sample_per_row)
    old = jax.jit(parent_sample_per_row)
    for seed in (0, 1):
        logits, keys = _logits_keys(B, V, seed + 10 * len(batch))
        got, want = new(logits, keys, *knobs), old(logits, keys, *knobs)
        assert got.dtype == jnp.int32 and got.shape == (B,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        greedy = np.asarray(knobs[0]) <= 0
        np.testing.assert_array_equal(
            np.asarray(got)[greedy],
            np.asarray(jnp.argmax(logits, -1))[greedy])


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_tier_is_what_the_rows_that_sample_ask_for(batch):
    pattern, want = BATCHES[batch]
    draws, masks = sampling.tier(*_rows(8, pattern))
    assert (bool(draws), bool(masks)) == want


def test_rows_that_are_not_running_ask_for_nothing():
    """A freed slot keeps its last request's knobs: masked by
    live_knobs, it holds the batch on no tier."""
    t, k, p = _rows(4, [(0.8, 5, 0.9)])
    assert tuple(map(bool, sampling.tier(t, k, p))) == (True, True)
    run = jnp.asarray([False, False, False, False])
    assert tuple(map(bool, sampling.tier(
        *sampling.live_knobs(run, t, k, p)))) == (False, False)
    run = jnp.asarray([False, True, False, False])
    lt, lk, lp = sampling.live_knobs(run, t, k, p)
    assert lt.tolist() == pytest.approx([0.0, 0.8, 0.0, 0.0])
    assert lk.tolist() == [0, 5, 0, 0]
    assert lp.tolist() == pytest.approx([1.0, 0.9, 1.0, 1.0])
    assert (lt.dtype, lk.dtype, lp.dtype) == (t.dtype, k.dtype, p.dtype)


def _computations(hlo):
    """{name: body} of a compiled module's text, and the entry's name."""
    out, entry, name = {}, None, None
    for ln in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", ln)
        if m and not ln.startswith(" "):
            name = m.group(2)
            out[name] = []
            entry = name if m.group(1) else entry
        elif name is not None:
            out[name].append(ln)
    return {k: "\n".join(v) for k, v in out.items()}, entry


def test_compiled_sampler_keeps_sort_and_draw_behind_its_conditionals():
    """In the optimised program the Gumbel draw sits inside the outer
    conditional's branch and the sort inside the inner one's: what is
    above them is the argmax and the predicates."""
    B, V = 8, 1024
    logits, keys = _logits_keys(B, V, 0)
    hlo = jax.jit(sampling.sample_per_row).lower(
        logits, keys, *_rows(B, [(0.0, 0, 0.0)])).compile().as_text()
    comps, entry = _computations(hlo)
    assert entry is not None and " sort(" in hlo and "conditional(" in hlo

    def reachable(root, through_conditionals):
        seen, todo = set(), [root]
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for ln in comps[c].splitlines():
                if "conditional(" in ln and not through_conditionals:
                    continue
                todo += re.findall(r"%([\w.\-]+)", ln.split("=", 1)[-1])
        return "\n".join(comps[c] for c in seen)
    always = reachable(entry, through_conditionals=False)
    assert "conditional(" in always
    assert " sort(" not in always and "rng-bit-generator" not in always
    # the threefry draw is shifts and xors over u32[B, V]-sized operands
    assert not re.search(r"u32\[%d,%d\]" % (B, V), always)
    everything = reachable(entry, through_conditionals=True)
    assert " sort(" in everything


# --- (c) through the engine -------------------------------------------------


def _tokens(q):
    items, err = _drain(q)
    assert err is None
    return [t for it in items for t in it["tokens"]]


ENGINES = {"dense": {}, "paged": dict(paged_kv=True, kv_block=8,
                                      prefix_block=8)}
GREEDY_SHAPES = {  # the knobs a greedy request may carry
    "plain": dict(temperature=0.0),
    "transport_zeros": dict(temperature=0.0, top_p=0.0),
    "openai_style": dict(temperature=0.0, top_p=0.95, top_k=40),
}


@pytest.mark.parametrize("knobs", sorted(GREEDY_SHAPES))
@pytest.mark.parametrize("mode", sorted(ENGINES))
def test_greedy_traffic_counts_only_greedy_steps(mode, knobs):
    eng = _engine(**ENGINES[mode])
    try:
        qs = [eng.submit([5 + i, 9, 11], SamplingParams(
            max_new_tokens=10, seed=i, **GREEDY_SHAPES[knobs]))
            for i in range(3)]
        outs = [_tokens(q) for q in qs]
    finally:
        eng.stop()
    snap = eng.stats.snapshot()
    assert all(len(o) >= 1 for o in outs)
    assert 0 < snap["sampler_steps"] <= snap["decode_steps"]
    assert snap["sampler_drawn_steps"] == snap["sampler_masked_steps"] == 0


@pytest.mark.parametrize("sampled,masked", [
    (dict(temperature=0.8), False),
    (dict(temperature=0.8, top_k=5), True),
    (dict(temperature=0.8, top_p=0.0), True),
], ids=["no_knobs", "top_k", "top_p_0"])
@pytest.mark.parametrize("mode", sorted(ENGINES))
def test_a_freed_sampled_slot_does_not_hold_later_steps(mode, sampled, masked):
    """A short sampled request beside a long greedy one: the device
    counts a drawn step exactly while the sampled row runs (its first
    token comes from the admission, every later one from a decode step),
    though its freed slot keeps temperature and knobs to the end."""
    eng = _engine(**ENGINES[mode])
    try:
        short = eng.submit([3, 4, 5], SamplingParams(
            max_new_tokens=5, seed=7, **sampled))
        long = eng.submit([6, 7, 8], SamplingParams(
            temperature=0.0, top_p=0.0, max_new_tokens=30, seed=1))
        n_short, n_long = len(_tokens(short)), len(_tokens(long))
        # and a later all-greedy wave, after the sampled slot was freed
        again = eng.submit([9, 10], SamplingParams(
            temperature=0.0, max_new_tokens=6))
        _tokens(again)
    finally:
        eng.stop()
    snap = eng.stats.snapshot()
    assert n_long > n_short
    assert snap["sampler_drawn_steps"] == n_short - 1
    assert snap["sampler_masked_steps"] == (n_short - 1 if masked else 0)
    assert snap["sampler_steps"] - snap["sampler_drawn_steps"] >= n_long - n_short


@pytest.mark.parametrize("mode", sorted(ENGINES))
def test_sampled_stream_is_the_same_beside_greedy_neighbours(mode):
    sp = SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                        max_new_tokens=12, seed=42)
    prompt = [11, 12, 13, 14]
    eng = _engine(**ENGINES[mode])
    try:
        alone = _tokens(eng.submit(prompt, sp))
    finally:
        eng.stop()
    eng = _engine(**ENGINES[mode])
    try:
        others = [eng.submit([20 + i, 3], SamplingParams(
            temperature=0.0, top_p=0.0, max_new_tokens=16)) for i in range(2)]
        shared = _tokens(eng.submit(prompt, sp))
        for q in others:
            _tokens(q)
    finally:
        eng.stop()
    assert shared == alone and len(alone) >= 2


# --- (d) over REST, with the benchmark's own body ---------------------------


def test_the_benchmarks_body_over_rest_never_masks(rest_unit):
    """benchmark/client.py's request: temperature 0.0 and no top_p, which
    the transport hands down as top_p 0.0. The sampler reads it as what
    it is, a greedy request."""
    srv, url, records = rest_unit
    before = srv.engine.stats.snapshot()
    raw, _ = _post(url + "/generate_stream", {
        "prompt_token_ids": [5, 6, 7, 8], "max_new_tokens": 9,
        "temperature": 0.0})
    chunks = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    assert sum(len(c["token_ids"]) for c in chunks) >= 2
    snap = srv.engine.stats.snapshot()
    assert snap["sampler_steps"] > before["sampler_steps"]
    assert snap["sampler_drawn_steps"] == before["sampler_drawn_steps"]
    assert snap["sampler_masked_steps"] == before["sampler_masked_steps"]
    text = _get(url + "/metrics")
    tiers = {m.group(1): float(m.group(2)) for m in re.finditer(
        r'jaxserver_sampler_steps_total\{[^}]*tier="(\w+)"[^}]*\} (\S+)',
        text)}
    assert set(tiers) == {"greedy", "drawn", "masked"}
    assert tiers["greedy"] == snap["sampler_steps"] - snap["sampler_drawn_steps"]
    assert tiers["masked"] == snap["sampler_masked_steps"]
    assert tiers["drawn"] == (snap["sampler_drawn_steps"]
                              - snap["sampler_masked_steps"])
    # the access line carries the running totals as the request ended
    # (a chunk dispatched ahead of its end may still add steps after it)
    row = json.loads(records[-1].getMessage()[len("request "):])
    assert before["sampler_steps"] < row["sampler_steps"] <= snap["sampler_steps"]
    assert row["sampler_drawn_steps"] == snap["sampler_drawn_steps"]
    assert row["sampler_masked_steps"] == snap["sampler_masked_steps"]


def test_the_attention_counters_ride_beside_the_samplers(rest_unit):
    """What decode attention read of the slab (transformer.decode_kv_counts):
    off a TPU the einsums score every slot's whole window, so every step
    reads all the slab holds, slots x window x layers; the two counters
    are on /metrics and on the access line like the sampler's."""
    srv, url, records = rest_unit
    before = srv.engine.stats.snapshot()
    _post(url + "/generate", {"prompt_token_ids": [9, 8, 7],
                              "max_new_tokens": 6, "temperature": 0.0})
    snap = srv.engine.stats.snapshot()
    steps = snap["sampler_steps"] - before["sampler_steps"]
    cfg = srv.engine.cfg
    assert steps > 0
    assert snap["attn_kv_tokens_held"] - before["attn_kv_tokens_held"] \
        == steps * cfg.n_layers * 4 * 64
    assert snap["attn_kv_tokens_read"] == snap["attn_kv_tokens_held"]
    text = _get(url + "/metrics")
    for name in ("attn_kv_tokens_read", "attn_kv_tokens_held"):
        assert float(re.search(
            r"^jaxserver_%s(?:\{[^}]*\})? (\S+)$" % name, text,
            re.MULTILINE).group(1)) == snap[name]
    row = json.loads(records[-1].getMessage()[len("request "):])
    assert 0 < row["attn_kv_tokens_read"] == row["attn_kv_tokens_held"] \
        <= snap["attn_kv_tokens_held"]
