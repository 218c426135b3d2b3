"""Generation by diffusion over blocks (ModelConfig.gen_block) on the CPU
at `tiny-sdar` size: the engine's completions against the benchmark's
plain reference of the whole procedure (benchmark/families/sdar.py:
generate), a pass through the cache against the reference's full
forward, the teacher-forced form the harness's parity uses against the
procedure it stands for, what a denoising pass may not touch, and what
the opt-in paths do with such a model (refuse, by name).

Tolerances. The engine is run in float32 here, as the reference is: a
pass through prefill and cache then differs from the reference's full
forward by float32 rounding in another order of summation, ~1e-5 on
logits of magnitude ~1 (LOGIT_ATOL is ten times that). A pass computed
in bfloat16 differs by ~1e-2, a thousand times the tolerance: the slip
the comparison has to catch. Tokens are compared exactly: at float32 the
argmax of 256 seeded logits does not sit on a tie.
"""

import dataclasses
import functools
import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_tpu.models import slot
from seldon_tpu.models import transformer as T
from seldon_tpu.models.config import ModelConfig, get_config
from seldon_tpu.servers.engine import (
    DIFF_COUNTERS,
    EngineConfig,
    InferenceEngine,
    SamplingParams,
    chunk_counter_names,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_ATOL = 1e-4
BK = 4


@pytest.fixture(scope="module")
def fam():
    spec = importlib.util.spec_from_file_location(
        "family_sdar", os.path.join(ROOT, "benchmark", "families", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def file_keys(cfg: ModelConfig) -> dict:
    """A program config under the key names a configuration file of the
    sdar family has."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "intermediate_size": cfg.d_ff,
        "max_position_embeddings": cfg.max_seq_len,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "moe_intermediate_size": cfg.expert_width,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "norm_topk_prob": True, "mlp_only_layers": [],
        "decoder_sparse_step": 1, "tie_word_embeddings": False,
        "attention_bias": False,
        "assumed": {"qk_norm": cfg.qk_norm, "block_length": cfg.gen_block,
                    "denoise_steps": cfg.denoise_steps, "remask": cfg.remask,
                    "denoise_threshold": cfg.denoise_threshold,
                    "mask_token_id": cfg.mask_token_id},
        "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16"},
    }


def tiny(**kw) -> ModelConfig:
    return get_config("tiny-sdar", dtype="float32", **kw)


PARAMS = {}


def params_of(cfg):
    """One seeded tree for every test (the procedure's fields do not
    enter init_params)."""
    if "p" not in PARAMS:
        PARAMS["p"] = T.init_params(tiny(), jax.random.key(0))
    return PARAMS["p"]


def prompt(n: int, seed: int = 0):
    rng = np.random.RandomState(seed + 31 * n)
    return [int(t) for t in rng.randint(2, 250, size=n)]


def _serve(cfg):
    # one bucket and one chunk length: two programs to compile
    eng = InferenceEngine(params_of(cfg), cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(32,), decode_chunk=4,
        adaptive_chunk=False))
    eng.start()
    return eng


def collect(q):
    toks, items = [], []
    while (item := q.get(timeout=120)) is not None:
        assert "error" not in item, item
        toks += item["tokens"]
        items.append(item)
    return toks, items


@pytest.fixture(scope="module")
def sequential():
    cfg = tiny()
    eng = _serve(cfg)
    yield eng, cfg
    eng.stop()


# the rule's own engines: the confidence-ordered rule, with an EOS that
# its stream reaches inside a block (EOS_CASE), and with a threshold low
# enough to fire on seeded weights (the largest softmax value of 256
# near-uniform logits is ~0.01)
EOS_CASE = (prompt(9, 5), 13)


@pytest.fixture(scope="module")
def low_confidence(fam):
    cfg = tiny(remask="low_confidence")
    p, n = EOS_CASE
    free = fam.generate(params_of(cfg), p, n, file_keys(cfg))
    # the second token of the second block: the cut falls inside a block
    cfg = dataclasses.replace(cfg, eos_token_id=free[BK - 9 % BK + 1])
    eng = _serve(cfg)
    yield eng, cfg
    eng.stop()


@pytest.fixture(scope="module")
def thresholded():
    cfg = tiny(remask="low_confidence", denoise_threshold=0.006)
    eng = _serve(cfg)
    yield eng, cfg
    eng.stop()


# -- the engine against the whole procedure ----------------------------------

# (prompt length, max_new): every tail 0-3 past a whole block, prompts
# that hold no whole block, budgets off the block
CASES = [(8, 12), (9, 5), (10, 13), (11, 2), (1, 5), (2, 13), (3, 2),
         (16, 12), (21, 13)]


@pytest.mark.parametrize("plen,n_new", CASES)
def test_engine_equals_the_reference_procedure(sequential, fam, plen, n_new):
    eng, cfg = sequential
    p = prompt(plen)
    toks, items = collect(eng.submit(
        p, SamplingParams(max_new_tokens=n_new, temperature=0.0)))
    want = fam.generate(params_of(cfg), p, n_new, file_keys(cfg),
                        eos=cfg.eos_token_id)
    assert toks == want and len(want) == n_new
    # tokens come at commits: the first item carries the first block's,
    # with the request's TTFT and its phases
    assert "ttft_ms" in items[0] and len(items[0]["tokens"]) >= 1
    assert items[0]["timings"]["first_token_held_ms"] is not None


def test_requests_admitted_together_and_staggered(sequential, fam):
    """Five requests over four slots: three admitted in one group, one
    while they decode, one into a reused slot; each completion is the
    reference's, so no request saw another's block or its KV."""
    eng, cfg = sequential
    ps = [prompt(n, seed=3) for n in (5, 12, 18, 7, 10)]
    sp = SamplingParams(max_new_tokens=11, temperature=0.0)
    queues = [eng.submit(p, sp) for p in ps[:3]]
    first, _ = collect(queues[0])
    queues += [eng.submit(p, sp) for p in ps[3:]]
    got = [first] + [collect(q)[0] for q in queues[1:]]
    for p, toks in zip(ps, got):
        assert toks == fam.generate(params_of(cfg), p, 11, file_keys(cfg),
                                    eos=cfg.eos_token_id)
    snap = eng.stats.snapshot()
    assert snap["diff_tokens_out"] == snap["tokens_out"]
    assert 0 < snap["diff_commit_passes"] < snap["diff_slot_passes"]


@pytest.mark.parametrize("plen,n_new", [(8, 12), (10, 9), (3, 7)])
def test_the_confidence_ordered_rule(low_confidence, fam, plen, n_new):
    eng, cfg = low_confidence
    p = prompt(plen, seed=1)
    toks, _ = collect(eng.submit(
        p, SamplingParams(max_new_tokens=n_new, temperature=0.0)))
    assert toks == fam.generate(params_of(cfg), p, n_new, file_keys(cfg),
                                eos=cfg.eos_token_id)


def test_an_eos_inside_a_block_cuts_the_commit(low_confidence, fam):
    eng, cfg = low_confidence
    p, n = EOS_CASE
    toks, _ = collect(eng.submit(
        p, SamplingParams(max_new_tokens=n, temperature=0.0)))
    want = fam.generate(params_of(cfg), p, n, file_keys(cfg),
                        eos=cfg.eos_token_id)
    assert toks == want and toks[-1] == cfg.eos_token_id
    assert len(toks) < n and (len(p) + len(toks)) % BK != 0


@pytest.mark.parametrize("plen,n_new", [(8, 12), (9, 10), (2, 9)])
def test_a_threshold_that_fires_decides_more_a_pass(thresholded, fam, plen,
                                                    n_new):
    eng, cfg = thresholded
    p = prompt(plen, seed=2)
    before = eng.stats.snapshot()
    toks, _ = collect(eng.submit(
        p, SamplingParams(max_new_tokens=n_new, temperature=0.0)))
    trace = []
    assert toks == fam.generate(params_of(cfg), p, n_new, file_keys(cfg),
                                eos=cfg.eos_token_id, trace=trace)
    # it fired: some block took fewer denoising passes than the schedule's
    blocks = {start for start, _, _ in trace}
    assert len(trace) < cfg.denoise_steps * len(blocks)
    after = eng.stats.snapshot()
    assert after["diff_slot_passes"] - before["diff_slot_passes"] >= \
        len(trace) + len(blocks) - 1


# -- a pass through the cache against the reference's full forward -----------

def _admitted(cfg, p, slots=4, window=64, bucket=32):
    """The slot state after one admission of prompt `p` into slot 1."""
    state = slot.fresh(T.init_cache(cfg, slots, window), slots, cfg.gen_block)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(p)] = p
    one = lambda v, dt: jnp.asarray([v], dt)
    state, _, _ = InferenceEngine._admit_impl(
        params_of(cfg), state, jnp.asarray(toks), one(len(p), jnp.int32),
        one(0, jnp.uint32), one(0.0, jnp.float32), one(0, jnp.int32),
        one(1.0, jnp.float32), one(32, jnp.int32), one(1, jnp.int32), cfg=cfg)
    return state


def _pass_logits(cfg, state, compute=None):
    live = state["active"]
    params = params_of(cfg) if compute is None else jax.tree.map(
        lambda a: a.astype(compute) if a.dtype == jnp.float32
        and a.ndim > 1 else a, params_of(cfg))
    if compute is not None:
        cfg = dataclasses.replace(cfg, dtype="bfloat16")
    hidden, cache, routing = T.decode_block(
        params, state["blk_tok"], state["blk_known"], state["pos"],
        state["cache"], cfg, live, slot.committing(state))
    logits = T.block_logits(params, hidden.reshape(-1, cfg.d_model), cfg)
    return np.asarray(logits, np.float32).reshape(-1, BK, cfg.vocab_size), \
        cache, routing


def _reference_logits(fam, cfg, p, block, known):
    start = len(p) // BK * BK
    ids = jnp.asarray([p[:start] + block], jnp.int32)
    kn = jnp.asarray([[True] * start + known])
    fam._need_jax()  # the family imports JAX in the functions that compute
    with jax.default_matmul_precision("highest"):
        x = fam._hidden(params_of(cfg), ids, kn, file_keys(cfg), False)
        return np.asarray(fam._head(params_of(cfg), x[0, start:]))


@pytest.mark.parametrize("plen", [8, 9, 10, 11, 2])
def test_prefill_then_a_pass_agrees_with_the_full_forward(fam, plen):
    cfg, p = tiny(), prompt(plen, seed=4)
    state = _admitted(cfg, p)
    tail = plen % BK
    assert int(state["pos"][1]) == plen - tail
    assert state["blk_known"][1].tolist() == [i < tail for i in range(BK)]
    got, _, _ = _pass_logits(cfg, state)
    want = _reference_logits(fam, cfg, p, p[plen - tail:] + [0] * (BK - tail),
                             [i < tail for i in range(BK)])
    np.testing.assert_allclose(got[1], want, atol=LOGIT_ATOL, rtol=0)


def test_a_pass_in_a_lower_precision_fails_the_comparison(fam):
    cfg, p = tiny(), prompt(10, seed=4)
    state = _admitted(cfg, p)
    got, _, _ = _pass_logits(cfg, state, compute=jnp.bfloat16)
    want = _reference_logits(fam, cfg, p, p[8:] + [0, 0],
                             [True, True, False, False])
    assert np.abs(got[1] - want).max() > 10 * LOGIT_ATOL


def test_fresh_columns_masked_causally_fail_the_comparison(fam):
    """The block's positions see each other in both directions: with the
    causal mask an autoregressive step would put among them, the first
    position's logits move."""
    cfg, p = tiny(), prompt(10, seed=4)
    state = _admitted(cfg, p)
    real = T.gqa_attention_block

    def causal(q, ck, cv, kf, vf, mask_lt):
        out = [real(q[:, :i + 1], ck, cv, kf[:, :i + 1], vf[:, :i + 1],
                    mask_lt)[:, i:i + 1] for i in range(q.shape[1])]
        return jnp.concatenate(out, axis=1)

    with mock.patch.object(T, "gqa_attention_block", causal):
        got, _, _ = _pass_logits(cfg, state)
    want = _reference_logits(fam, cfg, p, p[8:] + [0, 0],
                             [True, True, False, False])
    assert np.abs(got[1, 0] - want[0]).max() > 10 * LOGIT_ATOL


def _one_pass(cfg, state):
    return InferenceEngine._chunk_impl(params_of(cfg), state, cfg=cfg,
                                       n_steps=1)


def test_a_denoising_pass_keeps_the_slab_and_the_decided_tokens(fam):
    """Two denoising passes, then the commit: the slab's bytes are the
    same before and after a denoising pass, a decided token never
    changes, and the commit writes the block's four rows of the live slot
    and nothing else. A commit pass skipped (the block's KV never
    written) moves the next block's logits off the reference's."""
    cfg, p = tiny(), prompt(8, seed=6)
    state = _admitted(cfg, p)
    slab = {k: np.asarray(v) for k, v in state["cache"].items()}
    decided = []
    for n_known in (2, 4):  # sequential: two positions a pass
        state, toks, valid, _, counts = _one_pass(cfg, state)
        assert not np.asarray(valid).any()
        for k, v in state["cache"].items():
            np.testing.assert_array_equal(np.asarray(v), slab[k])
        known = np.asarray(state["blk_known"][1])
        assert known.sum() == n_known
        now = np.asarray(state["blk_tok"][1])[known].tolist()
        assert now[:len(decided)] == decided
        decided = now
        names = chunk_counter_names(cfg)
        got = dict(zip(names, np.asarray(counts).tolist()))
        assert (got["diff_slot_passes"], got["diff_commit_passes"],
                got["attn_kv_rows_written"]) == (1, 0, 0)
    uncommitted = state
    state, toks, valid, _, counts = _one_pass(cfg, state)
    got = dict(zip(chunk_counter_names(cfg), np.asarray(counts).tolist()))
    assert (got["diff_commit_passes"], got["diff_tokens_out"],
            got["attn_kv_rows_written"]) == (1, BK, cfg.n_layers * BK)
    assert np.asarray(toks)[0, 1].tolist() == decided
    assert np.asarray(valid)[0, 1].all() and int(state["pos"][1]) == 12
    k_new = np.asarray(state["cache"]["k"])
    changed = np.argwhere((k_new != slab["k"]).any(axis=-1))
    assert {tuple(c[1:4]) for c in changed} == {(1, 0, t) for t in range(8, 12)}
    assert decided == fam.generate(params_of(cfg), p, BK, file_keys(cfg))
    # the next block's first pass, with and without the commit's rows
    want = _reference_logits(fam, cfg, p + decided, [0] * BK, [False] * BK)
    got, _, _ = _pass_logits(cfg, state)
    np.testing.assert_allclose(got[1], want, atol=LOGIT_ATOL, rtol=0)
    skipped = {**state, "cache": uncommitted["cache"]}
    got, _, _ = _pass_logits(cfg, skipped)
    assert np.abs(got[1] - want).max() > 10 * LOGIT_ATOL


def test_a_dead_slots_rows_route_nowhere():
    """One live slot of four: its four positions x top-2 reach the
    experts of each layer and the twelve dead rows reach none; routed as
    live, they would be counted (and on a TPU read) with it."""
    cfg, p = tiny(), prompt(8, seed=6)
    state = _admitted(cfg, p)
    _, _, routing = _pass_logits(cfg, state)
    layers, touched, assigned = np.asarray(routing).tolist()
    assert (layers, assigned) == (cfg.n_layers,
                                  cfg.n_layers * BK * cfg.n_experts_per_token)
    assert touched <= assigned
    everyone = {**state, "active": jnp.ones((4,), bool)}
    _, _, routing = _pass_logits(cfg, everyone)
    assert int(routing[2]) == 4 * assigned


# -- the head over the slots that will read their scores ---------------------

R = slot.SCORED_SLOTS
WIDE = R + 3  # slots: more than the rung holds, and more than one more
PASSES = 6


def _group(cfg, tails):
    """A slab of WIDE slots after one admission of len(tails) prompts,
    prompt i into slot i with `tails[i]` tokens past its last whole
    block; greedy and drawn rows alternate, every fourth row asks for
    top-k as well."""
    n = len(tails)
    state = slot.fresh(T.init_cache(cfg, WIDE, 64), WIDE, cfg.gen_block)
    toks = np.zeros((n, 32), np.int32)
    plens = [8 + 4 * (i % 2) + t for i, t in enumerate(tails)]
    for i, plen in enumerate(plens):
        toks[i, :plen] = prompt(plen, seed=9 + i)
    rows = np.arange(n)
    state, _, _ = InferenceEngine._admit_impl(
        params_of(cfg), state, jnp.asarray(toks),
        jnp.asarray(plens, jnp.int32), jnp.asarray(rows + 5, jnp.uint32),
        jnp.asarray(np.where(rows % 2, 0.8, 0.0), jnp.float32),
        jnp.asarray(np.where(rows % 4 == 3, 5, 0), jnp.int32),
        jnp.ones((n,), jnp.float32), jnp.full((n,), 20, jnp.int32),
        jnp.asarray(rows, jnp.int32), cfg=cfg)
    return state


@functools.lru_cache(maxsize=None)
def _passes(cfg, every_slot: bool):
    """One pass of the engine's chunk, jitted: as the program scores
    (the slots that need it, by the rung), or with the head, the sampler
    and the confidence over every slot whatever the pass holds."""
    def one(params, state):
        if not every_slot:
            return InferenceEngine._chunk_impl(params, state, cfg=cfg,
                                               n_steps=1)
        with mock.patch.object(
                jax.lax, "switch", lambda _, branches: branches[-1]()):
            return InferenceEngine._chunk_impl(params, state, cfg=cfg,
                                               n_steps=1)
    return jax.jit(one)


# slots that need scores in the first pass; a tail of 2 or 3 commits a
# pass before a tail of 0 or 1, so later passes hold slots of both kinds
TAILS = {
    "none": [0, 0],               # both commit in one pass: 2, 2, 0, ...
    "one": [2],                   # 1, 0, 1, 1, 0, ...
    "the_rung": [i % 4 for i in range(R)],
    "one_more": [i % 4 for i in range(R + 1)],
    "every_slot": [i % 4 for i in range(WIDE)],
}
RULES = {
    "sequential": {},
    "low_confidence": {"remask": "low_confidence", "denoise_threshold": 0.006},
}


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("case", TAILS)
def test_scoring_the_slots_that_need_it_changes_nothing(case, rule):
    """Pass by pass, the chunk whose head runs over the slots that hold
    an undecided position (none, SCORED_SLOTS of them or all, by their
    count) hands on what the chunk whose head runs over every slot does:
    tokens, valid, the whole carry, the counts; and diff_rows_scored is
    0, SCORED_SLOTS x Bk or slots x Bk by that count."""
    cfg = tiny(**RULES[rule])
    state = _group(cfg, TAILS[case])
    names = chunk_counter_names(cfg)
    needs = []
    for _ in range(PASSES):
        need = int(np.sum(np.asarray(state["active"])
                          & ~np.asarray(state["blk_known"]).all(axis=1)))
        needs.append(need)
        want = _passes(cfg, True)(params_of(cfg), state)
        got = _passes(cfg, False)(params_of(cfg), state)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
        counts = dict(zip(names, np.asarray(got[4]).tolist()))
        assert counts["diff_rows_scored"] == BK * (
            0 if need == 0 else R if need <= R else WIDE)
        state = got[0]
    assert needs[0] == len(TAILS[case])
    # a slot that commits and one that denoises share a pass
    assert case in ("none", "one") or any(
        0 < n < len(TAILS[case]) for n in needs)
    assert case not in ("none", "one") or 0 in needs
    # every request has emitted a block by now
    assert (np.asarray(state["remaining"])[:len(TAILS[case])] < 20).all()


# -- the teacher-forced form against the procedure ---------------------------

@pytest.mark.parametrize("plen,n_new", [(8, 12), (12, 7), (4, 5)])
def test_forward_logits_is_the_logits_each_token_was_decided_from(
        fam, plen, n_new):
    """What ties the harness's parity to the procedure: on generate's own
    `sequential` output, where the prompt ends on a block, the
    teacher-forced logits' argmax IS the generated token at every
    position (gap 0), as benchmark/reference.logit_gaps reads them."""
    cfg, p = tiny(), prompt(plen, seed=8)
    fk = file_keys(cfg)
    toks = fam.generate(params_of(cfg), p, n_new, fk)
    logits = fam.forward_logits(params_of(cfg), p + toks[:-1], fk)
    assert logits.shape == (plen + n_new - 1, cfg.vocab_size)
    rows = np.asarray(logits[plen - 1:])
    gaps = rows.max(axis=-1) - rows[np.arange(n_new), toks]
    assert (gaps == 0.0).all()


def test_the_harness_own_comparison_reads_gap_zero_on_the_procedure(fam):
    """benchmark/reference.logit_gaps, as the parity child calls it, on
    the reference procedure's own tokens: every gap is 0, and only the
    rows it slices are ever computed."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import reference
    finally:
        sys.path.pop(0)
    cfg, p = tiny(), prompt(12, seed=9)
    fk = file_keys(cfg)
    toks = fam.generate(params_of(cfg), p, 6, fk)
    gaps, _ = reference.logit_gaps(fam, params_of(cfg), fk, [(p, toks)])
    assert gaps == [0.0] * 6
    rows = fam.forward_logits(params_of(cfg), p + toks[:-1], fk)
    assert rows.shape == (17, cfg.vocab_size) and not rows._have
    assert rows[11:].shape == (6, cfg.vocab_size) and sorted(rows._have) == \
        list(range(11, 17))


@pytest.mark.parametrize("plen", [9, 10, 11])
def test_forward_logits_takes_a_prompt_that_ends_inside_a_block(fam, plen):
    cfg, p = tiny(), prompt(plen, seed=8)
    fk = file_keys(cfg)
    toks = fam.generate(params_of(cfg), p, 9, fk)
    rows = np.asarray(fam.forward_logits(
        params_of(cfg), p + toks[:-1], fk, prompt_len=plen)[plen - 1:])
    assert (rows.argmax(axis=-1) == np.asarray(toks)).all()


# -- configuration, refusals, counters ----------------------------------------

def test_gen_block_is_validated():
    with pytest.raises(AssertionError, match="full_attention layers only"):
        get_config("tiny-lfm2", gen_block=4, mask_token_id=255)
    with pytest.raises(AssertionError, match="multiple of denoise_steps"):
        get_config("tiny-sdar", denoise_steps=3)
    with pytest.raises(AssertionError, match="need gen_block"):
        get_config("tiny", denoise_steps=2)
    with pytest.raises(AssertionError, match="unknown remask"):
        get_config("tiny-sdar", remask="random")


@pytest.mark.parametrize("path,kw", [
    ("paged_kv", dict(paged_kv=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("chunked_prefill", dict(chunked_prefill=True)),
    ("spec_decode", dict(spec_decode=True, paged_kv=True)),
    ("heal", dict(heal=True)),
    ("tp > 1", dict(tp=2)),
])
def test_the_opt_in_engine_paths_refuse_gen_block_by_name(path, kw):
    cfg = tiny()
    with pytest.raises(ValueError) as e:
        InferenceEngine(params_of(cfg), cfg, EngineConfig(
            max_slots=2, max_seq_len=64, prompt_buckets=(32,), **kw))
    assert path in str(e.value) and "gen_block 4" in str(e.value)


def test_a_window_or_bucket_that_cuts_a_block_is_refused():
    cfg = tiny()
    with pytest.raises(ValueError, match=r"multiple of it; \[2\]"):
        InferenceEngine(params_of(cfg), cfg, EngineConfig(
            max_slots=2, max_seq_len=64, prompt_buckets=(2, 32)))


def test_the_passes_counters_sit_after_the_samplers():
    names = chunk_counter_names(get_config("tiny-sdar"))
    assert names[3:7] == DIFF_COUNTERS
    assert names[7:11] == ("attn_kv_tokens_read", "attn_kv_tokens_held",
                           "attn_kv_rows_written", "attn_kv_rows_slots")
    assert not set(DIFF_COUNTERS) & set(chunk_counter_names(get_config("tiny")))


def test_tokens_after_counts_what_is_in_flight():
    cfg = get_config("tiny-sdar")  # blocks of 4 in 2 + 1 passes
    assert [slot.tokens_after(n, 0, cfg) for n in range(8)] == \
        [0, 0, 0, 4, 4, 4, 8, 8]
    assert [slot.tokens_after(n, 3, cfg) for n in range(6)] == \
        [0, 0, 1, 1, 1, 5]  # one position left: one pass, then the commit
    assert [slot.tokens_after(n, 1, cfg) for n in range(7)] == \
        [0, 0, 0, 3, 3, 3, 7]


def test_the_low_rung_is_whole_blocks_of_passes():
    """A block's tokens come at its commit: whatever the rule of the
    host turn asks for (1, 2 or 4 passes), a chunk is whole blocks of
    denoise_steps + 1 passes, as many as the cap holds and at least one.
    On the chip the rule alone sat on its line for this model's pass and
    a run read a TTFT of 59 or 86 ms by which side it fell (PR 52)."""
    cfg = get_config("tiny-sdar")  # 2 + 1 passes a block
    assert [slot.whole_blocks(n, 4, cfg) for n in (1, 2, 4)] == [3, 3, 3]
    assert [slot.whole_blocks(n, 8, cfg) for n in (1, 2, 4, 8)] == [3, 3, 6, 6]
    assert slot.whole_blocks(1, 2, cfg) == 3  # at least one block


@pytest.mark.parametrize("step_ms,turn_ms", [(4.7, 1.8), (4.7, 2.1), (14.0, 1.9)])
def test_the_engine_sizes_its_rung_in_whole_blocks(step_ms, turn_ms):
    from test_chunk_steps import FULL, _Planted, _dispatched
    cfg = tiny()
    est = _Planted()
    eng = InferenceEngine(params_of(cfg), cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(32,)))
    eng._depth_est = est
    eng.start()
    seen = _dispatched(eng)
    try:
        est.plant(step_ms / 1e3, turn_ms / 1e3, FULL)
        toks, _ = collect(eng.submit(
            prompt(8), SamplingParams(max_new_tokens=12, temperature=0.0)))
        assert len(toks) == 12
        assert set(seen) == {3} and eng.chunk_sizes[0] == 3
        assert "decode/3" in eng.static_lattice()
    finally:
        eng.stop()
