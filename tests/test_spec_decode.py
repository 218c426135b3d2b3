"""graftspec (models/spec_decode.py + engine._dispatch_spec): draft
k tokens, verify all k+1 positions in one wide wave, commit the
accepted prefix, roll the rest back — pinned against the plain engine.

The load-bearing claims, in test form:
 * output is BIT-IDENTICAL spec-on vs spec-off — greedy AND sampled,
   across paged / paged+chunked / prefix-warm modes, for bf16 and int8
   KV: verification is exact-match against the target's own
   sequentially-keyed samples, so speculation can never change a
   token, only the number of dispatches it took;
 * speculation genuinely COMPRESSES dispatches: with a perfect drafter
   the engine emits ~(k+1) tokens per verify wave, driving
   dispatches/token well under 1.0;
 * rollback is leak-free at every edge: rejection at position 0,
   full-k acceptance, acceptance crossing a kv_block boundary (the
   host-side block-table tail trim must unref exactly the dead decode
   blocks), and EOS landing mid-accepted-prefix (drafts that matched
   but fell after the terminal token count rejected);
 * the lattice stays CLOSED: static_lattice() grows exactly the
   ("verify", k) pow2 ladder (+ ("draft", k) with a resident draft
   model), warmup compiles it, and live traffic never retraces;
 * the sched ledger's acceptance accounting is conservation-exact:
   accepted + rejected == drafted, and every verify-wave cell is
   attributed useful-or-rejected with zero audit breaches;
 * spec_decode=False leaves the engine byte-identical to the seed
   build, and EngineConfig rejects unusable spec knob combinations.
"""

import dataclasses
import queue

import jax
import pytest

from _engine_fixture import PROMPT, live_config

from seldon_tpu.models import init_params
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=12)
SAMPLED = SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                         max_new_tokens=12, seed=7)

MIXED = [
    list(range(2, 26)),
    list(range(30, 33)),
    list(range(40, 57)),
    [5, 9],
]

# The spec engine rides the paged substrate (rollback is a block-table
# tail trim); kv_block=8 makes block-boundary crossings cheap to hit.
PAGED = dict(paged_kv=True, kv_block=8, prefix_block=8)
SPEC = dict(spec_decode=True, spec_k=4, **PAGED)


def _engine(cfg, start=True, **ekw):
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


def _want(cfg, prompt=PROMPT, sp=GREEDY, **ekw):
    """Spec-off reference output for one prompt under a given mode."""
    eng = _engine(cfg, **ekw)
    try:
        return eng.generate_blocking(prompt, sp)["token_ids"]
    finally:
        eng.stop()


def _collect(q, timeout=120):
    toks, err = [], None
    while True:
        item = q.get(timeout=timeout)
        if item is None:
            return toks, err
        if "error" in item:
            err = item
        else:
            toks.extend(item.get("tokens", []))


class _Oracle:
    """Perfect drafter: proposes the exact greedy continuation — every
    wave accepts full-k (until the budget/EOS terminal)."""

    uses_model = False

    def __init__(self, want):
        self._want = list(want)

    def draft(self, prompt, gen, k):
        i = len(gen)
        out = list(self._want[i:i + k])
        while len(out) < k:
            out.append(self._want[-1] if self._want else 0)
        return out


class _AntiOracle:
    """Adversarial drafter: always wrong — every wave rejects at
    position 0 and the engine degrades to one token per dispatch."""

    uses_model = False

    def __init__(self, want, vocab):
        self._want = list(want)
        self._vocab = vocab

    def draft(self, prompt, gen, k):
        i = len(gen)
        out = []
        for j in range(k):
            t = self._want[i + j] if i + j < len(self._want) else 0
            out.append((t + 1) % self._vocab)
        return out


# ---------------------------------------------------------------------------
# Bit-exactness: spec-on vs spec-off across modes and dtypes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", ["paged", "chunked", "prefix",
                                  "chunked+prefix"])
def test_spec_bit_identical_across_modes(kv_dtype, mode):
    """The acceptance gate's exactness criterion: greedy output under
    SPEC matches the spec-off engine token-for-token in every paged
    mode x KV dtype."""
    cfg = live_config(kv_cache_dtype=kv_dtype)
    extra = {}
    if "chunked" in mode:
        extra.update(chunked_prefill=True, prefill_chunk=8)
    if "prefix" in mode:
        extra.update(prefix_cache=True)
    want = _want(cfg, **PAGED, **extra)

    eng = _engine(cfg, **SPEC, **extra)
    try:
        if "prefix" in mode:
            # Cold admission seeds the trie; the warm resume is the
            # interesting path (spec waves over shared blocks).
            assert eng.generate_blocking(PROMPT, GREEDY)["token_ids"] \
                == want
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        if "prefix" in mode:
            assert eng.stats.snapshot()["zero_copy_admissions"] >= 1
    finally:
        eng.stop()
    assert got == want


def test_spec_sampled_bit_identical():
    """Exact-match verification is temperature-blind: per-row keys are
    position-derived, so sampled output is bit-identical too (this is
    what separates graftspec from rejection-sampling schemes)."""
    cfg = live_config()
    want = _want(cfg, sp=SAMPLED, **PAGED)
    eng = _engine(cfg, **SPEC)
    try:
        got = eng.generate_blocking(PROMPT, SAMPLED)["token_ids"]
    finally:
        eng.stop()
    assert got == want


def test_spec_mixed_burst_bit_identical():
    """A concurrent mixed-length burst: every row's stream matches its
    spec-off reference even as waves carry different per-row rewind
    depths."""
    cfg = live_config()
    wants = [_want(cfg, p, **PAGED) for p in MIXED]
    eng = _engine(cfg, **SPEC)
    try:
        qs = [eng.submit(p, GREEDY) for p in MIXED]
        gots = []
        for q in qs:
            toks, err = _collect(q)
            assert err is None, err
            gots.append(toks)
    finally:
        eng.stop()
    assert gots == wants


# ---------------------------------------------------------------------------
# Compression: dispatches/token < 1.0 with a good drafter
# ---------------------------------------------------------------------------


def test_spec_oracle_compresses_dispatches():
    """With a perfect drafter the engine emits k+1 tokens per verify
    wave: 12 decode tokens land in ~3 dispatches instead of 11 — the
    CPU-smoke form of the 2x TPU target (docs/benchmarking.md)."""
    cfg = live_config()
    want = _want(cfg, **PAGED)
    eng = _engine(cfg, start=False, **SPEC)
    eng._drafter = _Oracle(want)
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert got == want
    n_decoded = len(want) - 1  # first token comes from the admit
    assert snap["decode_dispatches"] < n_decoded, snap
    # Perfect acceptance: ceil(11 / (k+1)) = 3 waves for k=4.
    assert snap["decode_dispatches"] <= 3
    assert snap["decode_dispatches"] / snap["tokens_out"] < 1.0


# ---------------------------------------------------------------------------
# Rollback edge cases
# ---------------------------------------------------------------------------


def test_spec_rejection_at_position_zero_is_leak_free():
    """An always-wrong drafter rejects at position 0 every wave: the
    engine degrades to one token per dispatch, stays bit-exact, and
    the per-wave block growth + tail trim nets out to zero leaks."""
    cfg = live_config()
    want = _want(cfg, **PAGED)
    eng = _engine(cfg, start=False, **SPEC)
    eng._drafter = _AntiOracle(want, cfg.vocab_size)
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
        leaks = eng.debug_lifecycle_check()
    finally:
        eng.stop()
    assert got == want
    # Every wave rejected everything: one emitted token per dispatch.
    assert snap["decode_dispatches"] == len(want) - 1
    assert leaks == {}, leaks


def test_spec_full_k_acceptance_crosses_block_boundary():
    """Full-k waves march the write position straight across kv_block
    boundaries (24-token prompt + 12 generated crosses pos 32 with
    kv_block=8): the commit allocates blocks mid-wave and the
    allocator's refcount discipline stays exact."""
    cfg = live_config()
    want = _want(cfg, **PAGED)
    eng = _engine(cfg, start=False, **SPEC)
    eng._drafter = _Oracle(want)
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        leaks = eng.debug_lifecycle_check()
        pool = eng._allocator.snapshot()
    finally:
        eng.stop()
    assert got == want
    assert leaks == {}, leaks
    # Every block the request grew came back on completion.
    assert pool["free"] == pool["total"], pool


def test_spec_eos_mid_accepted_prefix():
    """EOS landing inside an accepted run terminates the row exactly
    there: drafts that matched but fell after the terminal token count
    rejected, and the stream matches the spec-off engine's EOS stop."""
    cfg = live_config()
    base = _want(cfg, **PAGED)
    # Re-point EOS at a token the greedy continuation actually emits,
    # mid-stream, so the terminal lands inside a wave.
    eos_cfg = dataclasses.replace(cfg, eos_token_id=int(base[5]))
    want = _want(eos_cfg, **PAGED)
    assert len(want) < len(base), "fixture must terminate early on EOS"
    eng = _engine(eos_cfg, start=False, **SPEC)
    eng._drafter = _Oracle(base)  # drafts continue PAST the terminal
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        leaks = eng.debug_lifecycle_check()
    finally:
        eng.stop()
    assert got == want
    assert leaks == {}, leaks


# ---------------------------------------------------------------------------
# Lattice containment + zero live retraces
# ---------------------------------------------------------------------------


def test_spec_lattice_declares_verify_ladder_and_never_retraces(
    monkeypatch,
):
    """static_lattice() grows exactly the pow2 verify ladder, warmup
    compiles it, and a full generation stays inside it (zero live
    retraces) — the compile-audit SPEC=1 leg's criterion."""
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    cfg = live_config()
    eng = _engine(cfg, start=False, **SPEC)
    static = set(eng.static_lattice())
    assert {"verify/1", "verify/2", "verify/4"} <= static
    assert not any(k.startswith("decode/") for k in static), (
        "spec replaces the decode family, not adds to it")
    assert not any(k.startswith("draft/") for k in static), (
        "n-gram drafting is host-side: no draft variants")
    eng.warmup()
    eng.start()
    try:
        eng.generate_blocking(PROMPT, GREEDY)
        comp = eng.debug_compile()
    finally:
        eng.stop()
    assert comp["live_retrace_count"] == 0, comp["live_retraces"]
    assert {e["key"] for e in comp["lattice"]} <= static


def test_spec_model_drafter_declares_draft_family():
    """A resident draft model adds the ("draft", k) ladder to the
    lattice and stays bit-exact — even with weights that disagree with
    the target (bad drafts cost acceptance, never output)."""
    cfg = live_config()
    want = _want(cfg, **PAGED)
    params = init_params(cfg, jax.random.key(0))
    dparams = init_params(cfg, jax.random.key(1))
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(8, 32),
                     spec_draft="tiny", **SPEC),
        draft=(dparams, cfg),
    )
    static = set(eng.static_lattice())
    assert {"draft/1", "draft/2", "draft/4"} <= static
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
    finally:
        eng.stop()
    assert got == want


def test_spec_self_draft_perfect_greedy_acceptance():
    """The same weights as drafter: greedy drafts are the greedy
    continuation, so acceptance is perfect and the wave count collapses
    to ceil(n/(k+1)) — the strongest compression witness."""
    cfg = live_config()
    params = init_params(cfg, jax.random.key(0))
    want = _want(cfg, **PAGED)
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(8, 32),
                     spec_draft="tiny", **SPEC),
        draft=(params, cfg),
    )
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        snap = eng.stats.snapshot()
    finally:
        eng.stop()
    assert got == want
    assert snap["decode_dispatches"] <= 3


# ---------------------------------------------------------------------------
# Sched-ledger acceptance accounting
# ---------------------------------------------------------------------------


def test_spec_conservation_and_acceptance_identities(monkeypatch):
    """Every verified token-slot is attributed useful-or-rejected, the
    acceptance identity accepted + rejected == drafted re-sums, and the
    ledger's own boundary audits never breach."""
    monkeypatch.setenv("SCHED_LEDGER", "1")
    cfg = live_config()
    want = _want(cfg, **PAGED)
    eng = _engine(cfg, start=False, **SPEC)
    eng._drafter = _Oracle(want)
    eng.start()
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        eng.drain(timeout=120)
        sched = eng.debug_sched()
    finally:
        eng.stop()
    assert got == want
    assert sched["conservation"]["breaches"] == 0, (
        sched["conservation"]["last_breach"])
    spec = sched["spec"]
    assert spec["verify_waves"] >= 1
    assert spec["drafted_tokens"] > 0
    assert (spec["accepted_tokens"] + spec["rejected_tokens"]
            == spec["drafted_tokens"])
    # Oracle drafts: acceptance is high (only terminal-clipped drafts
    # reject).
    assert spec["acceptance_rate"] >= 0.5, spec
    # The four-way attribution re-sums to the dispatched cells.
    assert (sched["useful_tokens"] + sched["bucket_pad_tokens"]
            + sched["group_pad_tokens"] + sched["spec_rejected_tokens"]
            == sched["dispatch_cells"])
    verify_shapes = [e for e in sched["by_shape"]
                     if str(e["key"]).startswith("verify/")]
    assert verify_shapes, sched["by_shape"]
    assert all(e["bucket_pad_tokens"] == 0 and e["group_pad_tokens"] == 0
               for e in verify_shapes)


def test_spec_pilot_binds_fourth_knob(monkeypatch):
    """PILOT=1 + SPEC: the controller's spec_k knob lives on the rung
    ladder envelope and the spec acceptance signals flow into decision
    windows — output stays bit-identical (pilot-at-defaults)."""
    monkeypatch.setenv("PILOT", "1")
    cfg = live_config()
    want = _want(cfg, **PAGED)
    eng = _engine(cfg, **SPEC)
    try:
        got = eng.generate_blocking(PROMPT, GREEDY)["token_ids"]
        pilot = eng.debug_pilot()
    finally:
        eng.stop()
    assert got == want
    assert pilot["knobs"]["spec_k"] == 4
    assert pilot["envelope"]["speck_min"] == 1
    assert pilot["envelope"]["speck_max"] == 4


# ---------------------------------------------------------------------------
# Off-mode isolation + config validation
# ---------------------------------------------------------------------------


def test_verify_wave_has_one_attention_leg():
    """No argument picks between implementations of the wave's
    attention: it is gqa_attention_verify, the leg that is exact at
    any temperature."""
    import inspect

    from seldon_tpu.models import spec_decode

    assert list(inspect.signature(spec_decode.verify_wave).parameters) == [
        "params", "state", "table", "drafts", "wave", "cfg", "tp"]
    assert list(inspect.signature(
        InferenceEngine._verify_impl).parameters) == [
        "params", "state", "table", "drafts", "wave", "cfg", "mesh", "tp"]


def test_spec_off_engine_is_untouched():
    cfg = live_config()
    eng = _engine(cfg, start=False, **PAGED)
    assert not any(k.startswith(("verify/", "draft/"))
                   for k in eng.static_lattice())
    assert eng._spec is False
    assert eng._drafter is None


def test_spec_config_validation():
    base = dict(max_slots=4, max_seq_len=64, prompt_buckets=(8, 32))
    with pytest.raises(ValueError, match="paged_kv"):
        EngineConfig(spec_decode=True, **base)
    with pytest.raises(ValueError, match="power of two"):
        EngineConfig(spec_decode=True, spec_k=3, paged_kv=True,
                     kv_block=8, prefix_block=8, **base)
