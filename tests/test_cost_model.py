"""graftroof cost-model tests: closed forms, coverage, purity.

The load-bearing claims, in test form:
 * the closed-form arithmetic is RIGHT — hand-counted totals for the
   tiny config (flops/token, kv bytes/token, weight bytes, a full
   decode-rung dispatch) pinned as literals;
 * every family in ``shape_lattice.FAMILIES`` is priced (the covered
   set is pinned to FAMILIES exactly) and an unknown family raises
   instead of silently pricing zero;
 * env gating follows the None-attribute idiom (ROOF_LEDGER), peak
   resolution honors env > table > microbench, and the conservation
   audit is not vacuous (a ledger fed inconsistent spans breaches);
 * the ledger is pure observation — greedy outputs are BIT-IDENTICAL
   with ROOF_LEDGER on vs off across all five dispatch paths (dense,
   paged-KV, chunked prefill, both together, spec-decode).
"""

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import cost_model
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from seldon_tpu.servers.shape_lattice import FAMILIES

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=4)
# Mixed lengths so admission groups carry real bucket + group padding.
PROMPTS = [list(range(2, 2 + n)) for n in (5, 12, 16, 7)]

# The five dispatch paths whose outputs the roof must not perturb.
MODES = {
    "dense": {},
    "paged": dict(paged_kv=True, kv_block=16, kv_pool_blocks=12,
                  prompt_buckets=(16, 32)),
    "chunked": dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8),
    "paged+chunked": dict(paged_kv=True, chunked_prefill=True,
                          prefill_chunk=8, prefix_block=8, kv_block=8),
    "paged+chunked+prefix": dict(paged_kv=True, chunked_prefill=True,
                                 prefill_chunk=8, prefix_block=8,
                                 kv_block=8, prefix_cache=True),
    "spec": dict(spec_decode=True, spec_k=2, paged_kv=True, kv_block=8,
                 prefix_block=8),
}

TINY = get_config("tiny")
GEOM = dict(max_slots=4, max_seq_len=64)


def _engine(start=True, **ekw):
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


def _collect(eng, prompts):
    qs = [eng.submit(p, GREEDY) for p in prompts]
    outs = []
    for q in qs:
        toks = []
        while True:
            item = q.get(timeout=300)
            if item is None:
                break
            toks.extend(item["tokens"])
        outs.append(toks)
    return outs


# ---------------------------------------------------------------------------
# Closed forms, hand-counted on the tiny config
# ---------------------------------------------------------------------------
# tiny: n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
# vocab=256, bf16 weights + kv. Per layer: qkv 64*(4*16 + 2*2*16) =
# 8192, o 64*64 = 4096, mlp 3*64*128 = 24576 -> 36864 params.


def test_matmul_params_per_layer_hand_counted():
    assert cost_model.matmul_params_per_layer(TINY) == 36864


def test_flops_per_token_hand_counted():
    # 2 * (2 layers * 36864 + lm_head 64*256) = 2*(73728 + 16384)
    assert cost_model.flops_per_token(TINY) == 180224


def test_kv_bytes_per_token_hand_counted():
    # 2 (K+V) * 2 layers * 2 kv_heads * 16 head_dim * 2 bytes
    assert cost_model.kv_bytes_per_token(TINY) == 256


def test_weight_bytes_hand_counted():
    # matmuls 2*36864*2B + embedding 256*64*2B + lm_head 64*256*2B
    assert cost_model.weight_bytes(TINY) == 212992


def test_attn_flops_hand_counted():
    # 4 * d_model * q * kv * layers = 4 * 64 * 1 * 64 * 2
    assert cost_model.attn_flops(TINY, 1, 64) == 32768
    # Causal prefill of 8 fresh tokens: sum 1..8 = 36 kv positions.
    assert cost_model.causal_attn_flops(TINY, 8) == 4 * 64 * 36 * 2
    # With an 8-token prior every row attends 8 more positions.
    assert (cost_model.causal_attn_flops(TINY, 8, prior=8)
            == 4 * 64 * (36 + 64) * 2)


def test_decode_key_hand_counted():
    # ("decode", 8): 8 steps x 4 slots, each step fpt + full-window
    # attention; bytes re-read the weights + window every step.
    flops, bytes_ = cost_model.cost_of_key(("decode", 8), TINY, **GEOM)
    assert flops == 8 * 4 * (180224 + 32768) == 6815744
    assert bytes_ == 8 * (212992 + 4 * 64 * 256 + 4 * 256) == 2236416


def test_admit_key_hand_counted():
    flops, bytes_ = cost_model.cost_of_key(("admit", 8, 2), TINY, **GEOM)
    assert flops == 2 * (8 * 180224 + cost_model.causal_attn_flops(TINY, 8))
    assert bytes_ == 212992 + 2 * 8 * 256


# ---------------------------------------------------------------------------
# graftmesh: per-chip closed forms at tp=2, hand-counted
# ---------------------------------------------------------------------------
# Exact-TP split (models/tp_sharding): qkv + gate/up shard their output
# dim, o / down / embeddings / lm_head replicate. Per layer per chip:
# qkv 8192/2 = 4096, o 4096, gate+up 2*64*128/2 = 8192, down 8192
# -> 24576 params.


def test_tp2_per_layer_hand_counted():
    assert cost_model.matmul_params_per_layer(TINY, 2) == 24576


def test_tp2_flops_per_token_hand_counted():
    # 2 * (2 layers * 24576 + lm_head 64*256 replicated) = 131072
    assert cost_model.flops_per_token(TINY, 2) == 131072


def test_tp2_attn_and_kv_hand_counted():
    # Heads shard on 'tp': per-chip attention and KV both halve.
    assert cost_model.attn_flops(TINY, 1, 64, tp=2) == 16384
    assert cost_model.kv_bytes_per_token(TINY, 2) == 128


def test_tp2_weight_bytes_hand_counted():
    # matmuls 2*24576*2B + embedding 32768 + lm_head 32768 (both full
    # on every chip) = 163840
    assert cost_model.weight_bytes(TINY, 2) == 163840


def test_tp2_decode_key_hand_counted():
    flops, bytes_ = cost_model.cost_of_key(("decode", 8), TINY,
                                           tp=2, **GEOM)
    assert flops == 8 * 4 * (131072 + 16384) == 4718592
    assert bytes_ == 8 * (163840 + 4 * 64 * 128 + 4 * 128) == 1576960


def test_tp1_default_unchanged():
    # The tp kwarg defaults to 1 and must price exactly the seed
    # numbers — the tp=1 path is byte-identical to a build without
    # graftmesh.
    assert cost_model.matmul_params_per_layer(TINY, 1) == 36864
    assert (cost_model.cost_of_key(("decode", 8), TINY, tp=1, **GEOM)
            == cost_model.cost_of_key(("decode", 8), TINY, **GEOM))


def test_tp_moe_shards_attention_only():
    # MoE expert weights replicate (expert_out contracts d_ff — a psum
    # would break exactness), so only the qkv term divides.
    moe = get_config("tiny-moe")
    full = cost_model.matmul_params_per_layer(moe, 1)
    half = cost_model.matmul_params_per_layer(moe, 2)
    assert full - half == 8192 - 4096  # qkv/2 is the only delta


def test_roof_ledger_binds_tp():
    led = cost_model.RoofLedger()
    led.bind(TINY, tp=2, **GEOM)
    snap = led.snapshot()
    assert snap["tp"] == 2
    # The bound geometry threads into every priced key.
    assert led._cost(("decode", 8)) == cost_model.cost_of_key(
        ("decode", 8), TINY, tp=2, **GEOM)
    # Default bind stays tp=1 — the seed schema payload, plus the key.
    led2 = cost_model.RoofLedger()
    led2.bind(TINY, **GEOM)
    assert led2.snapshot()["tp"] == 1


# ---------------------------------------------------------------------------
# Family coverage pinned to the lattice
# ---------------------------------------------------------------------------

# One representative key per family, at the registered arity.
REPRESENTATIVE = {
    "deactivate": ("deactivate",),
    "admit": ("admit", 8, 2),
    "admit-prefix": ("admit-prefix", 8, 8, 2),
    "admit-paged": ("admit-paged", 8, 2, 16),
    "chunk": ("chunk", 8, 2, 16),
    "seed-prefix": ("seed-prefix", 16),
    "cow": ("cow",),
    "decode": ("decode", 8),
    "draft": ("draft", 4),
    "verify": ("verify", 4),
}


def test_every_family_is_priced():
    assert set(REPRESENTATIVE) == set(FAMILIES), \
        "FAMILIES drifted — add a representative key AND a cost formula"
    for fam, key in REPRESENTATIVE.items():
        flops, bytes_ = cost_model.cost_of_key(key, TINY, kv_block=16, **GEOM)
        assert flops >= 0.0 and bytes_ >= 0.0, fam
        # Everything but the host-drafted spec rung moves SOME bytes.
        if fam != "draft":
            assert bytes_ > 0.0, fam


def test_unknown_family_raises():
    with pytest.raises(ValueError, match="unknown dispatch family"):
        cost_model.cost_of_key(("warp", 8), TINY, **GEOM)


def test_draft_prices_zero_without_resident_model():
    # Host n-gram drafting dispatches nothing on the device.
    assert cost_model.cost_of_key(("draft", 4), TINY, **GEOM) == (0.0, 0.0)
    # A resident draft checkpoint prices as its own decode ladder.
    flops, bytes_ = cost_model.cost_of_key(("draft", 4), TINY,
                                           draft_cfg=TINY, **GEOM)
    assert (flops, bytes_) == cost_model.cost_of_key(("decode", 4), TINY,
                                                     **GEOM)


# ---------------------------------------------------------------------------
# Peaks + predict
# ---------------------------------------------------------------------------


def test_peak_resolution_order(monkeypatch):
    monkeypatch.delenv("ROOF_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("ROOF_PEAK_GBS", raising=False)
    table = cost_model.resolve_peaks("TPU v5e")
    assert table == {"tflops": 197.0, "gbs": 819.0, "source": "table"}
    # Longest-substring wins: v5p must not fall through to "v5 lite".
    assert cost_model.resolve_peaks("TPU v5p")["tflops"] == 459.0
    # The host CPU (and only it): the cached one-shot microbench.
    mb = cost_model.resolve_peaks("cpu")
    assert mb["source"] == "microbench" and mb["tflops"] > 0.0
    # An accelerator the table does not know is an error, not a default
    # — unless the operator supplies BOTH peaks.
    with pytest.raises(ValueError, match="TPU v9"):
        cost_model.resolve_peaks("TPU v9")
    monkeypatch.setenv("ROOF_PEAK_TFLOPS", "1000")
    with pytest.raises(ValueError, match="TPU v9"):
        cost_model.resolve_peaks("TPU v9")
    monkeypatch.setenv("ROOF_PEAK_GBS", "2000")
    assert cost_model.resolve_peaks("TPU v9") == {
        "tflops": 1000.0, "gbs": 2000.0, "source": "env"}
    monkeypatch.delenv("ROOF_PEAK_TFLOPS")
    monkeypatch.delenv("ROOF_PEAK_GBS")
    # Env overrides everything, each knob individually.
    monkeypatch.setenv("ROOF_PEAK_TFLOPS", "123.5")
    env = cost_model.resolve_peaks("TPU v5e")
    assert env["tflops"] == 123.5 and env["source"] == "env"
    assert env["gbs"] == 819.0  # GBS still from the table
    # A malformed override falls back rather than crashing the engine.
    monkeypatch.setenv("ROOF_PEAK_TFLOPS", "fast")
    assert cost_model.resolve_peaks("TPU v5e")["tflops"] == 197.0


def test_predict_surface_monotone():
    peaks = {"tflops": 1.0, "gbs": 1.0, "source": "env"}
    base = cost_model.predict(16, 8, TINY, peaks=peaks, **GEOM)
    assert set(base) == {"flops", "bytes", "est_ms"}
    assert base["est_ms"] > 0.0
    longer = cost_model.predict(32, 8, TINY, peaks=peaks, **GEOM)
    deeper = cost_model.predict(16, 16, TINY, peaks=peaks, **GEOM)
    assert longer["flops"] > base["flops"]
    assert deeper["flops"] > base["flops"]
    assert longer["est_ms"] > base["est_ms"]
    # Degenerate inputs clamp instead of going negative.
    zero = cost_model.predict(-3, 0, TINY, peaks=peaks, **GEOM)
    assert zero["flops"] >= 0.0 and zero["est_ms"] >= 0.0


def test_predict_request_ms_is_memoized():
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    a = led.predict_request_ms(16, 8)
    assert a > 0.0
    assert led.predict_request_ms(16, 8) == a
    assert (16, 8) in led._predict_cache


# ---------------------------------------------------------------------------
# Ledger unit semantics
# ---------------------------------------------------------------------------


def test_from_env_gating(monkeypatch):
    monkeypatch.delenv("ROOF_LEDGER", raising=False)
    assert cost_model.from_env() is None
    monkeypatch.setenv("ROOF_LEDGER", "0")
    assert cost_model.from_env() is None
    monkeypatch.setenv("ROOF_LEDGER", "1")
    assert cost_model.from_env() is not None


def test_note_wave_conserves_device_time():
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    led.note_wave([("admit", 8, 2), ("decode", 8), ("cow",)],
                  device_ms=30.0)
    snap = led.snapshot()
    assert snap["waves"] == 1
    assert sum(v["device_ms"] for v in snap["variants"]) \
        == pytest.approx(30.0, abs=0.01)
    # The split is est-weighted: decode prices far above cow, so it
    # must carry more of the wave.
    by_fam = {v["family"]: v for v in snap["variants"]}
    assert by_fam["decode"]["device_ms"] > by_fam["cow"]["device_ms"]


def test_note_wave_unpriceable_key_never_raises():
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    led.note_wave([("warp", 3), ("decode", 8)], device_ms=10.0)
    snap = led.snapshot()
    # The foreign key prices zero but still appears, and the priced key
    # absorbs the whole est-weighted wave.
    assert sum(v["device_ms"] for v in snap["variants"]) \
        == pytest.approx(10.0, abs=0.01)


def test_variant_overflow_folds_to_other():
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    for g in range(cost_model._MAX_VARIANTS + 8):
        led.note_wave([("admit", 8, g + 1)], device_ms=1.0)
    snap = led.snapshot()
    assert len(snap["variants"]) <= cost_model._MAX_VARIANTS + 1
    other = [v for v in snap["variants"] if v["key"] == "other"]
    assert len(other) == 1 and other[0]["dispatches"] == 8


def test_audit_clean_on_consistent_feed():
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    for _ in range(5):
        led.note_step(1.0, 10.0, 2.0, 15.0)  # 2ms pipelined gap
        led.audit()
    snap = led.snapshot()
    assert snap["conservation"]["checked"] == 5
    assert snap["conservation"]["breaches"] == 0
    assert snap["step"]["overlap_ms"] == pytest.approx(10.0)
    assert snap["host_frac"] == pytest.approx(3.0 / 15.0, abs=1e-6)


def test_audit_breaches_on_inconsistent_feed():
    # The audit is not vacuous: components exceeding the measured wall
    # (a span clocked shorter than its own parts) must breach.
    led = cost_model.RoofLedger()
    led.bind(TINY, **GEOM)
    led.note_step(100.0, 100.0, 100.0, 5.0)
    led.audit()
    snap = led.snapshot()
    assert snap["conservation"]["breaches"] == 1
    assert "step components" in snap["conservation"]["last_breach"]


# ---------------------------------------------------------------------------
# Purity: greedy outputs bit-identical with the roof on vs off
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(MODES))
def test_greedy_bit_identical_roof_on_off(mode, monkeypatch):
    monkeypatch.delenv("ROOF_LEDGER", raising=False)
    eng = _engine(**MODES[mode])
    try:
        base = _collect(eng, PROMPTS)
    finally:
        eng.stop()
    monkeypatch.setenv("ROOF_LEDGER", "1")
    eng = _engine(**MODES[mode])
    try:
        roofed = _collect(eng, PROMPTS)
        snap = eng.debug_roof()
    finally:
        eng.stop()
    assert roofed == base, f"ROOF_LEDGER perturbed {mode} greedy output"
    # And the roof actually observed the run it rode along on.
    assert snap is not None and snap["boundaries"] > 0
    assert snap["totals"]["dispatches"] > 0
    assert snap["conservation"]["breaches"] == 0


def test_disabled_engine_keeps_none_attribute(monkeypatch):
    monkeypatch.delenv("ROOF_LEDGER", raising=False)
    eng = _engine(start=False)
    assert eng._roof is None
    assert eng.debug_roof() is None
    assert eng.roof_predict_ms(16, 8) is None


def test_enabled_engine_predicts_and_serves_snapshot(monkeypatch):
    monkeypatch.setenv("ROOF_LEDGER", "1")
    eng = _engine(start=False)
    assert eng._roof is not None
    assert eng._timing_on, "ROOF_LEDGER must imply dispatch timing"
    assert eng.roof_predict_ms(16, 8) > 0.0
    snap = eng.debug_roof()
    assert snap["enabled"] is True and snap["boundaries"] == 0
