"""graftmesh (servers/mesh_engine.py + models/tp_sharding.py +
engine tp threading): tensor-parallel serving on the fake 8-device CPU
mesh, pinned bit-exact against tp=1.

The load-bearing claims, in test form:
 * greedy output is BIT-IDENTICAL tp=2 vs tp=1 across every dispatch
   family the engine ships — dense, paged, chunked, both, spec —
   and for bf16, int8-KV and W8A8 weights: the exact-TP scheme shards
   only output dims (models/tp_sharding docstring), so per-element
   reduction order never changes;
 * sampled output is identical too (logits are replicated, so the
   seeded sampler sees the same distribution);
 * the sharding tables are enforced: validate() rejects indivisible
   configs, hints() rejects a mesh whose 'tp' axis disagrees with the
   config, EngineConfig rejects tp < 1, and the engine rejects
   flash/ring attention under tp;
 * one sealed lattice serves the whole TP group: with COMPILE_LEDGER=1
   a warmed tp=2 engine reports its geometry and ZERO live retraces
   under traffic (donated-state sharding is pinned, so jit cache keys
   cannot drift);
 * /debug/hbm grows honest per-device accounting: weights commit
   sharded (per-device < full), the KV reservation halves per chip;
 * MESH_DEVICES caps the devices build_tp_mesh may claim.

CPU CI serves real 2-device meshes via
XLA_FLAGS=--xla_force_host_platform_device_count=8 (conftest.py).
"""

import dataclasses

import jax
import pytest

from seldon_tpu.models import init_params, tp_sharding
from seldon_tpu.models.config import get_config
from seldon_tpu.models.quantize import quantize_params
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import mesh_engine
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)
SAMPLED = SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                         max_new_tokens=8, seed=7)
# Mixed lengths: admission groups carry real bucket + group padding.
PROMPTS = [list(range(2, 2 + n)) for n in (5, 12, 24, 7)]

GEOM = dict(max_slots=4, max_seq_len=64)
MODES = {
    "dense": {},
    "paged": dict(paged_kv=True, kv_block=16, kv_pool_blocks=12,
                  prompt_buckets=(16, 32)),
    "chunked": dict(chunked_prefill=True, prefill_chunk=8, prefix_block=8),
    "paged+chunked": dict(paged_kv=True, chunked_prefill=True,
                          prefill_chunk=8, prefix_block=8, kv_block=8),
    "spec": dict(spec_decode=True, spec_k=2, paged_kv=True, kv_block=8,
                 prefix_block=8),
}


def _params(cfg):
    params = init_params(cfg, jax.random.key(0))
    if cfg.weight_dtype == "int8":
        params = quantize_params(params)
    return params


def _run(cfg, params, tp, sp=GREEDY, **ekw):
    ekw = dict(GEOM, **ekw)
    ekw.setdefault("prompt_buckets", (8, 32))
    if tp > 1:
        eng = mesh_engine.MeshEngine(params, cfg, EngineConfig(**ekw),
                                     tp=tp)
    else:
        eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    eng.start()
    try:
        qs = [eng.submit(p, sp) for p in PROMPTS]
        outs = []
        for q in qs:
            toks = []
            while True:
                item = q.get(timeout=300)
                if item is None:
                    break
                assert "error" not in item, item
                toks.extend(item["tokens"])
            outs.append(toks)
        return outs
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Bit-exact parity: tp=2 vs tp=1, every dispatch family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(MODES))
def test_greedy_bit_identical_tp2_vs_tp1(mode):
    cfg = get_config("tiny")
    params = _params(cfg)
    want = _run(cfg, params, 1, **MODES[mode])
    got = _run(cfg, params, 2, **MODES[mode])
    assert got == want, f"tp=2 diverged from tp=1 under {mode}"
    assert all(len(t) > 0 for t in want)


def test_greedy_bit_identical_int8_kv_paged_chunked():
    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype="int8")
    params = _params(cfg)
    want = _run(cfg, params, 1, **MODES["paged+chunked"])
    got = _run(cfg, params, 2, **MODES["paged+chunked"])
    assert got == want, "tp=2 diverged from tp=1 with int8 KV"


def test_greedy_bit_identical_w8a8_dense():
    # Sharded int8 weights carry per-output-channel scales that ride
    # their output slice; the per-token activation scale is a max over
    # the unsharded feature axis — both exact under the split.
    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8",
                              act_dtype="int8")
    params = _params(cfg)
    want = _run(cfg, params, 1)
    got = _run(cfg, params, 2)
    assert got == want, "tp=2 diverged from tp=1 under W8A8"


def test_greedy_bit_identical_w8a8_big_bucket():
    # Regression: at the 128 bucket the W8A8 activation-quantization max
    # used to fuse into its producer and read unrounded f32
    # intermediates, so the int8 scale depended on fusion choices —
    # which differ between the single-chip and SPMD-partitioned
    # compilations — and tp=2 greedy drifted from tp=1 on near-ties
    # mid-stream. _quantize_act/_quantize_kv now pin their input with an
    # optimization_barrier; this is the geometry that caught it.
    cfg = dataclasses.replace(get_config("tiny"), weight_dtype="int8",
                              act_dtype="int8", kv_cache_dtype="int8")
    params = _params(cfg)
    big = dict(max_slots=4, max_seq_len=128, prompt_buckets=(32, 128),
               paged_kv=True, kv_block=16, kv_pool_blocks=33,
               chunked_prefill=True, prefill_chunk=32, prefix_block=16)
    prompts = [list(range(2, 2 + n)) for n in (24, 48, 96, 16)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=16)

    def leg(tp):
        ekw = dict(big)
        if tp > 1:
            eng = mesh_engine.MeshEngine(params, cfg, EngineConfig(**ekw),
                                         tp=tp)
        else:
            eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
        eng.start()
        try:
            qs = [eng.submit(p, sp) for p in prompts]
            outs = []
            for q in qs:
                toks = []
                while True:
                    item = q.get(timeout=300)
                    if item is None:
                        break
                    assert "error" not in item, item
                    toks.extend(item["tokens"])
                outs.append(toks)
            return outs
        finally:
            eng.stop()

    want = leg(1)
    got = leg(2)
    assert got == want, "tp=2 diverged from tp=1 under W8A8 at the 128 bucket"


def test_sampled_bit_identical_tp2_vs_tp1():
    # Logits replicate across the group, so the seeded sampler draws
    # the same tokens — not just argmax parity.
    cfg = get_config("tiny")
    params = _params(cfg)
    want = _run(cfg, params, 1, sp=SAMPLED)
    got = _run(cfg, params, 2, sp=SAMPLED)
    assert got == want, "tp=2 diverged from tp=1 under seeded sampling"


# ---------------------------------------------------------------------------
# Sharding-table enforcement
# ---------------------------------------------------------------------------


def test_slab_rows_shard_into_whole_head_groups():
    """The dense slab stores a token's heads side by side in one row
    (transformer.cache_spec): 'tp' takes the row's lanes, so that each
    device holds the contiguous lanes of its own Hkv / tp heads (and
    those heads' int8 scales), the paged pool still shards its Hkv
    axis, and TpHints.rows views a device's lanes as rows of its own
    head group without moving a byte between devices."""
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from seldon_tpu.models import transformer

    cfg = dataclasses.replace(get_config("tiny"), kv_cache_dtype="int8")
    Hkv, Dh, tp = cfg.n_kv_heads, cfg.head_dim, 2
    assert Hkv % tp == 0
    slab = transformer.init_cache(cfg, 4, 16)
    pool = transformer.init_paged_cache(cfg, 6, 8)
    assert tp_sharding.state_leaf_spec(slab["k"]) == \
        P(None, None, None, None, "tp")
    assert tp_sharding.state_leaf_spec(slab["k_scale"]) == \
        P(None, None, "tp", None)
    assert tp_sharding.state_leaf_spec(pool["k"]) == \
        P(None, None, "tp", None, None)
    assert tp_sharding.state_leaf_spec(jnp.zeros((4,))) == P()

    mesh = mesh_engine.build_tp_mesh(tp)
    # Lane c of a row holds head c // Dh: mark every lane with its head.
    L, B, _, T, C = slab["k"].shape
    heads = jnp.broadcast_to(
        (jnp.arange(C) // Dh).astype(jnp.int8), slab["k"].shape)
    sharded = tp_sharding.shard_state(mesh, {"k": heads})["k"]
    group = Hkv // tp
    for shard in sharded.addressable_shards:
        d = list(mesh.devices.flat).index(shard.device)
        got = np.unique(np.asarray(shard.data))
        assert list(got) == list(range(d * group, (d + 1) * group))
        assert shard.data.shape == (L, B, 1, T, C // tp)

    hints = tp_sharding.hints(mesh, tp)
    view = jax.jit(hints.rows)
    layer = sharded[0]
    out = view(layer)
    assert out.shape == (B, tp, T, C // tp)
    for g in range(tp):  # group g = the heads of device g, lanes in order
        np.testing.assert_array_equal(
            np.asarray(out[:, g]),
            np.asarray(heads[0, :, 0, :, g * (C // tp):(g + 1) * (C // tp)]))
    hlo = view.lower(layer).compile().as_text()
    for collective in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute"):
        assert collective not in hlo, collective


def test_validate_rejects_indivisible_configs():
    cfg = get_config("tiny")  # n_kv_heads=2, n_heads=4, d_ff=128
    with pytest.raises(ValueError, match="n_kv_heads"):
        tp_sharding.validate(cfg, 3)
    with pytest.raises(ValueError, match="n_kv_heads"):
        tp_sharding.validate(cfg, 4)
    tp_sharding.validate(cfg, 2)  # divides everything
    tp_sharding.validate(cfg, 1)  # tp=1 is always fine


def test_hints_rejects_mesh_mismatch():
    assert tp_sharding.hints(None, 1) is None
    with pytest.raises(ValueError, match="requires a mesh"):
        tp_sharding.hints(None, 2)
    mesh = mesh_engine.build_tp_mesh(2)
    with pytest.raises(ValueError, match="2-way"):
        tp_sharding.hints(mesh, 4)
    h = tp_sharding.hints(mesh, 2)
    assert h is not None and h.tp == 2


def test_engine_config_rejects_bad_tp():
    with pytest.raises(ValueError):
        EngineConfig(max_slots=4, max_seq_len=64, tp=0)
    with pytest.raises(ValueError):
        EngineConfig(max_slots=4, max_seq_len=64, tp=-2)


def test_engine_rejects_untheaded_attention_kernels():
    cfg = dataclasses.replace(get_config("tiny"), attn_impl="flash")
    params = init_params(get_config("tiny"), jax.random.key(0))
    with pytest.raises(ValueError, match="not supported"):
        mesh_engine.MeshEngine(params, cfg,
                               EngineConfig(tp=2, **GEOM), tp=2)


def test_mesh_engine_rejects_tp_disagreement():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="disagrees"):
        mesh_engine.MeshEngine(params, cfg,
                               EngineConfig(tp=2, **GEOM), tp=4)


def test_mesh_devices_env_caps_budget(monkeypatch):
    monkeypatch.setenv("MESH_DEVICES", "1")
    assert mesh_engine.device_budget() == 1
    with pytest.raises(ValueError, match="MESH_DEVICES"):
        mesh_engine.build_tp_mesh(2)
    monkeypatch.setenv("MESH_DEVICES", "0")
    assert mesh_engine.device_budget() == len(jax.devices())


# ---------------------------------------------------------------------------
# One sealed lattice, per-device HBM
# ---------------------------------------------------------------------------


def test_tp_group_seals_one_lattice_zero_retraces(monkeypatch):
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    cfg = get_config("tiny")
    eng = mesh_engine.MeshEngine(_params(cfg), cfg,
                                 EngineConfig(prompt_buckets=(8, 32),
                                              **GEOM),
                                 tp=2)
    eng.warmup()
    eng.start()
    try:
        qs = [eng.submit(p, GREEDY) for p in PROMPTS]
        for q in qs:
            while q.get(timeout=300) is not None:
                pass
        snap = eng.debug_compile()
    finally:
        eng.stop()
    assert snap["tp"] == 2 and snap["mesh_devices"] == 2
    assert snap["warmup_complete"] is True
    assert snap["live_retrace_count"] == 0, snap["live_retraces"]
    assert snap["declared_variants"] >= snap["dispatched_variants"]


def test_hbm_reports_per_device_bytes(monkeypatch):
    monkeypatch.setenv("HBM_LEDGER", "1")
    cfg = get_config("tiny")
    params = _params(cfg)
    ref = InferenceEngine(params, cfg,
                          EngineConfig(prompt_buckets=(8, 32), **GEOM))
    try:
        ref_w = ref.debug_hbm()["categories"]["weights"]["bytes"]
    finally:
        ref.stop()
    eng = mesh_engine.MeshEngine(params, cfg,
                                 EngineConfig(prompt_buckets=(8, 32),
                                              **GEOM),
                                 tp=2)
    try:
        snap = eng.debug_hbm()
        assert snap["devices"] == 2
        cats = snap["categories"]
        w = cats["weights"]
        # Mesh-wide weight bytes are per-device x devices (replicated
        # leaves genuinely live on every chip).
        assert w["bytes"] == 2 * w["bytes_per_device"]
        # Sharding actually saves per-chip memory vs single-chip, but
        # less than half of it (wo / w_down / embeddings / norms
        # replicate).
        assert ref_w // 2 < w["bytes_per_device"] < ref_w
        # KV reservation shards exactly on the head axis.
        kv = cats["kv_cache"]
        assert kv["bytes_per_device"] == kv["bytes"] // 2
        assert snap["total_bytes_per_device"] < snap["total_bytes"]
    finally:
        eng.stop()


def test_mesh_info_surface():
    cfg = get_config("tiny")
    eng = mesh_engine.MeshEngine(_params(cfg), cfg,
                                 EngineConfig(prompt_buckets=(8, 32),
                                              **GEOM),
                                 tp=2)
    try:
        info = eng.mesh_info()
        assert info["tp"] == 2
        assert info["axis"] == tp_sharding.TP_AXIS
        assert len(info["devices"]) == 2
        assert info["weight_bytes_per_device"] > 0
    finally:
        eng.stop()


def test_roof_prices_per_chip_under_tp(monkeypatch):
    monkeypatch.setenv("ROOF_LEDGER", "1")
    cfg = get_config("tiny")
    eng = mesh_engine.MeshEngine(_params(cfg), cfg,
                                 EngineConfig(prompt_buckets=(8, 32),
                                              **GEOM),
                                 tp=2)
    eng.start()
    try:
        qs = [eng.submit(p, GREEDY) for p in PROMPTS]
        for q in qs:
            while q.get(timeout=300) is not None:
                pass
        snap = eng.debug_roof()
    finally:
        eng.stop()
    assert snap["tp"] == 2
    assert snap["boundaries"] > 0
    assert snap["conservation"]["breaches"] == 0
