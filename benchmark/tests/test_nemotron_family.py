"""The nemotron_h family (benchmark/families/nemotron_h.py) and its
configuration nemotron-3-nano-30b-a3b: found by name, the key map onto the
program's ModelConfig, what the file states about the share of the model
one chip holds, the closed forms against values worked out by hand from
the published widths, the control's grid, the readers its cell adds, and
the parity limits against the readings they were set from."""
import json
import os
import re

import pytest

import family
import metrics

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "nemotron-3-nano-30b-a3b", "nemotron3.chat"
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return family.load(BENCH, _cfg())


def test_the_configuration_names_its_family_and_the_loader_finds_the_file(fam):
    cfg = _cfg()
    assert family.name_of(cfg) == "nemotron_h"
    assert fam.__file__ == os.path.join(BENCH, "families", "nemotron_h.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)
    assert fam.CONTROL == "float8 e4m3 grid"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (CELL, "chat", 1)
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["ssm.update_roofline.chat", "moe.held_touched.chat"]
    for name in mine:  # each reader agrees with its entry
        (e,) = [m for m in bench["per_layer"] if m["name"] == name]
        mod = metrics.load_reader(BENCH, name)
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])
        assert e["moves"] == "tpot_mid80_ms"


def test_every_published_number_is_kept_and_the_cut_is_stated():
    cfg = _cfg()
    assert cfg["published"] == {"num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED,
                                "n_routed_experts": 128, "vocab_size": 131072}
    # two whole periods of the published list, from its first layer
    assert cfg["hybrid_override_pattern"] == PUBLISHED[:14] == "MEMEM*E" * 2
    assert cfg["num_hidden_layers"] == 14
    # the share: the router is as wide as published, the experts held are half
    assert (cfg["router_width"], cfg["n_routed_experts"]) == (128, 64)
    assert cfg["serving"]["experts_held_from"] == 0
    assert cfg["vocab_size"] * 2 == cfg["published"]["vocab_size"]
    for key in ("deployment", "why"):
        assert len(cfg["serving"][key]) > 100 and "TBD" not in cfg["serving"][key]
    assert "router_width" in cfg["assumed"] and "rope_theta_and_partial_rotary_factor" in cfg["assumed"]
    assert cfg["serving"]["ssm_state_dtype"] == "float32"
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in pub.items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: pub[k] for k in cfg["reduced"]} == cfg["published"]


def test_the_cell_runs_at_the_rate_its_why_names_and_holds_both_limits():
    """benchmark/cells/nemotron3.chat.json against the cell's entry, as
    tests/test_lfm2_family.py holds lfm2.chat's: the rate is the number the
    `why` names and the stated fraction of the stated knee; `limit` is 2.2 x
    the TTFT and 2 x the TPOT read at that rate (`limit_from`)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (cell,) = [w for w in json.load(f)["workloads"] if w["name"] == CELL]
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        over = json.load(f)
    m = re.search(r"at ([0-9.]+) req/s \(([0-9.]+) of its knee, ~?([0-9.]+)\)", cell["why"])
    assert m, cell["why"]
    rate, fraction, knee = (float(g) for g in m.groups())
    assert rate == over["rate_rps"]
    assert fraction in (0.4, 0.25)          # ISSUE 34: 0.4, or 0.25 if that is the steadier
    assert rate == pytest.approx(fraction * knee, abs=0.051)  # rates go by 0.1
    assert sorted(over["limit"]) == sorted(over["limit_from"]) == ["tpot_ms", "ttft_ms"]
    for key, times in (("ttft_ms", 2.2), ("tpot_ms", 2.0)):
        assert over["limit"][key] == pytest.approx(times * over["limit_from"][key], rel=0.05)
    assert len(cell["why"]) <= 200


def test_key_map_gives_the_single_block_fields_and_survives_a_json_round_trip(fam):
    import dataclasses

    from seldon_tpu.models.config import ModelConfig

    cfg = _cfg()
    kw = fam.model_config_kwargs(cfg)
    assert kw["layer_types"] == ["mamba", "moe", "mamba", "moe", "mamba", "attention", "moe"] * 2
    assert isinstance(kw["layer_types"], list)
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"], kw["rotary"]) == \
        (2688, 32, 2, 128, False)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"], kw["ssm_state"],
            kw["ssm_chunk"], kw["conv_kernel"]) == (64, 64, 8, 128, 128, 4)
    assert (kw["n_experts"], kw["n_experts_held"], kw["expert_first"], kw["n_experts_per_token"],
            kw["d_ff_expert"], kw["d_ff_shared"], kw["ff_act"]) == (128, 64, 0, 6, 1856, 3712, "relu2")
    assert (kw["router"], kw["router_bias"], kw["router_norm_topk"], kw["router_scale"],
            kw["router_norm_eps"], kw["tie_embeddings"], kw["vocab_size"]) == \
        ("sigmoid", True, True, 2.5, 1e-20, False, 65536)
    model = ModelConfig(**kw).validate()   # what launcher.register_preset does
    served = json.loads(json.dumps(dataclasses.asdict(model)))  # what /metadata serves
    assert [k for k, v in kw.items() if served.get(k) != v] == []  # run.check_metadata
    assert (model.n_mamba_layers, model.n_attn_layers, model.n_sparse_layers,
            model.n_conv_layers, model.experts_held) == (6, 2, 6, 0, 64)
    assert (model.ssm_inner, model.ssm_conv_dim) == (4096, 6144)
    # all 128 held is "no share" to the program
    assert fam.model_config_kwargs(dict(cfg, n_routed_experts=128))["n_experts_held"] == 0
    with pytest.raises(ValueError, match="no bias"):
        fam.model_config_kwargs(dict(cfg, mamba_proj_bias=True))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        fam.model_config_kwargs(dict(cfg, num_hidden_layers=13))
    with pytest.raises(ValueError, match="group limit"):
        fam.model_config_kwargs(dict(cfg, n_group=2))
    with pytest.raises(ValueError, match="relu2"):
        fam.model_config_kwargs(dict(cfg, mlp_hidden_act="silu"))
    with pytest.raises(ValueError, match="float32"):
        fam.model_config_kwargs(dict(cfg, serving=dict(cfg["serving"], ssm_state_dtype="bf16")))


def test_closed_forms_against_hand_values(fam):
    cfg = _cfg()
    assert fam.layer_counts(cfg) == {"mamba": 6, "attention": 2, "moe": 6}
    assert not hasattr(fam, "slots_held")   # a need does not know how many slots the slab has
    assert (fam.ssm_inner(cfg), fam.ssm_conv_dim(cfg)) == (4096, 6144)
    assert fam.mamba_params(cfg) == 2688 * 10304 + 4096 * 2688 + 5 * 6144 == 38737920   # 38.7 M
    assert fam.attn_params(cfg) == 2 * 2688 * 4096 + 2 * 2688 * 256 == 23396352        # 23.4 M
    assert fam.expert_params(cfg) == 2 * 2688 * 1856 == 9977856                        # no gate
    assert fam.shared_params(cfg) == 2 * 2688 * 3712 == 19955712
    assert fam.router_params(cfg) == 2688 * 128
    assert fam.held_share(cfg) == 0.5
    assert fam.kv_bytes_per_token(cfg) == 2 * 2 * 2 * 128 * 2 == 2048                  # 2 layers of 14
    assert fam.ssm_state_bytes_per_slot(cfg) == 6 * 64 * 64 * 128 * 4 == 12582912      # 2.1 MB a layer
    assert fam.conv_state_bytes_per_slot(cfg) == 6 * 3 * 6144 * 2
    whole = fam.weight_bytes(cfg)   # the 64 held experts of every E layer: 8.82 GB without the embedding
    assert whole == 2 * (6 * 38737920 + 2 * 23396352 + 6 * (64 * 9977856 + 19955712)
                         + 2688 * 65536) + 4 * 6 * 2688 * 128 == 8821481472
    # uniform routing over 128, of which 64 are held: 64 (1 - (122/128)^rows)
    assert fam.experts_touched(cfg, 1) == pytest.approx(3.0)
    assert fam.experts_touched(cfg, 2.5) == pytest.approx(64 * (1 - (122 / 128) ** 2.5))
    # one layer's update over 64 slots: the state read and written, x B C dt in, y out
    uf, ub = fam.ssm_update_cost(cfg, 64)
    assert ub == 64 * (2 * 64 * 64 * 128 * 4 + (4096 + 2048) * 2 + 64 * 4 + 4096 * 4)
    assert uf == 64 * 5.0 * 64 * 64 * 128
    assert 0.26e9 < ub < 0.28e9            # 270 MB a layer a step were every slot live
    assert fam.ssm_update_cost(cfg, 2.5) == (uf * 2.5 / 64, ub * 2.5 / 64)   # linear in the slots
    # a step's need: the state of the 2.5 LIVE slots, not of the slab's 64
    flops, bytes_ = fam.decode_step_cost(cfg, 2.5, 400)
    touched = fam.experts_touched(cfg, 2.5)
    assert bytes_ == pytest.approx(
        whole - 2 * 6 * (64 - touched) * 9977856 + 2.5 * 401 * 2048
        + 2 * 2.5 * (12582912 + 6 * 3 * 6144 * 2))
    assert 2.0e9 < bytes_ < 2.2e9          # of which the live state's 0.064 GB (1.64 over the slab)
    per_tok = 2 * (6 * 38737920 + 2 * 23396352
                   + 6 * (3 * 9977856 + 19955712 + 2688 * 128) + 2688 * 65536)
    assert flops == pytest.approx(2.5 * (per_tok + 2 * 32 * 4 * 128 * 400)
                                  + 6 * uf * 2.5 / 64)
    gf, gb = fam.grouped_product_cost(cfg, 2.5)
    assert gf == pytest.approx(2 * 2.5 * 3 * 2688 * 1856)
    assert gb == pytest.approx(touched * 2688 * 1856 * 2 + 2.5 * 3 * (2688 + 1856) * 2)


def test_the_control_is_the_float8_grid_written_out_in_arithmetic(fam):
    import jax
    import jax.numpy as jnp

    fam._need_jax()
    w = (jax.random.normal(jax.random.key(0), (50000,))
         * jnp.exp(2.0 * jax.random.normal(jax.random.key(1), (50000,))) * 0.02
         ).astype(jnp.bfloat16)
    want = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    got = fam._mat(w, True)
    assert bool(jnp.all((got == want) | jnp.isnan(want)))  # nan: beyond 448, clipped here
    assert float(jnp.mean(got != w.astype(jnp.float32))) > 0.8   # it is coarser
    assert bool(jnp.all(fam._mat(w, False) == w.astype(jnp.float32)))


class _Obs(dict):
    def __getattr__(self, k):
        return self.get(k)


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_readers_return_nothing_where_the_program_writes_nothing(fam):
    """The parent's program has no held counter on its access lines and no
    op of the state's shape; another family has no Mamba-2 layers: None."""
    held = metrics.load_reader(BENCH, "moe.held_touched.chat")
    roof = metrics.load_reader(BENCH, "ssm.update_roofline.chat")
    obs = _Obs(cfg=_cfg(), family=fam, cell={"name": "no-such-cell"}, slots=64,
               trace={"device_ops": [["fusion.1_bf16_64_2048", 0.5]],
                      "ops_by_program": {"_chunk_impl": {"fusion.1_bf16_64_2048": 0.5,
                                                         "fusion.9_f32_64_64_64_128_3_2_1_0_T": 0.1}},
                      "modules": {"_chunk_impl": {"count": 10, "total_s": 1.0, "median_s": 0.1}}},
               decode_steps=400.0, decode_dispatches=100.0, rows_per_step=2.0, peaks=PEAKS)
    assert held.read(obs) is None and roof.read(obs) is None
    assert roof.read(_Obs(obs, trace=None)) is None
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        other = json.load(f)
    assert roof.read(_Obs(obs, cfg=other, family=family.load(BENCH, other))) is None


def test_update_roofline_reads_the_decode_programs_updates_by_shape_or_by_name(fam):
    """The state's shape in a cleaned op name marks the in-place update;
    the float32 [slots, heads, head width] results beside it (y, dt x) are
    the update's too. An admission's ops and other shapes are not counted.
    Where a kernel carries the update's name, the name alone counts."""
    import _ssm
    roof = metrics.load_reader(BENCH, "ssm.update_roofline.chat")
    cfg = _cfg()
    chunk_ops = {
        "add_dynamic-update-slice_fusion.6_f32_6_64_64_64_128_4_3_2_1_0_T": 0.30,
        "add_dynamic-update-slice_fusion.7_f32_6_64_64_64_128_4_3_2_1_0_T": 0.30,
        "fusion.160_f32_64_64_64_2_1_0_T_8_128_fusion_f32_6_64_64_64_128": 0.16,
        "fusion.171_f32_64_64_64_2_1_0_T_8_128_fusion_bf16_64_6144": 0.01,
        "fusion.9_f32_64_64_64_128_3_2_1_0_T_8_128": 0.5,      # four axes: another array
        "fusion.12_bf16_64_64_64_2_1_0_T_8_128": 0.5,          # bf16: x itself
        "gmm.5_bf16_384_2688_1_0_T_8_128": 0.06}
    ops = {"_chunk_impl": chunk_ops,
           "_admit_impl": {"fusion.3_f32_6_64_64_64_128_4_3_2_1_0_T": 0.9}}
    obs = _Obs(cfg=cfg, family=fam, cell={"name": "no-such-cell"}, slots=64,
               trace={"ops_by_program": ops,
                      "modules": {"_chunk_impl": {"count": 100, "total_s": 2.4,
                                                  "median_s": 0.024}}},
               decode_steps=400.0, decode_dispatches=100.0, rows_per_step=2.0, peaks=PEAKS)
    assert _ssm.state_dims(obs) == (6, 64, 64, 64, 128)
    found = _ssm.decode_update_ops(obs)
    assert sorted(found.values()) == [0.01, 0.16, 0.30, 0.30]
    # the need is the 2 LIVE slots' (no access line here: the window's rows by /metrics),
    # whatever the ops stepped: 2 / 64 of what a full slab needs in the time of a full slab
    _, bytes_ = fam.ssm_update_cost(cfg, 2.0)
    need = bytes_ / 819e9 * 6 * 400            # memory-bound; 6 layers x 100 chunks x 4 steps
    assert roof.read(obs) == pytest.approx(100.0 * need / 0.77, rel=1e-6)
    assert 3.0 < roof.read(obs) < 3.5
    full = _Obs(obs, rows_per_step=64.0)       # every slot live: the reading of PR 34 to 53
    assert roof.read(full) == pytest.approx(32 * roof.read(obs)) and roof.read(full) < 105
    named = dict(chunk_ops, **{"ssm_update.3_f32_6_64_64_64_128_4_3_2_1_0_T": 0.9})
    obs2 = _Obs(obs, trace=dict(obs.trace, ops_by_program={"_chunk_impl": named}))
    assert list(_ssm.decode_update_ops(obs2).values()) == [0.9]
    # THE NAME COMES FIRST: a kernel that steps the live slots alone has no operand of
    # the slab's shape, and fusions of that shape beside it are no part of the update
    live = {"ssm_update.3_f32_6_2_64_64_128_4_3_2_1_0_T_8_128_f32_2_64_64": 0.02,
            "ssm_update.4_f32_2_64_64_128_3_2_1_0_T_8_128": 0.01,
            "fusion.7_f32_6_64_64_64_128_4_3_2_1_0_T": 0.30, "gmm.5_bf16_384_2688_1_0_T_8_128": 0.06}
    obs3 = _Obs(obs, trace=dict(obs.trace, ops_by_program={"_chunk_impl": live}))
    assert sorted(_ssm.decode_update_ops(obs3).values()) == [0.01, 0.02]
    assert roof.read(obs3) == pytest.approx(100.0 * need / 0.03, rel=1e-6)


def _request_line(received, counters):
    return "INFO:seldon_tpu.access:request " + json.dumps(dict({
        "rid": 1, "outcome": "ok", "received_unix": received, "executor_wait_ms": 1.0,
        "queue_wait_ms": 9.0, "device_wait_ms": 50.0, "first_token_held_ms": 40.0,
        "decode_ms": 500.0}, **counters)) + "\n"


def test_held_touched_reads_the_window_difference_of_the_units_counters(fam, tmp_path, monkeypatch):
    import time

    import _access
    held = metrics.load_reader(BENCH, "moe.held_touched.chat")
    t0 = time.perf_counter()
    wall = t0 + (time.time() - time.perf_counter())
    names = ("moe_sparse_layer_steps", "moe_experts_touched", "moe_assignments",
             "moe_assignments_held")
    # 6 sparse layers x 100 steps a second, 2 rows x top-6, 5.5 held experts touched a layer
    text = ""
    for i in range(5):
        n = 600 * (i + 1)
        text += _request_line(wall + 0.5 + i, dict(zip(names, (n, n * 5.5, n * 12, n * 6.1))))
    log = tmp_path / "unit.log"
    log.write_text("startup {}\n" + text)
    monkeypatch.setattr(_access, "log_path", lambda obs: str(log))
    obs = _Obs(cfg=_cfg(), family=fam, cell={"name": "x"}, t0=t0, t1=t0 + 5.0,
               samples=[object()] * 5)
    assert held.read(obs) == pytest.approx(5.5)
    # a program that does not tell held from chosen: nothing to read
    log.write_text("startup {}\n" + "".join(
        _request_line(wall + 0.5 + i, dict(zip(names[:3], (600 * (i + 1),) * 3))) for i in range(5)))
    assert held.read(obs) is None


def test_parity_limits_pass_every_sound_reading_and_reject_every_control_reading():
    """The configuration's `parity` numbers against the chip readings they
    were set from (PR 34: 8 weight seeds at the cell's size through the
    harness's own probes, the engine's tokens and the float8-grid
    control's): each limit lies between the two readings, with room on both
    sides, and either alone rejects the control."""
    import reference

    lim = reference.limits(_cfg()["parity"])
    with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr34.json")) as f:
        rs = json.load(f)
    assert len({r["weights_seed"] for r in rs}) == len(rs) >= 8
    assert all(r["config"] == NAME and r["positions"] == 48 for r in rs)
    eps, eps_all = lim["epsilon"], lim["epsilon_all"]
    assert (eps, eps_all) == (rs[0]["epsilon"], rs[0]["epsilon_all"])   # what the readings counted at
    for r in rs:
        n = r["positions"]
        assert r["within"] / n >= lim["min_share_within"]
        assert r["over"] <= lim["max_over_epsilon_all"]
        assert r["control_within"] / n < lim["min_share_within"]      # by the share alone
        assert r["control_over"] > lim["max_over_epsilon_all"]        # and by the count alone
    sound_low = min(r["within"] for r in rs)
    control_high = max(r["control_within"] for r in rs)
    asked = lim["min_share_within"] * 48
    assert control_high < asked <= sound_low
    assert control_high + 4 <= asked <= sound_low - 2       # room on both sides
    assert lim["max_over_epsilon_all"] >= max(r["over"] for r in rs) + 1
    assert min(r["control_over"] for r in rs) >= 3 * max(lim["max_over_epsilon_all"], 1)
