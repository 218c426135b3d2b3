"""The lfm2 family (benchmark/families/lfm2.py) and its configuration
lfm2-24b-a2b: found by name, the key map onto the program's ModelConfig,
the closed forms against values worked out by hand from the published
widths, the control's grid, and the two readers its cell adds."""
import json
import os
import re

import pytest

import family
import metrics

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return family.load(BENCH, _cfg())


def test_the_configuration_names_its_family_and_the_loader_finds_the_file(fam):
    cfg = _cfg()
    assert family.name_of(cfg) == "lfm2"
    assert fam.__file__ == os.path.join(BENCH, "families", "lfm2.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)
    assert fam.CONTROL == "float8 e4m3 grid"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == "lfm2-24b-a2b"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("lfm2.chat", "chat", 1)
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == ["lfm2.chat"]]
    assert mine == ["moe.experts_touched.chat", "moe.kernel_roofline.chat"]
    for name in mine:  # each reader agrees with its entry
        (e,) = [m for m in bench["per_layer"] if m["name"] == name]
        mod = metrics.load_reader(BENCH, name)
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])


def test_every_published_number_is_kept_and_the_cut_is_depth_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B"]
    cfg, pub = _cfg(), row["config"]
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in pub.items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 40, "layer_types": pub["layer_types"]}
    # two leading dense layers, then two whole periods, as published
    assert cfg["layer_types"] == pub["layer_types"][:10] and cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"][2:6] == cfg["layer_types"][6:10] == \
        ["full_attention", "conv", "conv", "conv"]


def test_the_cell_runs_at_the_rate_its_why_names_and_holds_both_limits():
    """benchmark/cells/lfm2.chat.json against the cell's entry: the offered
    rate is the number the entry's `why` names and is the fraction of the
    knee that stands beside it, whatever fraction that is; `limit` holds
    both keys, each by the cells' rule: 2.2 x the TTFT and 2 x the TPOT
    read at that rate (`limit_from`, the readings it was set from), rounded
    by at most a twentieth."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (cell,) = [w for w in json.load(f)["workloads"] if w["name"] == "lfm2.chat"]
    with open(os.path.join(BENCH, "cells", "lfm2.chat.json")) as f:
        over = json.load(f)
    m = re.search(r"at ([0-9.]+) req/s \(([0-9.]+) of its knee, ~?([0-9.]+)\)", cell["why"])
    assert m, cell["why"]
    rate, fraction, knee = (float(g) for g in m.groups())
    assert rate == over["rate_rps"]
    assert 0 < fraction < 1
    assert rate == pytest.approx(fraction * knee, abs=0.051)  # rates go by 0.1
    assert sorted(over["limit"]) == sorted(over["limit_from"]) == ["tpot_ms", "ttft_ms"]
    for key, times in (("ttft_ms", 2.2), ("tpot_ms", 2.0)):
        assert over["limit"][key] == pytest.approx(times * over["limit_from"][key], rel=0.05)
    assert len(cell["why"]) <= 200


def test_key_map_gives_the_patterned_fields_and_survives_a_json_round_trip(fam):
    import dataclasses

    from seldon_tpu.models.config import ModelConfig

    kw = fam.model_config_kwargs(_cfg())
    assert kw["layer_types"] == _cfg()["layer_types"] and isinstance(kw["layer_types"], list)
    assert (kw["d_model"], kw["d_ff"], kw["d_ff_expert"], kw["n_experts"],
            kw["n_experts_per_token"], kw["n_dense_layers"]) == (2048, 11776, 1536, 64, 4, 2)
    assert (kw["router"], kw["router_bias"], kw["router_norm_topk"], kw["router_scale"],
            kw["qk_norm"], kw["conv_kernel"], kw["tie_embeddings"]) == \
        ("sigmoid", True, True, 1.0, True, 3, True)
    model = ModelConfig(**kw).validate()   # what launcher.register_preset does
    served = json.loads(json.dumps(dataclasses.asdict(model)))  # what /metadata serves
    assert [k for k, v in kw.items() if served.get(k) != v] == []  # run.check_metadata
    assert (model.n_attn_layers, model.n_conv_layers, model.n_sparse_layers) == (2, 8, 8)
    with pytest.raises(ValueError, match="no bias"):
        fam.model_config_kwargs(dict(_cfg(), conv_bias=True))
    with pytest.raises(ValueError, match="layer_types"):
        fam.model_config_kwargs(dict(_cfg(), num_hidden_layers=9))


def test_closed_forms_against_hand_values(fam):
    cfg = _cfg()
    assert fam.layer_counts(cfg) == {"attention": 2, "conv": 8, "sparse": 8, "dense": 2}
    assert fam.attn_params(cfg) == 2048 * 2048 * 2 + 2 * 2048 * 512          # 10.5 M
    assert fam.conv_params(cfg) == 2048 * 6144 + 2048 * 2048 + 3 * 2048      # 16.8 M
    assert fam.dense_ff_params(cfg) == 3 * 2048 * 11776                      # 72.3 M
    assert fam.expert_params(cfg) == 3 * 2048 * 1536                         # 9.44 M
    assert fam.kv_bytes_per_token(cfg) == 2 * 2 * 8 * 64 * 2 == 4096         # 2 layers of 10
    assert fam.conv_state_bytes_per_row(cfg) == 8 * 2 * 2048 * 2
    # all 64 experts: the whole tree but norms and taps' rounding, 10.5 GB
    whole = fam.weight_bytes(cfg)
    assert whole == 2 * (2 * 10485760 + 8 * 16783360 + 2 * 72351744 + 8 * 64 * 9437184
                         + 2048 * 65536) + 4 * 8 * 2048 * 64
    assert 10.5e9 < whole < 10.6e9
    # uniform routing: 64 (1 - (60/64)^rows)
    assert fam.experts_touched(cfg, 1) == pytest.approx(4.0)
    assert fam.experts_touched(cfg, 4.3) == pytest.approx(64 * (1 - (60 / 64) ** 4.3))
    assert fam.experts_touched(cfg, 64) == pytest.approx(64 * (1 - (60 / 64) ** 64))
    flops, bytes_ = fam.decode_step_cost(cfg, 4.3, 400)
    touched = fam.experts_touched(cfg, 4.3)
    assert bytes_ == pytest.approx(
        whole - 2 * 8 * (64 - touched) * 9437184 + 4.3 * 401 * 4096 + 2 * 4.3 * 65536)
    assert 3.1e9 < bytes_ < 3.3e9          # ISSUE.md: about 3.2 GB a step at 4.3 rows
    per_tok = 2 * (2 * 10485760 + 8 * 16783360 + 2 * 72351744
                   + 8 * (4 * 9437184 + 2048 * 64) + 2048 * 65536)
    assert flops == pytest.approx(4.3 * (per_tok + 2 * 32 * 4 * 64 * 400))
    # one grouped product: rows x 4 rows through one 2048 x 1536 matrix
    gf, gb = fam.grouped_product_cost(cfg, 4.3)
    assert gf == pytest.approx(2 * 4.3 * 4 * 2048 * 1536)
    assert gb == pytest.approx(touched * 2048 * 1536 * 2 + 4.3 * 4 * (2048 + 1536) * 2)
    assert fam.grouped_product_cost(cfg, 4.3, touched=12.0)[1] == \
        pytest.approx(12 * 2048 * 1536 * 2 + 4.3 * 4 * 3584 * 2)
    assert fam.sparse_period_repeats(cfg) == 2
    assert fam.sparse_period_repeats(dict(cfg, layer_types=cfg["published"]["layer_types"],
                                          num_hidden_layers=40)) == 1  # 38 = 9 x 4 + 2


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


def test_readers_return_nothing_where_the_program_writes_nothing(fam, tmp_path, monkeypatch):
    """The parent's program has no routing counters on its access lines
    and no op named after the grouped kernel: both readers say None."""
    touched = metrics.load_reader(BENCH, "moe.experts_touched.chat")
    roof = metrics.load_reader(BENCH, "moe.kernel_roofline.chat")
    obs = _Obs(cfg=_cfg(), family=fam, cell={"name": "no-such-cell"}, slots=64,
               trace={"device_ops": [["fusion.1_bf16_64_2048", 0.5]],
                      "ops_by_program": {"_chunk_impl": {"fusion.1_bf16_64_2048": 0.5}},
                      "modules": {"_chunk_impl": {"count": 10, "total_s": 1.0,
                                                  "median_s": 0.1}}},
               decode_steps=400.0, decode_dispatches=100.0, rows_per_step=4.0,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert touched.read(obs) is None and roof.read(obs) is None
    assert roof.read(_Obs(obs, trace=None)) is None


def _request_line(ended, layer_steps, touched, assignments, decode_ms=1000.0):
    """One access line of a request that ended at `ended` (unit's wall
    clock) with the engine's running routing counters at these values."""
    return "INFO:seldon_tpu.access:request " + json.dumps({
        "rid": 1, "outcome": "ok", "received_unix": ended - 0.1 - decode_ms / 1000.0,
        "executor_wait_ms": 1.0, "queue_wait_ms": 9.0, "device_wait_ms": 50.0,
        "first_token_held_ms": 40.0, "decode_ms": decode_ms,
        "moe_sparse_layer_steps": layer_steps, "moe_experts_touched": touched,
        "moe_assignments": assignments}) + "\n"


def test_kernel_roofline_reads_the_decode_products_by_name_and_shape(fam, tmp_path, monkeypatch):
    """Need and time are of the same seconds: rows and experts touched come
    from the counters between the slice's two ends (read off the lines of
    the requests that ended around each), not from the window's mean."""
    import time

    import _access
    import _moe
    roof = metrics.load_reader(BENCH, "moe.kernel_roofline.chat")
    cfg = _cfg()
    # every op of the slice by program and name, in seconds. An admission's
    # products are not counted: a prefill's longer list, nor the two-row
    # group of the shortest bucket (2 x 32 x 4 rows), which XLA numbers like
    # the decode program's own gmm.4
    ops = {"_chunk_impl": {
               "sort.45_f32_64_65536_1_0": 1.0,
               "gmm.5_bf16_256_2048_1_0_T_8_128_2_1_S_1_custom-call_s32": 0.06,
               "gmm.4_bf16_256_1536_1_0_T_8_128_2_1_S_1_custom-call_s32": 0.06},
           "_admit_impl": {
               "gmm.9_bf16_16384_1536_1_0_T_8_128": 0.2,
               "gmm.4_bf16_256_1536_1_0_T_8_128_2_1_S_1_custom-call_s32": 0.001}}
    a = time.perf_counter()
    wall = a + (time.time() - time.perf_counter())
    log = tmp_path / "unit.log"
    # 8 sparse layers x 80 steps a second; before the slice 2 rows a step
    # touch 8 experts, inside it (wall .. wall + 3) 3.5 rows touch 12,
    # after it 6 rows touch 20: lines end 1 s before, at each end, 1 s after
    # the slice and in its middle
    counters, t = [0, 0, 0], wall - 2.0
    text = _request_line(t, *counters)
    for seconds, rows, touched in ((2.0, 2.0, 8.0), (1.5, 3.5, 12.0), (1.5, 3.5, 12.0),
                                   (1.0, 6.0, 20.0)):
        n = 8 * 80 * seconds
        counters = [counters[0] + n, counters[1] + n * touched, counters[2] + n * rows * 4]
        t += seconds
        text += _request_line(t, *counters)
    log.write_text("startup {}\n" + text)
    monkeypatch.setattr(_access, "log_path", lambda obs: str(log))
    obs = _Obs(cfg=cfg, family=fam, cell={"name": "no-such-cell"}, slots=64,
               trace={"device_ops": [["sort.45_f32_64_65536_1_0", 1.0]],  # the ranking holds no gmm
                      "ops_by_program": ops, "slice": (a, a + 3.0),
                      "modules": {"_chunk_impl": {"count": 60, "total_s": 2.8,
                                                  "median_s": 0.044}}},
               decode_steps=400.0, decode_dispatches=100.0, rows_per_step=5.0,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert _moe.decode_grouped_ops(obs) == [(0.06, 2048), (0.06, 1536)]
    d = _moe.slice_delta(obs)
    assert d["moe_sparse_layer_steps"] == pytest.approx(8 * 240, rel=1e-3)
    assert d["moe_experts_touched"] / d["moe_sparse_layer_steps"] == pytest.approx(12.0, rel=1e-3)
    _, bytes_ = fam.grouped_product_cost(cfg, 3.5, 12.0)
    need = bytes_ / 819e9 * (60 * 4) * 2 * 2    # steps x repeats x listed products
    assert roof.read(obs) == pytest.approx(100.0 * need / 0.12, rel=1e-3)
    assert 0 < roof.read(obs) < 100
    # a slice whose end no line has reached yet, or no slice: nothing to read
    assert roof.read(_Obs(obs, trace=dict(obs.trace, slice=(a + 2.0, a + 5.0)))) is None
    assert roof.read(_Obs(obs, trace={k: v for k, v in obs.trace.items() if k != "slice"})) is None


def test_parity_limits_pass_every_sound_reading_and_reject_every_control_reading():
    """The configuration's `parity` numbers against the chip readings they
    were set from (PR 27: 8 weight seeds at the cell's size, the engine's
    tokens and the float8-grid control's): each limit lies between the
    two readings, with room on both sides, and either alone rejects the
    control."""
    import reference

    lim = reference.limits(_cfg()["parity"])
    with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr27.json")) as f:
        rs = json.load(f)
    assert len({r["weights_seed"] for r in rs}) == len(rs) >= 8
    assert (lim["epsilon"], lim["epsilon_all"]) == (0.75, 1.5)   # what the readings counted at
    for r in rs:
        n = r["positions"]
        assert r["within_0.75"] / n >= lim["min_share_within"]
        assert r["over_1.5"] <= lim["max_over_epsilon_all"]
        assert r["control_within_0.75"] / n < lim["min_share_within"]      # by the share alone
        assert r["control_over_1.5"] > lim["max_over_epsilon_all"]         # and by the count alone
    sound_low = min(r["within_0.75"] for r in rs)
    control_high = max(r["control_within_0.75"] for r in rs)
    asked = lim["min_share_within"] * 48
    assert control_high + 20 < asked <= sound_low - 4
    assert lim["epsilon_all"] >= 2 * max(r["widest_gap"] for r in rs)
    assert lim["epsilon_all"] <= 0.6 * min(r["control_widest_gap"] for r in rs)
    assert lim["max_over_epsilon_all"] == max(r["over_1.5"] for r in rs) + 1
    assert min(r["control_over_1.5"] for r in rs) >= 10 * lim["max_over_epsilon_all"]
