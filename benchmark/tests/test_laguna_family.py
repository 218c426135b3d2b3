"""The laguna family (benchmark/families/laguna.py), its configuration
laguna-xs.2, the traffic mix `code` and the cell laguna.code: found by
name with nothing edited, the key map onto the program's ModelConfig,
every published number kept, the closed forms against values worked out
by hand from the published widths, the control's grid, the cell's rate and
limits, the three readers the cell adds, and the parity limits against
the chip readings they were set from."""
import json
import os
import re
import time

import pytest

import family
import metrics
import traffic
from test_access import BENCH, N, cell, read, request_line  # noqa: F401  (cell: fixture)

ROOT = os.path.dirname(BENCH)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "laguna-xs.2", "laguna.code"
MINE = ["attn.window_read_share.code", "attn.prefill_roofline.code",
        "moe.experts_touched.code"]


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return family.load(BENCH, _cfg())


def test_discovery_finds_family_traffic_cell_and_readers_by_name(fam):
    cfg, bench = _cfg(), _bench()
    assert family.name_of(cfg) == "laguna"
    assert fam.__file__ == os.path.join(BENCH, "families", "laguna.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)
    assert fam.CONTROL == "float8 e4m3 grid"
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
    (cell_,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert len({w["name"] for w in bench["workloads"]}) == len(bench["workloads"]) >= 6
    assert (cell_["name"], cell_["traffic"], cell_["chips"]) == (CELL, "code", 1)
    assert len(cell_["why"]) <= 200 and len(entry["why"]) <= 200
    # found by name, wherever later cells and metrics were appended
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = [by_name[n] for n in MINE]
    assert all(m["workloads"] == [CELL] for m in mine)
    for e in mine:  # each reader agrees with its entry
        mod = metrics.load_reader(BENCH, e["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])
    assert [(m["better"], m["source"]) for m in mine] == [
        ("lower", "program_counter"), ("higher", "device_trace"), ("lower", "program_counter")]


def test_the_traffic_is_the_issues_to_the_letter():
    spec = traffic.load_traffic(BENCH, "code", CELL)
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 1536, "sigma": 0.7,
                                     "min": 256, "max": 3584}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                                     "min": 16, "max": 256}
    assert (spec["window_tokens"], spec["stratify"], spec["lead_in_s"], spec["tail_s"],
            spec["trace_s"]) == (4096, 8, 8.0, 15.0, 3.0)
    assert _cfg()["serving"]["kv_budget_tokens"] // spec["window_tokens"] == 32
    # every bucket of the engine from 512 up, and none below: the harness's
    # own probes are the first to reach 32 and 128
    buckets = [32, 128, 512, 1024, 2048, 4096]
    assert traffic.buckets_reached(spec, 51, buckets) == [512, 1024, 2048, 4096]
    pairs = traffic.multiset(spec, round(spec["rate_rps"] * 51))
    assert all(p + o + 1 <= 4096 for p, o in pairs)
    assert min(p for p, _ in pairs) >= 256 and max(p for p, _ in pairs) <= 3584
    two_windows = sum(1 for p, _ in pairs if p >= 1024) / len(pairs)
    assert two_windows > 0.6  # most prompts are several windows long


def test_every_published_number_is_kept_and_the_cut_is_depth_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2"]
    cfg, pub = _cfg(), row["config"]
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in pub.items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"])
    assert cfg["published"] == {k: pub[k] for k in cfg["reduced"]}
    assert cfg["num_hidden_layers"] == 5
    for k in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert cfg[k] == pub[k][:5]   # the published lists' first five
    assert cfg["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"][1:5] == pub["layer_types"][5:9]  # one whole period
    assert sorted(cfg["assumed"])[:5] != [] and all(
        any(cfg["assumed"][k].startswith(f"({c})") for k in cfg["assumed"]) for c in "abcde")
    assert "pipeline stages" in cfg["serving"]["deployment"]


def test_the_cell_runs_at_the_rate_its_why_names_and_holds_both_limits():
    """benchmark/cells/laguna.code.json against the cell's entry: the rate
    the entry's `why` names, at the share of the knee beside it; `limit`
    2.2 x the TTFT and 2 x the TPOT read at that rate (`limit_from`)."""
    (cell_,) = [w for w in _bench()["workloads"] if w["name"] == CELL]
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        over = json.load(f)
    m = re.search(r"([0-9.]+) req/s \(([0-9.]+) of its knee, ~?([0-9.]+)\)", cell_["why"])
    assert m, cell_["why"]
    rate, fraction, knee = (float(g) for g in m.groups())
    assert rate == over["rate_rps"]
    assert fraction in (0.3, 0.4)
    assert rate == pytest.approx(fraction * knee, abs=0.051)  # rates go by 0.1
    assert sorted(over["limit"]) == sorted(over["limit_from"]) == ["tpot_ms", "ttft_ms"]
    for key, times in (("ttft_ms", 2.2), ("tpot_ms", 2.0)):
        assert over["limit"][key] == pytest.approx(times * over["limit_from"][key], rel=0.05)
    for word in ("five of forty", "head", "46 %"):
        assert word in cell_["why"], word


def test_key_map_gives_the_kinds_fields_and_survives_a_json_round_trip(fam):
    import dataclasses

    from seldon_tpu.models.config import ModelConfig

    kw = fam.model_config_kwargs(_cfg())
    assert kw["layer_types"] == _cfg()["layer_types"] and isinstance(kw["layer_types"], list)
    assert (kw["d_model"], kw["d_ff"], kw["d_ff_expert"], kw["d_ff_shared"], kw["n_experts"],
            kw["n_experts_per_token"], kw["n_dense_layers"]) == (2048, 8192, 512, 512, 256, 8, 1)
    assert (kw["n_heads"], kw["n_heads_window"], kw["n_kv_heads"], kw["head_dim"],
            kw["sliding_window"], kw["attn_gate"]) == (48, 64, 8, 128, 512, True)
    assert (kw["rope_theta"], kw["rope_theta_window"], kw["rotary_share"],
            kw["rope_scaling_type"], kw["rope_scaling_factor"],
            kw["rope_scaling_original_max_position"], kw["rope_scaling_beta_fast"],
            kw["rope_scaling_beta_slow"], kw["rope_attention_factor"]) == \
        (500000.0, 10000.0, 0.5, "yarn", 64.0, 4096, 64.0, 1.0, 1.4158883083359672)
    assert (kw["router"], kw["router_norm_topk"], kw["router_scale"], kw["tie_embeddings"],
            kw["vocab_size"]) == ("softmax", True, 2.5, False, 100352)
    model = ModelConfig(**kw).validate()   # what launcher.register_preset does
    served = json.loads(json.dumps(dataclasses.asdict(model)))  # what /metadata serves
    assert [k for k, v in kw.items() if served.get(k) != v] == []  # run.check_metadata
    assert (model.n_attn_layers, model.n_window_layers, model.n_sparse_layers) == (2, 3, 4)
    for key, bad, said in (
            ("gating", False, "the gate"), ("attention_bias", True, "no attention bias"),
            ("num_hidden_layers", 6, "num_hidden_layers layers"),
            ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse", "sparse"],
             "leading dense layers"),
            ("num_attention_heads_per_layer", [48, 64, 56, 64, 48], "differ in their head count")):
        with pytest.raises(ValueError, match=said):
            fam.model_config_kwargs(dict(_cfg(), **{key: bad}))


def test_closed_forms_against_hand_values(fam):
    cfg = _cfg()
    assert fam.layer_counts(cfg) == {"full": 2, "sliding": 3, "sparse": 4, "dense": 1}
    # q + o at the kind's heads, k + v at 8 KV heads, the gate [2048, heads]
    assert fam.attn_params(cfg, "full_attention") == \
        2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48                       # 29.46 M
    assert fam.attn_params(cfg, "sliding_attention") == \
        2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64                       # 37.88 M
    assert fam.dense_ff_params(cfg) == 3 * 2048 * 8192                      # 50.33 M
    assert fam.expert_params(cfg) == fam.shared_params(cfg) == 3 * 2048 * 512  # 3.146 M
    assert fam.router_params(cfg) == 2048 * 256
    assert fam.kv_bytes_per_token_layer(cfg) == 2 * 8 * 128 * 2 == 4096     # 4 KB a token and layer
    # the whole tree: 3869.8 M parameters, 7.74 GB at 2 bytes (routers float32)
    # (a step gathers a few rows of the embedding: the closed form leaves it out)
    whole = fam.weight_bytes(cfg) + 2 * 100352 * 2048
    assert whole == pytest.approx(7.744e9, rel=2e-3)
    # what a step reads whatever its rows: attention 0.345, head 0.411, dense 0.101,
    # the 4 shared experts + routers 0.029 GB
    fixed = fam.weight_bytes(cfg, touched=0)
    assert fixed == pytest.approx(0.890e9, rel=2e-3)  # 0.885 with the routers at 2 bytes
    assert 2 * 2048 * 100352 / fixed == pytest.approx(0.46, abs=0.01)       # the head: 46 %
    assert fam.experts_touched(cfg, 1) == pytest.approx(8.0)
    assert 8 < fam.experts_touched(cfg, 2) < 16
    # one live row: 8 experts x 4 sparse layers x 6.29 MB = 0.20 GB
    assert fam.weight_bytes(cfg, touched=8) - fixed == pytest.approx(0.2013e9, rel=1e-3)


@pytest.mark.parametrize("context,inside", [(100, 100), (512, 512), (1600, 512), (4000, 512)])
def test_decode_step_cost_counts_the_window_on_the_window_layers(fam, context, inside):
    """Under the window both kinds read the context; over it a sliding
    layer reads 512 tokens whatever the context, at its own 64 heads."""
    cfg = _cfg()
    rows = 5.0
    flops, bytes_ = fam.decode_step_cost(cfg, rows, context)
    attn = 4 * 128 * (2 * 48 * context + 3 * 64 * inside)
    assert flops == pytest.approx(rows * (fam.flops_per_token(cfg) + attn))
    kv = (2 * (context + 1) + 3 * (inside + 1)) * 4096
    assert bytes_ == pytest.approx(
        fam.weight_bytes(cfg, fam.experts_touched(cfg, rows)) + rows * kv)
    # 8 experts a row on each of the 4 sparse layers, the shared one, the routers
    per_token = fam.flops_per_token(cfg) / 2
    assert per_token == fam.fixed_params(cfg) + 4 * (8 * 3 * 2048 * 512 + 2048 * 256)


def test_prefill_attention_flops_are_banded_on_the_window_layers(fam):
    cfg = _cfg()
    assert fam.causal_pairs(4) == 10 and fam.banded_pairs(4, 512) == 10
    assert fam.banded_pairs(600, 512) == 512 * 513 // 2 + 88 * 512
    # under the window both kinds are causal
    assert fam.prefill_attention_flops(cfg, 300) == \
        4 * 128 * fam.causal_pairs(300) * (2 * 48 + 3 * 64)
    # S = 4096: a sliding layer 4 x S x 512 x 8192 = 69 GFLOP less the first
    # window's triangle, where all causal keys would be 275
    s = 4096
    band = 4 * 128 * 64 * fam.banded_pairs(s, 512)
    assert band == pytest.approx(4 * s * 512 * 8192, rel=0.07)
    assert 4 * 128 * 64 * fam.causal_pairs(s) == pytest.approx(275e9, rel=0.01)
    assert fam.prefill_attention_flops(cfg, s) == \
        2 * 4 * 128 * 48 * fam.causal_pairs(s) + 3 * band


def test_the_control_is_the_float8_grid_and_leaves_router_gate_and_norms_alone(fam):
    import jax.numpy as jnp
    fam._need_jax()
    w = jnp.asarray([0.02173, -0.3, 1.0, 500.0, 3e-4], jnp.float32)
    got = [float(x) for x in fam._mat(w, True)]
    assert got == [0.021484375, -0.3125, 1.0, 448.0, 0.0]
    assert [float(x) for x in fam._mat(w, False)] == [float(x) for x in w]


def _obs_with(obs, **more):
    obs.update(more)
    return obs


def counted(obs, unwindowed, read_):
    """Window lines whose request i ended with the two counters at
    (i + 1) x the step; a lead-in line before the window carries numbers
    that must not be read."""
    off = time.time() - time.perf_counter()
    out = [request_line(940, obs.t0 + off - 1.0, attn_window_tokens_unwindowed=7,
                        attn_window_tokens_read=7)]
    for i in range(N):
        out.append(request_line(
            i, obs.t0 + off + 10.0 * (i + 0.5) / N,
            attn_window_tokens_unwindowed=10**9 + (i + 1) * unwindowed,
            attn_window_tokens_read=10**7 + (i + 1) * read_,
            moe_sparse_layer_steps=400 * (i + 1), moe_experts_touched=14000 * (i + 1),
            moe_assignments=16000 * (i + 1)))
    return out


@pytest.mark.parametrize("unwindowed,read_,want", [
    (3 * 5 * 1600, 3 * 5 * 512, 32.0),    # five rows at 1600: two blocks of 256 each
    (3 * 5 * 300, 3 * 5 * 512, 170.667),  # short rows read their whole blocks
])
def test_window_read_share_on_a_recorded_access_line(cell, unwindowed, read_, want):
    obs, _, work = cell
    (work / "unit.log").write_text("\n".join(counted(obs, unwindowed, read_)) + "\n")
    assert read("attn.window_read_share.code", obs) == pytest.approx(want, rel=1e-4)
    assert read("moe.experts_touched.code", obs) == pytest.approx(35.0)
    assert read("attn.kv_read_share.chat", obs) is None  # those two fields are not on these lines


def test_the_new_readers_read_nothing_of_a_program_without_the_counters(cell):
    obs, write, work = cell
    for name in MINE:
        assert read(name, obs) is None          # the parent's lines: no such fields, no trace
        assert read(name, metrics.Obs()) is None
    os.remove(work / "unit.log")
    for name in MINE:
        assert read(name, obs) is None


def test_prefill_roofline_from_a_traced_slice(fam, cell):
    """Three admissions of 1536 tokens in the slice, the kernel's two
    instructions of the admission program at 10 ms in all: need =
    3 x prefill_attention_flops(1536) / 197 TFLOP/s."""
    from client import Result
    from traffic import Request

    obs, _, _ = cell
    t = obs.t0 + 4.0
    results = [Result(Request(i, "window", 1536, 64, 0.0), due=t, sent=t, first=t + 0.1 * i,
                      last=t + 1.0, tokens=[1] * 64) for i in range(4)]
    trace = {"slice": (t - 0.05, t + 0.25), "modules": {},
             "ops_by_program": {
                 "_admit_impl": {"prefill_attention.3_bf16_2_2048_8192_2_1_0_T_8_128": 0.006,
                                 "prefill_attention.2_bf16_2_2048_6144_2_1_0_T_8_128": 0.004,
                                 "fusion.12": 0.5},
                 "_chunk_impl": {"prefill_attention.9_bf16": 9.0}}}
    full = _obs_with(obs, all_results=results, trace=trace, family=fam, cfg=_cfg(),
                     peaks={"bf16_flops": 197e12})
    need = 3 * fam.prefill_attention_flops(_cfg(), 1536) / 197e12
    assert read("attn.prefill_roofline.code", full) == pytest.approx(100 * need / 0.010)
    assert 0 < 100 * need / 0.010 < 100
    # fewer than three admissions in the slice, or no kernel by that name: nothing
    assert read("attn.prefill_roofline.code", _obs_with(obs, all_results=results[:2])) is None
    bare = dict(trace, ops_by_program={"_admit_impl": {"fusion.12": 0.5}})
    assert read("attn.prefill_roofline.code",
                _obs_with(obs, all_results=results, trace=bare)) is None


def test_parity_limits_pass_every_sound_reading_and_reject_every_control_reading():
    """The configuration's `parity` numbers against the chip readings they
    were set from (PR 43: the unit served through benchmark/launcher.py,
    greedy probes of 300 to 3000 tokens, alone and in admission groups of
    two, judged by reference.logit_gaps; tools/parity_gaps.py --serve):
    each limit lies between the sound readings and the control's, with
    room on both sides, and either alone rejects the control at every
    weight seed read, on the long probes as on all of them."""
    import reference

    lim = reference.limits(_cfg()["parity"])
    with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr43.json")) as f:
        rs = json.load(f)
    assert len({r["weights_seed"] for r in rs}) == len(rs) >= 4
    assert all(r["config"] == NAME for r in rs)
    eps, eps_all = lim["epsilon"], lim["epsilon_all"]
    sound_low, control_high, sound_over, control_over = 1.0, 0.0, 0, 10**9
    for r in rs:
        assert {300, 700, 1500, 3000} <= set(r["probe_lens"])
        assert set(r["modes"]) == {"alone", "group"}
        assert any(v.startswith("admit/4096/") for v in r["variants"])
        assert any(v.endswith("/2") for v in r["variants"])  # groups did form
        n = r["new"]
        assert len(r["gaps"]) == len(r["control_gaps"]) == n * len(r["probe_lens"])
        # the harness judges 48 positions at a time; a probe's 32 are judged alike here
        for i, plen in enumerate(r["probe_lens"]):
            part, cpart = (x[i * n:(i + 1) * n] for x in (r["gaps"], r["control_gaps"]))
            share, cshare = (sum(g <= eps for g in x) / n for x in (part, cpart))
            over, cover = (sum(g > eps_all for g in x) for x in (part, cpart))
            assert share >= lim["min_share_within"] and over <= lim["max_over_epsilon_all"], plen
            assert cshare < lim["min_share_within"], plen          # by the share alone
            assert cover > lim["max_over_epsilon_all"], plen        # and by the count alone
            sound_low, control_high = min(sound_low, share), max(control_high, cshare)
            sound_over, control_over = max(sound_over, over), min(control_over, cover)
        assert r["logit_std"] > 0.5  # epsilon is no free pass
    # each limit between its two readings, with room on both sides
    assert control_high + 0.1 <= lim["min_share_within"] <= sound_low - 0.05
    assert sound_over + 1 <= lim["max_over_epsilon_all"] <= control_over - 2
