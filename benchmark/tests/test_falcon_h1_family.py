"""The falcon_h1 family (benchmark/families/falcon_h1.py) and its
configuration falcon-h1-34b-instruct: found by name, the key map onto the
program's ModelConfig (the multipliers among it), the cut as the file
states it, the closed forms against the byte arithmetic of ISSUE 38 worked
out by hand from the published widths, the reference against itself under
the control, the two readers its cell adds, and the parity limits against
the readings they were set from."""
import json
import os
import re

import pytest

import family
import metrics

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "falcon-h1-34b-instruct", "falconh1.chat"
READERS = ["h1.ssm_update_roofline.chat", "h1.mixer_share.chat"]


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return family.load(BENCH, _cfg())


def test_the_configuration_names_its_family_and_the_loader_finds_the_file(fam):
    cfg = _cfg()
    assert family.name_of(cfg) == "falcon_h1"
    assert fam.__file__ == os.path.join(BENCH, "families", "falcon_h1.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)
    assert fam.CONTROL == "float8 e4m3 grid"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (CELL, "chat", 1)
    assert len(cell["why"]) <= 200
    # found by name, wherever later cells and metrics were appended
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert set(READERS) <= set(mine)
    for name in mine:  # each reader agrees with its entry
        (e,) = [m for m in bench["per_layer"] if m["name"] == name]
        mod = metrics.load_reader(BENCH, name)
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])
        assert (e["moves"], e["source"], e["unit"]) == ("tpot_mid80_ms", "device_trace", "%")
    # the accepted metric of the other Mamba-2 cell keeps its list
    (old,) = [m for m in bench["per_layer"] if m["name"] == "ssm.update_roofline.chat"]
    assert old["workloads"] == ["nemotron3.chat"]


def test_every_published_number_is_kept_and_the_cut_is_depth_alone():
    cfg = _cfg()
    assert cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["num_hidden_layers"] == 5  # the period is one layer; the floor is four
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]) == \
        (5120, 20, 4, 128, 21504, 261120)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_chunk_size"]) == \
        (32, 128, 2, 256, 4, 128)
    for key in ("deployment", "why"):
        assert len(cfg["serving"][key]) > 100 and "TODO" not in cfg["serving"][key]
    assert "TODO" not in json.dumps(cfg)
    for key in ("weights", "mamba_d_ssm_and_mamba_expand", "conv_state"):
        assert key in cfg["assumed"], key
    assert cfg["serving"]["ssm_state_dtype"] == "float32"
    assert (cfg["serving"]["kv_budget_tokens"], cfg["serving"]["window_tokens"]) == (65536, 1024)
    assert cfg["rehearse_preset"] == "tiny-falcon-h1"
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct"]
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    missing = object()
    differ = sorted(k for k, v in pub.items() if cfg.get(k, missing) != v)
    assert differ == cfg["reduced"]
    assert {k: pub[k] for k in cfg["reduced"]} == cfg["published"]


def test_the_cell_runs_at_the_rate_its_why_names_and_holds_both_limits():
    """benchmark/cells/falconh1.chat.json against the cell's entry: the
    rate is the number the `why` names and the stated fraction of the
    stated knee; `limit` is 2.2 x the TTFT and 2 x the TPOT read at that
    rate (`limit_from`)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (cell,) = [w for w in json.load(f)["workloads"] if w["name"] == CELL]
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        over = json.load(f)
    m = re.search(r"at ([0-9.]+) req/s \(([0-9.]+) of its knee, ~?([0-9.]+)\)", cell["why"])
    assert m, cell["why"]
    rate, fraction, knee = (float(g) for g in m.groups())
    assert rate == over["rate_rps"]
    assert fraction in (0.6, 0.4)           # ISSUE 38: 0.6, or 0.4 if 0.6 does not repeat
    assert rate == pytest.approx(fraction * knee, abs=0.051)  # rates go by 0.1
    assert sorted(over["limit"]) == sorted(over["limit_from"]) == ["tpot_ms", "ttft_ms"]
    for key, times in (("ttft_ms", 2.2), ("tpot_ms", 2.0)):
        assert over["limit"][key] == pytest.approx(times * over["limit_from"][key], rel=0.05)
    for said in ("4 MB", "head", "host"):   # what ISSUE 38 asks the why to state
        assert said in cell["why"], said


def test_key_map_gives_the_new_kind_and_the_multipliers_and_survives_a_json_round_trip(fam):
    import dataclasses

    from seldon_tpu.models.config import ModelConfig

    cfg = _cfg()
    kw = fam.model_config_kwargs(cfg)
    assert kw["layer_types"] == ["attention_mamba"] * 5 and isinstance(kw["layer_types"], list)
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"], kw["d_ff"],
            kw["vocab_size"], kw["tie_embeddings"], kw["rope_theta"]) == \
        (5120, 20, 4, 128, 21504, 261120, False, 1e11)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"], kw["ssm_state"],
            kw["ssm_chunk"], kw["conv_kernel"]) == (32, 128, 2, 256, 128, 4)
    assert (kw["embed_mult"], kw["logits_mult"], kw["attn_in_mult"], kw["attn_out_mult"],
            kw["key_mult"], kw["ssm_in_mult"], kw["ssm_out_mult"]) == \
        (5.656854249492381, 0.0078125, 1.0, 0.0375, 0.011048543456039804, 0.25,
         0.08838834764831845)
    assert kw["ssm_mults"] == [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                               0.3535533905932738]
    assert (kw["mlp_gate_mult"], kw["mlp_down_mult"]) == (0.1767766952966369,
                                                          0.011160714285714284)
    assert all(isinstance(kw[k], float) for k in kw if k.endswith("_mult"))  # 1 -> 1.0
    model = ModelConfig(**kw).validate()   # what launcher.register_preset does
    served = json.loads(json.dumps(dataclasses.asdict(model)))  # what /metadata serves
    assert [k for k, v in kw.items() if served.get(k) != v] == []  # run.check_metadata
    # the layer is counted once under each kind of state
    assert (model.n_attn_layers, model.n_mamba_layers, model.n_conv_layers,
            model.n_sparse_layers) == (5, 5, 0, 0)
    assert (model.ssm_inner, model.ssm_conv_dim, model.q_per_kv) == (4096, 5120, 5)
    with pytest.raises(ValueError, match="no bias"):
        fam.model_config_kwargs(dict(cfg, projectors_bias=True))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        fam.model_config_kwargs(dict(cfg, mamba_d_ssm=10240))
    with pytest.raises(ValueError, match="gates, then norms"):
        fam.model_config_kwargs(dict(cfg, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="five values"):
        fam.model_config_kwargs(dict(cfg, ssm_multipliers=[1.0]))
    with pytest.raises(ValueError, match="float32"):
        fam.model_config_kwargs(dict(cfg, serving=dict(cfg["serving"], ssm_state_dtype="bf16")))


def test_closed_forms_against_the_byte_arithmetic_of_the_issue(fam):
    cfg = _cfg()
    assert fam.layer_counts(cfg) == {"mamba": 5, "attention": 5, "dense": 5}
    assert not hasattr(fam, "slots_held")   # a need does not know how many slots the slab has
    assert (fam.ssm_inner(cfg), fam.ssm_conv_dim(cfg)) == (4096, 5120)
    assert fam.attn_params(cfg) == 5120 * (2560 + 512 + 512) + 2560 * 5120 == 31457280     # 31.46 M
    assert fam.mamba_params(cfg) == 5120 * 9248 + 4096 * 5120 + 5 * 5120 == 68346880       # 68.35 M
    assert fam.mlp_params(cfg) == 3 * 5120 * 21504 == 330301440                            # 330.30 M
    assert fam.layer_params(cfg) == 430105600                                              # 0.860 GB
    assert fam.head_params(cfg) == 261120 * 5120 == 1336934400                             # 2.67 GB
    # 72 layers + embedding and head: 33.6 B parameters, 67 GB at 2 bytes
    assert 72 * fam.layer_params(cfg) + 2 * fam.head_params(cfg) == pytest.approx(33.64e9, rel=1e-3)
    assert fam.kv_bytes_per_token(cfg) == 5 * 4 * 128 * 2 * 2 == 10240                     # 2 KB a layer
    assert fam.ssm_state_bytes_per_slot(cfg) == 5 * 32 * 128 * 256 * 4 == 5 * 4194304      # 4.19 MB a layer
    assert fam.conv_state_bytes_per_slot(cfg) == 5 * 3 * 5120 * 2 == 5 * 30720             # 31 KB a layer
    whole = fam.weight_bytes(cfg)
    assert whole == 2 * (5 * 430105600 + 1336934400) == 6974924800   # layers 4.30 GB + head 2.67 GB
    # what the chip holds: weights with the embedding 9.649, state 1.342, conv 0.010, KV 0.671
    held = whole + 2 * fam.head_params(cfg) + 64 * (
        fam.ssm_state_bytes_per_slot(cfg) + fam.conv_state_bytes_per_slot(cfg)
        + 1024 * fam.kv_bytes_per_token(cfg))
    assert held / 1e9 == pytest.approx(11.67, abs=0.01)
    # one layer's update over 64 slots: the state read and written, x B C dt in, y out
    uf, ub = fam.ssm_update_cost(cfg, 64)
    assert ub == 64 * (2 * 32 * 128 * 256 * 4 + (4096 + 1024) * 2 + 32 * 4 + 4096 * 4)
    assert uf == 64 * 5.0 * 32 * 128 * 256
    assert 0.53e9 < ub < 0.55e9            # 538 MB a layer a step were every slot live
    assert fam.ssm_update_cost(cfg, 2.0) == (uf * 2.0 / 64, ub * 2.0 / 64)   # linear in the slots
    # a step's need: the state of the 2 LIVE slots, not of the slab's 64 (ISSUE 38's 9.69 GB
    # held 2.68 GB of state; ISSUE 54: 9.69 -> 7.15 GB at 3.92 rows)
    flops, bytes_ = fam.decode_step_cost(cfg, 2.0, 400)
    assert bytes_ == pytest.approx(whole + 2.0 * 401 * 10240 + 2 * 2.0 * 5 * (4194304 + 30720))
    assert bytes_ / 1e9 == pytest.approx(7.07, abs=0.02)
    assert fam.decode_step_cost(cfg, 3.92, 400)[1] / 1e9 == pytest.approx(7.15, abs=0.03)
    assert 2 * 2.0 * 5 * 4194304 / bytes_ == pytest.approx(0.012, abs=0.002)   # the live state: 1 %
    assert 2 * fam.head_params(cfg) / bytes_ == pytest.approx(0.378, abs=0.005)  # the head: 38 %
    per_tok = 2 * (5 * 430105600 + 1336934400)
    assert flops == pytest.approx(2.0 * (per_tok + 5 * 20 * 4 * 128 * 400) + 5 * uf * 2.0 / 64)


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


def test_reference_against_itself_under_the_control_and_by_branch(fam):
    """At the rehearsal preset's size: the control moves the logits (every
    layer's matrices are on the float8 grid) and leaves the embedding and
    the head alone; either branch switched off moves them too; the head
    in blocks of columns is the head."""
    import jax
    import jax.numpy as jnp

    from seldon_tpu.models.config import get_config
    from seldon_tpu.models.transformer import init_params
    from tests.test_falcon_h1 import file_keys  # the preset under this family's key names

    model = get_config("tiny-falcon-h1")
    keys = file_keys(model)
    assert {k: v for k, v in fam.model_config_kwargs(keys).items()} == \
        {k: (list(getattr(model, k)) if isinstance(getattr(model, k), tuple)
             else getattr(model, k)) for k in fam.model_config_kwargs(keys)}
    params = init_params(model, jax.random.key(2))
    toks = jax.random.randint(jax.random.key(3), (21,), 0, model.vocab_size)
    sound = fam.forward_logits(params, toks, keys)
    assert sound.shape == (21, model.vocab_size) and sound.dtype == jnp.float32
    coarse = fam.forward_logits(params, toks, keys, control=True)
    assert 0.05 < float(jnp.max(jnp.abs(coarse - sound))) < 10.0
    for branches in ((True, False), (False, True)):
        part = fam.forward_logits(params, toks, keys, branches=branches)
        assert float(jnp.max(jnp.abs(part - sound))) > 0.5
    was, fam.HEAD_BLOCK = fam.HEAD_BLOCK, 100     # three blocks, the last ragged
    try:
        blocked = fam.forward_logits(params, toks, keys)
    finally:
        fam.HEAD_BLOCK = was
    assert float(jnp.max(jnp.abs(blocked - sound))) < 1e-5
    zero = jax.tree.map(jnp.zeros_like, params)   # a tree of another depth is refused
    short = {**zero, "segments": (tuple({k: v[:2] for k, v in pos.items()}
                                        for pos in zero["segments"][0]),)}
    with pytest.raises(ValueError, match="2 layers"):
        fam.forward_logits(short, toks, keys)


class _Obs(dict):
    def __getattr__(self, k):
        return self.get(k)


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
STATE = "f32_5_64_32_128_256_4_3_2_1_0_T_8_128"


def _obs(fam, chunk_ops, **more):
    return _Obs(dict(
        cfg=_cfg(), family=fam, cell={"name": "no-such-cell"}, slots=64,
        trace={"ops_by_program": {"_chunk_impl": chunk_ops,
                                  "_admit_impl": {"ssm_update.9_" + STATE: 0.9}},
               "modules": {"_chunk_impl": {"count": 100, "total_s": 6.8, "median_s": 0.068}}},
        decode_steps=400.0, decode_dispatches=100.0, rows_per_step=2.0, peaks=PEAKS), **more)


def test_the_two_readers_read_the_decode_programs_kernels_by_name(fam):
    roof = metrics.load_reader(BENCH, READERS[0])
    share = metrics.load_reader(BENCH, READERS[1])
    ops = {"ssm_update.10_" + STATE: 1.60, "decode_attention.10_bf16_64_20_128_2_1_0_T": 0.02,
           "fusion.12_bf16_64_21504_1_0_T_8_128": 2.0,
           "fusion.3_" + STATE: 0.5}          # the state's shape without the name: not counted
    obs = _obs(fam, ops)
    # the need is the 2 LIVE slots' (no access line here: the window's rows by /metrics)
    _, bytes_ = fam.ssm_update_cost(_cfg(), 2.0)
    need = bytes_ / 819e9 * 5 * 400            # memory-bound; 5 layers x 100 chunks x 4 steps
    assert roof.read(obs) == pytest.approx(100.0 * need / 1.60, rel=1e-6)
    assert 2.5 < roof.read(obs) < 2.6
    full = _obs(fam, ops, rows_per_step=64.0)  # every slot live: the reading of PR 38 to 53
    assert roof.read(full) == pytest.approx(32 * roof.read(obs)) and roof.read(full) < 100
    assert share.read(obs) == pytest.approx(100.0 * 1.62 / 6.8)
    # an admission's kernel of the same name is another program's
    assert roof.read(_obs(fam, {"fusion.3_" + STATE: 0.5})) is None
    assert share.read(_obs(fam, {"fusion.3_" + STATE: 0.5})) is None


def test_the_two_readers_return_nothing_where_the_program_has_nothing_to_read(fam):
    """No trace, no decode program, no kernel of the name (the jax.numpy
    branches), a family that prices no update or has other key names:
    None, and nothing raises."""
    roof = metrics.load_reader(BENCH, READERS[0])
    share = metrics.load_reader(BENCH, READERS[1])
    ops = {"ssm_update.10_" + STATE: 1.60}
    for reader in (roof, share):
        assert reader.read(_obs(fam, ops, trace=None)) is None
        assert reader.read(_obs(fam, ops, trace={"ops_by_program": {}, "modules": {}})) is None
        assert reader.read(_obs(fam, {"fusion.1_bf16_64_2048": 0.5})) is None
    assert roof.read(_obs(fam, ops, peaks=None)) is None
    assert roof.read(_obs(fam, ops, decode_dispatches=0.0)) is None
    for other in ("lfm2-24b-a2b", "nemotron-3-nano-30b-a3b", "mistral-7b-v0.3"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            raw = json.load(f)
        # another family's file read by this family's closed form, and the other way round
        assert roof.read(_obs(fam, ops, cfg=raw)) is None
        assert roof.read(_obs(fam, ops, family=family.load(BENCH, raw))) is None
    # the accepted reader of the other Mamba-2 cell finds nothing in this cell's file
    old = metrics.load_reader(BENCH, "ssm.update_roofline.chat")
    assert old.read(_obs(fam, ops)) is None


def test_parity_limits_pass_every_sound_reading_and_reject_every_control_reading():
    """The configuration's `parity` numbers against the chip readings they
    were set from (PR 38: the cell's size through the harness's own probes,
    the engine's tokens and the float8-grid control's): each limit lies
    between the two readings, with room on both sides, and either alone
    rejects the control at every weight seed read."""
    import reference

    lim = reference.limits(_cfg()["parity"])
    with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr38.json")) as f:
        rs = json.load(f)
    assert len({r["weights_seed"] for r in rs}) == len(rs) >= 6
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
    assert control_high + 4 <= asked <= sound_low - 2       # room on both sides
    assert lim["max_over_epsilon_all"] >= max(r["over"] for r in rs) + 1
    assert min(r["control_over"] for r in rs) >= 3 * max(lim["max_over_epsilon_all"], 1)
    # the logits are of the scale the criterion was made for: the reference's own
    # spread at the probes' positions, so that epsilon is no free pass
    assert min(r["logit_std"] for r in rs) > 0.5
