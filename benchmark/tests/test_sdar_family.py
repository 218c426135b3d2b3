"""The sdar family (benchmark/families/sdar.py) and its configuration
sdar-30b-a3b-chat: found by name, the key map onto the program's
ModelConfig, the closed forms against values worked out by hand from the
published widths, which position each teacher-forced row was decided
from, and the four readers its cell adds, on recorded observations."""
import json
import os

import pytest

import family
import metrics

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MINE = ["diff.tokens_per_pass.chat", "moe.block_touched.chat",
        "moe.block_kernel_roofline.chat", "diff.attn_roofline.chat"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return family.load(BENCH, _cfg())


def test_the_configuration_names_its_family_and_the_loader_finds_the_file(fam):
    cfg = _cfg()
    assert family.name_of(cfg) == "sdar"
    assert fam.__file__ == os.path.join(BENCH, "families", "sdar.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)
    assert fam.CONTROL == "float8 e4m3 grid"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "sdar-30b-a3b-chat"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == "sdar-30b-a3b-chat"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("sdar.chat", "chat", 1)
    # found by name, wherever later metrics of the cell were appended
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == ["sdar.chat"]]
    assert set(MINE) <= set(mine)
    for name in MINE:  # each reader agrees with its entry
        (e,) = [m for m in bench["per_layer"] if m["name"] == name]
        mod = metrics.load_reader(BENCH, name)
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])
        assert e["moves"] == "tpot_mid80_ms"


def test_every_published_number_is_kept_and_the_cut_is_depth_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat"]
    cfg, pub = _cfg(), row["config"]
    assert cfg["source"] == row["source_url"]
    assert sorted(k for k, v in pub.items() if cfg.get(k) != v) == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48} and cfg["num_hidden_layers"] == 7
    assert (cfg["num_experts"], cfg["vocab_size"]) == (128, 151936)


def test_the_key_map_gives_the_program_the_generation_procedure(fam):
    from seldon_tpu.models.config import ModelConfig

    kw = fam.model_config_kwargs(_cfg())
    model = ModelConfig(**kw).validate()
    assert (model.gen_block, model.denoise_steps, model.remask,
            model.denoise_threshold, model.mask_token_id) == \
        (4, 2, "sequential", None, 151669)
    assert model.layer_types == ("full_attention",) * 7 and model.qk_norm
    assert (model.n_experts, model.n_experts_per_token, model.expert_width,
            model.router, model.n_sparse_layers) == (128, 8, 768, "softmax", 7)
    assert (model.n_heads, model.n_kv_heads, model.head_dim) == (32, 4, 128)
    # what /metadata serves is what run.check_metadata compares with
    import dataclasses
    served = json.loads(json.dumps(dataclasses.asdict(model)))
    assert all(served[k] == v for k, v in kw.items())
    for wrong in ({"norm_topk_prob": False}, {"mlp_only_layers": [3]},
                  {"tie_word_embeddings": True}):
        with pytest.raises(ValueError):
            fam.model_config_kwargs({**_cfg(), **wrong})


def test_the_sizes_the_issue_works_out_by_hand(fam):
    cfg = _cfg()
    assert fam.attn_params(cfg) == 2048 * (4096 + 512 + 512) + 4096 * 2048
    assert fam.expert_params(cfg) == 3 * 2048 * 768
    layer = fam.attn_params(cfg) + fam.router_params(cfg) + 128 * fam.expert_params(cfg)
    assert round(layer / 1e6, 1) == 623.1
    assert round(2 * fam.head_params(cfg) * 2 / 1e9, 3) == 1.245  # embedding + head
    assert fam.kv_bytes_per_token(cfg) == 7 * 2048  # 2 KB a token and layer
    assert fam.sparse_period_repeats(cfg) == 7 and fam.passes_per_block(cfg) == 3
    # 128 (1 - (120/128)^(4 r)) at r = 1 .. 3 live slots of 4 positions, each
    # to 8 DISTINCT experts (the issue's 28, 51, 68 draw 32 r independent ones)
    assert [round(fam.experts_touched(cfg, 4 * r)) for r in (1, 2, 3)] == [29, 52, 69]


def test_a_pass_costs_what_its_parts_cost(fam):
    cfg = _cfg()
    # one live slot: 4/3 tokens a pass; a context of 384
    flops, bytes_ = fam.decode_step_cost(cfg, 4 / 3, 384.0)
    touched = fam.experts_touched(cfg, 4.0)
    want_bytes = (2 * 7 * (fam.attn_params(cfg) + touched * fam.expert_params(cfg))
                  + 4 * 7 * fam.router_params(cfg)
                  + 2 * (2 / 3) * fam.head_params(cfg)
                  + 384 * 7 * 2048 + 4 * 7 * 2048 / 3)
    assert bytes_ == pytest.approx(want_bytes, rel=1e-12)
    assert 2.0e9 < bytes_ < 2.7e9  # ~28 experts x 7 layers x 9.4 MB + 0.26 + 0.41 GB
    # rows scale the slots: three live slots read more experts, not 3 x
    _, b3 = fam.decode_step_cost(cfg, 4.0, 384.0)
    assert 1.5 * bytes_ < b3 < 3 * bytes_
    # memory-bound by far at one slot
    assert flops / PEAKS["bf16_flops"] < 0.2 * bytes_ / PEAKS["hbm_bytes_per_s"]
    # the concave count: priced at the mean of 2 and 4 slots it reads more
    # than the mean of the two prices
    mid = fam.experts_touched(cfg, 12.0)
    assert mid > (fam.experts_touched(cfg, 8.0) + fam.experts_touched(cfg, 16.0)) / 2


def test_the_two_kernels_closed_forms(fam):
    cfg = _cfg()
    flops, bytes_ = fam.grouped_product_cost(cfg, 12.0, 68.0)
    assert flops == 2.0 * 12 * 8 * 2048 * 768
    assert bytes_ == 68 * 2048 * 768 * 2 + 12 * 8 * (2048 + 768) * 2
    # 10 slot passes over 7 layers at a context of 512 (one block of 512
    # tokens read a slot and layer), 3 of them commits
    flops, bytes_ = fam.attention_cost(cfg, 10 * 7 * 512.0, 3 * 7 * 4.0, 10.0)
    assert flops == 4.0 * 32 * 128 * 4 * 10 * 7 * 512
    row = 2 * 4 * 128 * 2
    assert bytes_ == row * (10 * 7 * 512 + 3 * 7 * 4) + 10 * 7 * 4 * 2 * 32 * 128 * 2


@pytest.mark.parametrize("position,prompt_len,want", [
    (8, 8, 8), (9, 8, 8), (10, 8, 10), (11, 8, 10), (12, 8, 12),   # on a block
    (9, 9, 9), (10, 9, 9), (11, 9, 11),                            # tail 1
    (10, 10, 10), (11, 10, 10), (12, 10, 12),                      # tail 2
    (11, 11, 11), (12, 11, 12), (13, 11, 12),                      # tail 3
    (1, 1, 1), (2, 1, 1), (3, 1, 3), (4, 1, 4),                    # no whole block
    (5, 0, 4), (131, 0, 130),                                      # prompt_len unknown
])
def test_which_forward_a_position_was_decided_from(fam, position, prompt_len, want):
    assert fam.decided_from(position, prompt_len, 4, 2) == want


@pytest.mark.parametrize("known,conf,k,rule,threshold,want", [
    ([0, 0, 0, 0], [.1, .9, .8, .7], 2, "sequential", None, [0, 1]),
    ([1, 0, 1, 0], [.1, .9, .8, .7], 2, "sequential", 0.5, [1, 3]),
    ([0, 0, 0, 0], [.1, .9, .8, .7], 2, "low_confidence", None, [1, 2]),
    ([0, 0, 0, 0], [.5, .5, .5, .5], 2, "low_confidence", None, [0, 1]),
    ([0, 0, 0, 0], [.1, .9, .8, .7], 2, "low_confidence", 0.6, [1, 2, 3]),
    ([0, 0, 0, 0], [.1, .9, .8, .7], 2, "low_confidence", 0.85, [1, 2]),
    ([1, 1, 1, 0], [.1, .9, .8, .7], 2, "low_confidence", None, [3]),
])
def test_the_references_own_transfer(fam, known, conf, k, rule, threshold, want):
    assert fam.transfer([bool(x) for x in known], conf, k, rule, threshold) == want


# -- the four readers on recorded observations --------------------------------

def _lines(tmp_path, monkeypatch, rows):
    """unit.log with the given request lines, where _access looks."""
    import _access
    work = tmp_path / "chiprun_out" / "benchmark" / "sdar.chat"
    work.mkdir(parents=True)
    with open(work / "unit.log", "w") as f:
        for r in rows:
            f.write("INFO request " + json.dumps(r) + "\n")
    monkeypatch.setattr(_access, "log_path", lambda obs: str(work / "unit.log"))


def _obs(fam, **more):
    return metrics.Obs(cfg=_cfg(), family=fam, cell={"name": "sdar.chat"},
                       slots=64, peaks=PEAKS, **more)


def _row(i, t_unix, **counters):
    return {"rid": i, "received_unix": t_unix, "executor_wait_ms": 0.0,
            "queue_wait_ms": 0.0, "device_wait_ms": 0.0,
            "first_token_held_ms": 0.0, "decode_ms": 0.0, **counters}


def test_the_counter_readers_on_recorded_lines(fam, tmp_path, monkeypatch):
    import time
    metrics.load_reader(BENCH, MINE[0])  # puts layer_metrics on the path
    off = time.time() - time.perf_counter()
    rows = [_row(i, off + 10.0 + i, diff_slot_passes=300 * i,
                 diff_commit_passes=100 * i, diff_tokens_out=390 * i,
                 moe_sparse_layer_steps=700 * i, moe_experts_touched=47600 * i,
                 moe_assignments=700 * i * 96) for i in range(1, 6)]
    _lines(tmp_path, monkeypatch, rows)

    class R:  # a sampled request
        ok = True
    obs = _obs(fam, t0=10.5, t1=16.0, samples=[R()] * 5)
    assert metrics.load_reader(BENCH, "diff.tokens_per_pass.chat").read(obs) == \
        pytest.approx(1.3)
    assert metrics.load_reader(BENCH, "moe.block_touched.chat").read(obs) == \
        pytest.approx(68.0)
    # a model whose step is no pass: nothing to read
    other = metrics.Obs(obs, cfg={"num_experts_per_tok": 8, "assumed": {}})
    assert metrics.load_reader(BENCH, "moe.block_touched.chat").read(other) is None
    # a program that writes no such fields (the parent): nothing to read
    _lines(tmp_path / "parent", monkeypatch,
           [_row(i, off + 10.0 + i) for i in range(1, 6)])
    assert metrics.load_reader(BENCH, "diff.tokens_per_pass.chat").read(obs) is None
    assert metrics.load_reader(BENCH, "moe.block_touched.chat").read(obs) is None


def test_the_trace_readers_on_a_recorded_slice(fam, tmp_path, monkeypatch):
    import time
    metrics.load_reader(BENCH, MINE[0])
    off = time.time() - time.perf_counter()
    # the counters grow at a steady rate: 250 passes a second of three live
    # slots (12 token rows, 68 experts touched a layer), a context of 512
    def at(t):
        passes = 250.0 * t
        return dict(
            diff_slot_passes=3 * passes, diff_commit_passes=passes,
            diff_tokens_out=4 * passes,
            attn_kv_tokens_read=3 * passes * 7 * 512,
            attn_kv_rows_written=passes * 7 * 4,
            moe_sparse_layer_steps=7 * passes, moe_experts_touched=7 * passes * 68,
            moe_assignments=7 * passes * 96)
    rows = [_row(i, off + 100.0 + t, **at(t)) for i, t in enumerate((1.0, 9.0))]
    _lines(tmp_path, monkeypatch, rows)
    product, _ = __import__("costs").least_seconds(
        *fam.grouped_product_cost(_cfg(), 12.0, 68.0), PEAKS)
    attn_need, _ = __import__("costs").least_seconds(
        *fam.attention_cost(_cfg(), 3 * 500 * 7 * 512.0, 500 * 7 * 4.0, 1500.0), PEAKS)
    trace = {
        "slice": (103.0, 105.0),  # 500 passes
        "modules": {"_chunk_impl": {"count": 125, "median_s": 0.03, "total_s": 3.8}},
        "ops_by_program": {"_chunk_impl": {
            # the three grouped products, each at twice its need
            "gmm.12_bf16_2048_768_1_0_T_8_128_2_1_custom-call": 2 * product * 500 * 7,
            "gmm.13_bf16_2048_768_1_0_T_8_128_2_1_custom-call": 2 * product * 500 * 7,
            "gmm.14_bf16_2048_2048_1_0_T_8_128_2_1_custom-call": 2 * product * 500 * 7,
            # an autoregressive program's shape: not a pass's products
            "gmm.3_bf16_512_768_1_0_T_8_128_2_1_custom-call": 1.0,
            "decode_attention.12_bf16_64_128_128_2_1_0_T_8_128": 10 * attn_need,
            "fusion.371": 0.5}},
    }
    obs = _obs(fam, trace=trace, decode_steps=4000.0, decode_dispatches=1000.0)
    assert metrics.load_reader(BENCH, "moe.block_kernel_roofline.chat").read(obs) == \
        pytest.approx(50.0)
    assert metrics.load_reader(BENCH, "diff.attn_roofline.chat").read(obs) == \
        pytest.approx(10.0)
    # no such op in the program (the parent, another model): nothing to read
    bare = dict(trace, ops_by_program={"_chunk_impl": {"fusion.371": 0.5}})
    for name in MINE[2:]:
        assert metrics.load_reader(BENCH, name).read(_obs(
            fam, trace=bare, decode_steps=4000.0, decode_dispatches=1000.0)) is None
        assert metrics.load_reader(BENCH, name).read(_obs(fam)) is None


# -- the parity limits against the readings they were set from ----------------

def _readings():
    with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr52.json")) as f:
        return json.load(f)["readings"]


def _harness_sets(reading):
    """(sound, control) gaps as the harness reads them: 4 neighbouring
    probes' first 12 positions, 48 a set."""
    n, g, c = reading["new"], reading["gaps"], reading["control_gaps"]
    probes = [(g[i:i + n][:12], c[i:i + n][:12]) for i in range(0, len(g), n)]
    return [(sum((p[0] for p in probes[i:i + 4]), []),
             sum((p[1] for p in probes[i:i + 4]), []))
            for i in range(len(probes) - 3)]


def test_sound_readings_pass_and_control_readings_fail():
    """The rule benchmark/tests/test_parity_limits.py holds for the first
    two configurations, on this one's readings (PR 52, on the chip at the
    cell's size: 4 weight seeds x 15 probes x 16 positions, every prompt
    tail, alone and grouped): every sound set passes, every control set
    is rejected, and the share limit alone does it."""
    import reference
    par = _cfg()["parity"]
    lim = reference.limits(par)
    rs = _readings()
    assert len({r["seed"] for r in rs}) == len(rs) >= 4
    assert all(set(r["modes"]) == {"alone", "group"} for r in rs)
    assert {n % 4 for r in rs for n in r["probe_lens"]} == {0, 1, 2, 3}
    sets = [s for r in rs for s in _harness_sets(r)]
    assert len(sets) == 48
    for sound, control in sets:
        ok, share, over = reference.judge(sound, par)
        assert ok and share >= lim["min_share_within"] + 2 / 48 and over == 0
        ok, share, _ = reference.judge(control, par)
        assert not ok and share <= lim["min_share_within"] - 4 / 48
    # each limit lies between its two readings, with room on both sides
    widest = max(max(r["gaps"]) for r in rs)
    control_widest = min(max(r["control_gaps"]) for r in rs)
    assert 2 * widest <= lim["epsilon_all"] <= control_widest / 1.9
    assert all(reference.judge(r["gaps"], par)[0] for r in rs)  # all 240 of a seed
