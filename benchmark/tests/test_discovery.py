"""A configuration, a traffic mix, a per-layer metric, a cell and a model
family are added as new files and entries, with no edit to a file that is
there."""
import asyncio
import glob
import importlib
import importlib.util
import json
import os
import shutil

import pytest

import metrics
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# A made-up architecture: layers named by kind and a feed-forward width under
# keys the first family does not know, a bigram table for a forward pass,
# and costs that are known numbers.
BIGRAM_FAMILY = '''
CONTROL = "logits rounded to whole numbers"


def model_config_kwargs(cfg):
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["width"],
                n_layers=len(cfg["layer_types"]), n_heads=cfg["heads"],
                n_kv_heads=cfg["heads"], d_ff=cfg["ffn_width"],
                max_seq_len=cfg["positions"],
                weight_dtype=cfg["serving"]["weight_dtype"],
                kv_cache_dtype=cfg["serving"]["kv_cache_dtype"])


def build_params(cfg, seed):
    import jax
    v = cfg["vocab_size"]
    return {"table": 3.0 * jax.random.normal(jax.random.key(seed), (v, v))}


def forward_logits(params, tokens, cfg, control=False):
    import jax.numpy as jnp
    logits = jnp.take(params["table"], tokens, axis=0)
    return jnp.round(logits) if control else logits


def decode_step_cost(cfg, rows, context):
    return 197e9 * rows, 819e6 * len(cfg["layer_types"])
'''
BIGRAM_CONFIG = {
    "name": "bigram-3", "family": "bigram", "source": "made up for this test",
    "vocab_size": 97, "width": 64, "heads": 4, "ffn_width": 160, "positions": 512,
    "layer_types": ["conv", "conv", "full_attention"], "num_hidden_layers": 3,
    "serving": {"weight_dtype": "bf16", "kv_cache_dtype": "bf16", "kv_budget_tokens": 4096},
    "parity": {"epsilon": 0.25}, "rehearse_preset": "tiny"}


def _copy_of_the_checkout(tmp_path):
    """benchmark/ and BENCHMARK.json in a directory of the test's own."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return root, root / "benchmark", bench


def _load_from(b, module):
    """A harness module from the copy, under a name of its own."""
    spec = importlib.util.spec_from_file_location("copied_" + module, str(b / (module + ".py")))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_new_files_and_entries_make_a_new_cell(tmp_path, monkeypatch):
    # a copy of the checkout's benchmark, so the test edits nothing real
    root, b, bench = _copy_of_the_checkout(tmp_path)
    before = {p: (b / p).read_bytes()
              for p in ("run.py", "traffic.py", "metrics.py", "traffic/chat.json")}
    cfg = json.loads((b / "configs" / "mistral-7b-v0.3.json").read_text())
    cfg.update(name="new-model", num_hidden_layers=2)
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (b / "traffic" / "bursty.json").write_text(json.dumps({
        "rate_rps": 2.0, "lead_in_s": 1.0, "tail_s": 5.0, "window_tokens": 512,
        "stratify": 4,
        "prompt_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.5, "min": 16, "max": 128},
        "output_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 8, "max": 32}}))
    (b / "cells" / "new.bursty.json").write_text(json.dumps({"rate_rps": 3.0}))
    (b / "layer_metrics" / "e2e.ttft_max_ms.py").write_text(
        'UNIT = "ms"\nLAYER = "end to end"\nMOVES = "ttft_mid80_ms"\n\n\n'
        'def read(obs):\n    return max(obs.ttft_ms) if obs.ttft_ms else None\n')
    bench["configs"].append({"name": "new-model", "source": cfg["source"],
                             "file": "benchmark/configs/new-model.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "new.bursty", "config": "new-model",
                               "traffic": "bursty", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "e2e.ttft_max_ms", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "end to end",
                               "moves": "ttft_mid80_ms", "workloads": ["new.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    # the harness, loaded from the copy, finds all of it by name
    monkeypatch.syspath_prepend(str(b))
    import importlib
    import run as run_mod
    run_mod = importlib.reload(run_mod)
    monkeypatch.setattr(run_mod, "ROOT", str(root))
    monkeypatch.setattr(run_mod, "HERE", str(b))

    class Args:
        workload, seed, seconds, trace, rehearse = "new.bursty", 1, 10.0, 0, False
    r = run_mod.Run(Args)
    assert r.cfg["name"] == "new-model" and r.spec["rate_rps"] == 3.0
    assert r.slots == 65536 // 512
    reqs = traffic.open_loop(r.spec, 1, 10.0, 32768)
    assert sum(1 for q in reqs if q.phase == "window") == 30
    obs = metrics.Obs(ttft_ms=[1.0, 7.0], setup_s=3.0, samples=[])
    got = metrics.per_layer(bench, str(b), "new.bursty", obs)
    assert got["e2e.ttft_max_ms"] == {"value": 7.0, "unit": "ms"}
    assert "ttft_mid80_ms" in [e["name"] for e in bench["end_to_end"]
                             if metrics.applies(e, "new.bursty")]
    for p, content in before.items():  # nothing that was there has changed
        assert (b / p).read_bytes() == content


def test_every_benchmark_entry_has_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    names = {e["name"] for e in bench["end_to_end"]}
    for e in bench["per_layer"]:
        mod = metrics.load_reader(BENCH, e["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (e["unit"], e["layer"], e["moves"])
        assert e["moves"] in names
        assert mod.read(metrics.Obs()) is None  # nothing to read: nothing returned
    for e in bench["end_to_end"]:
        assert e["name"] in metrics.END_TO_END


def test_trace_readers_on_a_hand_made_observation():
    """The numbers of mistral7b.chat's first traced run (PR 23) through the
    readers: 43 decode chunks of 135.2 ms, 4 steps each, 5.5 live rows."""
    import family
    import peaks
    from client import Result
    from traffic import Request
    with open(os.path.join(BENCH, "configs", "mistral-7b-v0.3.json")) as f:
        cfg = json.load(f)
    done = [Result(Request(i, "window", 300, 128, 0.0), due=1.0, sent=1.0, first=2.0 + i,
                   last=6.0 + i, tokens=list(range(128))) for i in range(4)]
    obs = metrics.Obs(
        cfg=cfg, family=family.load(BENCH, cfg), peaks=peaks.peaks_for("TPU v5 lite"),
        rows_per_step=5.5,
        decode_steps=1256.0, decode_dispatches=314.0, samples=done, all_results=done,
        trace={"busy_s": 5.96, "window_s": 5.97, "slice": (1.5, 4.5), "modules": {
            "unnamed_most_run": {"count": 43, "total_s": 5.67, "median_s": 0.1352},
            "unnamed_other": {"count": 6, "total_s": 0.298, "median_s": 0.0457}}})
    read = lambda name: metrics.load_reader(BENCH, name).read(obs)
    assert read("step.decode_ms") == pytest.approx(33.8)
    assert 20.0 < read("step.decode_roofline") < 35.0          # memory-bound, far from 100
    # requests 0..2 saw their first token inside the slice: 900 prompt tokens
    assert read("step.prefill_ms_per_ktok.chat") == pytest.approx(1e3 * 0.298 / 0.9)
    assert read("device.idle_share.chat") == pytest.approx(100 * (1 - 5.96 / 5.97))
    assert metrics.load_reader(BENCH, "step.decode_ms").read(metrics.Obs()) is None


def test_a_second_family_arrives_as_files_and_entries(tmp_path, monkeypatch):
    import jax.numpy as jnp
    import numpy as np
    from seldon_tpu.models.config import PRESETS, ModelConfig

    root, b, bench = _copy_of_the_checkout(tmp_path)
    there = sorted(p for p in glob.glob(str(b / "**" / "*"), recursive=True)
                   if os.path.isfile(p))
    assert {str(b / p) for p in ("run.py", "launcher.py", "reference.py", "costs.py",
                                 "metrics.py", "family.py", "families/mistral.py",
                                 "configs/mistral-7b-v0.3.json", "configs/mixtral-8x7b.json",
                                 "layer_metrics/step.decode_roofline.py")} <= set(there)
    before = {p: open(p, "rb").read() for p in there}
    before[str(root / "BENCHMARK.json")] = json.dumps(bench).encode()

    # -- what a model_config PR adds: three files and two entries
    (b / "families" / "bigram.py").write_text(BIGRAM_FAMILY)
    (b / "configs" / "bigram-3.json").write_text(json.dumps(BIGRAM_CONFIG))
    (b / "cells" / "bigram.chat.json").write_text(json.dumps({"rate_rps": 2.0}))
    bench["configs"].append({"name": "bigram-3", "source": "made up",
                             "file": "benchmark/configs/bigram-3.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "bigram.chat", "config": "bigram-3",
                               "traffic": "chat", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ghost = dict(BIGRAM_CONFIG, name="ghost-1", family="ghost")
    (b / "configs" / "ghost-1.json").write_text(json.dumps(ghost))

    run_mod = _load_from(b, "run")
    monkeypatch.setattr(run_mod, "ROOT", str(root))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert run_mod.HERE == str(b)

    class Args:
        workload, seed, seconds, trace, rehearse = "bigram.chat", 2147484001, 10.0, 0, False
    r = run_mod.Run(Args)
    want_kw = dict(vocab_size=97, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=160,
                   max_seq_len=512, weight_dtype="bf16", kv_cache_dtype="bf16")

    # Run: the family by the configuration's key, from the new file
    assert r.family.__file__ == str(b / "families" / "bigram.py")
    assert r.obs.family is r.family and r.slots == 4096 // 1024
    assert r.family.model_config_kwargs(r.cfg) == want_kw

    # launcher.register_preset: the new key map, and nothing of the first family's
    launcher = _load_from(b, "launcher")
    try:
        assert launcher.register_preset(str(b / "configs" / "bigram-3.json")) == "bigram-3"
        preset = PRESETS["bigram-3"]
    finally:
        PRESETS.pop("bigram-3", None)
    assert preset == ModelConfig(**want_kw)

    # check_metadata compares what the unit serves with the new map's values
    class FakeUnit:
        def __init__(self, config):
            self.md = {"device": {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1},
                       "config": config,
                       "engine": {"max_slots": 4, "max_seq_len": 1024, "prompt_buckets": [32]}}

        async def get_json(self, path):
            return self.md
    import dataclasses
    served = dataclasses.asdict(preset)
    asyncio.run(r.check_metadata(FakeUnit(served)))
    assert r.vocab == 97 and r.obs.peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run_mod.BenchFailure, match="d_ff=176, configuration says 160"):
        asyncio.run(r.check_metadata(FakeUnit(dict(served, d_ff=176))))

    # the parity job: its digest covers the family's file, the child goes through it
    params = r.family.build_params(r.cfg, Args.seed % (2 ** 31 - 1))
    table = np.asarray(params["table"])
    prompt, toks = [5, 11, 90], []
    for _ in range(6):  # what a sound engine would return: the table's greedy walk
        toks.append(int(np.argmax(table[(prompt + toks)[-1]])))
    job = r.parity_job([(prompt, toks)])
    # the verdict stays in the checkout, whatever compile cache the machine shares
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "shared"))
    assert r.parity_job([(prompt, toks)]) == job
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert job["marker"] == str(root / ".jax_cache" / "benchmark_parity_bigram-3.json")
    assert job["config"] == str(b / "configs" / "bigram-3.json")
    with open(b / "families" / "bigram.py", "a") as f:
        f.write("# one more line\n")
    changed = r.parity_job([(prompt, toks)])
    assert changed["config_sha"] != job["config_sha"]  # the marker is stale: parity re-runs
    job_file = tmp_path / "job.json"
    job_file.write_text(json.dumps(changed))
    reference = _load_from(b, "reference")
    assert reference.main(str(job_file)) == 0
    with open(changed["marker"]) as f:
        m = json.load(f)
    assert (m["family"], m["config"], m["config_sha"]) == ("bigram", "bigram-3",
                                                           changed["config_sha"])
    assert m["ok"] and m["max_gap"] == 0.0 and m["positions"] == 6
    assert (m["over_epsilon_all"], m["max_over_epsilon_all"]) == (0, 0)
    assert m["control"]["weights"] == "logits rounded to whole numbers"
    gaps, _ = reference.logit_gaps(r.family, params, r.cfg, [(prompt, [t ^ 1 for t in toks])])
    assert not reference.judge(gaps, r.cfg["parity"])[0]  # another token: outside epsilon
    r.obs["parity"], r.obs["failed"], r.obs["attempted"] = m, 0, 20
    assert "family bigram" in r.compared()[1] and "(limit >= 1.0)" in r.compared()[1]
    assert "positions beyond 0.25: 0 (limit <= 0), widest gap 0.0;" in r.compared()[1]

    # step.decode_roofline asks the family for the step's cost
    from client import Result
    from traffic import Request
    done = [Result(Request(0, "window", 300, 128, 0.0), due=1.0, sent=1.0, first=2.0,
                   last=6.0, tokens=list(range(128)))]
    r.obs.update(rows_per_step=2.0, decode_steps=400.0, decode_dispatches=100.0, samples=done,
                 all_results=done,
                 trace={"busy_s": 0.9, "window_s": 1.0, "slice": (1.5, 2.5), "modules": {
                     "_chunk_impl": {"count": 9, "total_s": 0.9, "median_s": 0.1}}})
    # 2 rows x 197 GFLOP = 2 ms of compute, 3 x 819 MB = 3 ms of memory; a step is 25 ms
    got = metrics.per_layer(bench, str(b), "bigram.chat", r.obs)["step.decode_roofline"]
    assert got["value"] == pytest.approx(100.0 * 0.003 / 0.025, rel=1e-12)
    r.obs["family"] = None
    assert "step.decode_roofline" not in metrics.per_layer(bench, str(b), "bigram.chat", r.obs)

    # a family with no file: refused at the start, by the file's name
    bench["configs"].append({"name": "ghost-1", "source": "made up",
                             "file": "benchmark/configs/ghost-1.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ghost.chat", "config": "ghost-1",
                               "traffic": "chat", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    class GhostArgs(Args):
        workload = "ghost.chat"
    with pytest.raises(run_mod.BenchFailure) as e:
        run_mod.Run(GhostArgs)
    assert str(b / "families" / "ghost.py") in str(e.value)

    # and no byte of what was there has changed
    for p in there:
        assert open(p, "rb").read() == before[p], p
