"""A configuration, a traffic mix, a per-layer metric and a cell are added
as new files and entries, with no edit to a file that is there."""
import json
import os
import shutil

import pytest

import metrics
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_new_files_and_entries_make_a_new_cell(tmp_path, monkeypatch):
    # a copy of the checkout's benchmark, so the test edits nothing real
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("run.py", "traffic.py", "metrics.py", "traffic/chat.json")}
    b = root / "benchmark"
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
    import json
    import peaks
    from client import Result
    from traffic import Request
    with open(os.path.join(BENCH, "configs", "mistral-7b-v0.3.json")) as f:
        cfg = json.load(f)
    done = [Result(Request(i, "window", 300, 128, 0.0), due=1.0, sent=1.0, first=2.0 + i,
                   last=6.0 + i, tokens=list(range(128))) for i in range(4)]
    obs = metrics.Obs(
        cfg=cfg, peaks=peaks.peaks_for("TPU v5 lite"), rows_per_step=5.5,
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
