"""bench.py is one process: env gating of its phases, and the platform
gate — no TPU means a non-zero exit unless JAX_PLATFORMS=cpu is
explicit, and then the rate never carries a per-chip name."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_build_act_dtype_gating(monkeypatch):
    """BENCH_ACT (W8A8) only engages when weights are int8; BENCH_ACT
    and BENCH_WEIGHTS env reverts both stay honored."""
    monkeypatch.delenv("BENCH_WEIGHTS", raising=False)
    monkeypatch.delenv("BENCH_ACT", raising=False)
    b = _load_bench()
    assert b.ACT == "int8" and b.WEIGHTS == "int8"  # round-5 defaults
    _, cfg = b._build("tiny")
    assert cfg.weight_dtype == "int8" and cfg.act_dtype == "int8"
    monkeypatch.setenv("BENCH_WEIGHTS", "bf16")
    _, cfg2 = _load_bench()._build("tiny")
    # bf16 weights -> W8A8 must stay off regardless of ACT default.
    assert cfg2.weight_dtype == "bf16" and cfg2.act_dtype == "bf16"
    monkeypatch.delenv("BENCH_WEIGHTS")
    monkeypatch.setenv("BENCH_ACT", "bf16")
    _, cfg3 = _load_bench()._build("tiny")
    assert cfg3.weight_dtype == "int8" and cfg3.act_dtype == "bf16"


def test_bench_prefix_env_gating(monkeypatch):
    """BENCH_PREFIX is opt-in (the headline workload is i.i.d. random
    prompts where a prefix cache only adds overhead) and its block/nreq
    knobs flow through."""
    monkeypatch.delenv("BENCH_PREFIX", raising=False)
    monkeypatch.delenv("BENCH_PREFIX_BLOCK", raising=False)
    monkeypatch.delenv("BENCH_PREFIX_NREQ", raising=False)
    b = _load_bench()
    assert b.PREFIX is False
    monkeypatch.setenv("BENCH_PREFIX", "1")
    monkeypatch.setenv("BENCH_PREFIX_BLOCK", "32")
    monkeypatch.setenv("BENCH_PREFIX_NREQ", "8")
    b2 = _load_bench()
    assert b2.PREFIX is True
    assert b2.PREFIX_BLOCK == 32 and b2.PREFIX_NREQ == 8


def test_bench_is_one_process():
    """No launcher probes the backend in one process and measures in
    another: the supervisor, its child fork and its knobs are gone."""
    src = open(os.path.join(REPO, "bench.py")).read()
    for gone in ("subprocess", "_BENCH_CHILD", "BENCH_REQUIRE_TPU",
                 "BENCH_BACKEND_WAIT", "BENCH_ATTEMPT", "_phase_score"):
        assert gone not in src, gone


def test_bench_refuses_to_run_without_a_tpu(monkeypatch):
    """JAX landing on a CPU nobody asked for is a failure that names
    the platform — never a measurement."""
    monkeypatch.delenv("JAX_PLATFORMS")
    b = _load_bench()
    with pytest.raises(SystemExit) as exit_:
        b.main()
    assert "no TPU" in str(exit_.value.code)
    assert "'cpu'" in str(exit_.value.code)


# A tiny closed wave; the three ledgers main() would setdefault into
# os.environ are set HERE so monkeypatch takes them back out.
_TINY_RUN = {"BENCH_PRESET": "tiny", "BENCH_SLOTS": "4", "BENCH_NREQ": "8",
             "BENCH_PROMPT": "16", "BENCH_NEW": "4",
             "BENCH_SECOND_PRESET": "", "BENCH_SLO": "0",
             "COMPILE_LEDGER": "1", "SCHED_LEDGER": "1", "ROOF_LEDGER": "1"}


def test_bench_cpu_smoke_prints_no_per_chip_name(monkeypatch, capsys):
    """An explicit JAX_PLATFORMS=cpu run is a correctness smoke: it
    names its device and never reuses a device metric's name."""
    for k, v in _TINY_RUN.items():
        monkeypatch.setenv(k, v)
    b = _load_bench()
    b.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "engine_req_per_s_cpu_smoke"
    assert "per_chip" not in json.dumps(line)
    assert "vs_baseline" not in line
    assert line["detail"]["device"]["platform"] == "cpu"


def test_bench_failed_phase_fails_the_bench(monkeypatch):
    """An enabled phase that raises ends the run: no *_error note beside
    a headline number."""
    for k, v in {**_TINY_RUN, "BENCH_PREFIX": "1"}.items():
        monkeypatch.setenv(k, v)
    b = _load_bench()

    def boom(params, cfg):
        raise RuntimeError("prefix phase broke")

    # The headline wave is canned: only the phase plumbing is under test.
    monkeypatch.setattr(b, "_build", lambda preset: (None, None))
    monkeypatch.setattr(b, "_measure_throughput",
                        lambda *a, **kw: (1.0, {}, None))
    monkeypatch.setattr(b, "_measure_prefix", boom)
    with pytest.raises(RuntimeError, match="prefix phase broke"):
        b.main()
