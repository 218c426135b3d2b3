"""seldon_tpu.device: the one place that asks where the process runs,
and the compile cache that can be placed from outside."""

import os
import subprocess
import sys

import jax
import pytest

from seldon_tpu import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_on_tpu_is_false_on_cpu_and_never_swallows_errors(monkeypatch):
    assert device.on_tpu() is False

    def broken():
        raise RuntimeError("backend did not come up")

    # A failed device query is an error, not "not a TPU".
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="did not come up"):
        device.on_tpu()


def test_describe_reports_the_device_as_jax_does():
    d = device.describe()
    assert d["platform"] == "cpu" == jax.devices()[0].platform
    assert d["device_kind"] == jax.devices()[0].device_kind
    assert d["count"] == len(jax.devices()) == 8
    assert [m["id"] for m in d["memory"]] == list(range(8))
    # CPU keeps no memory statistic: null, not a made-up number.
    assert d["memory"][0]["peak_bytes_in_use"] is None


def test_compile_cache_env_wins_and_config_is_left_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert device.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        want = os.path.join(REPO, ".jax_cache")
        assert device.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_cache_path_is_set_anywhere_else():
    """One helper: no other module points JAX at a cache directory
    (a /tmp path, a pid or a timestamp never hits twice)."""
    offenders = []
    for root in ("seldon_tpu", "tools", "tests"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for f in files:
                path = os.path.join(dirpath, f)
                if not f.endswith(".py") or path == os.path.abspath(__file__):
                    continue
                src = open(path).read()
                if "jax_compilation_cache_dir" in src \
                        and not path.endswith("seldon_tpu/device.py"):
                    offenders.append(os.path.relpath(path, REPO))
    for f in ("bench.py", "chip_smoke.py", "bench_orchestrator.py"):
        if "jax_compilation_cache_dir" in open(os.path.join(REPO, f)).read():
            offenders.append(f)
    assert offenders == []


def test_launchers_stay_off_jax():
    """One process per chip: a parent that starts unit or engine
    children (microservice CLI before load(), the local process store,
    the orchestrator, chip_smoke's driver half) must not have touched
    JAX, or it holds the chip its child needs."""
    code = (
        "import sys\n"
        "import chip_smoke, bench_orchestrator\n"
        "import seldon_tpu.runtime.microservice\n"
        "import seldon_tpu.operator.localstore\n"
        "import seldon_tpu.orchestrator.server\n"
        "sys.exit(1 if 'jax' in sys.modules else 0)\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120)
    assert r.returncode == 0
