"""HBM ledger tests: per-category byte accounting with high-watermarks.

Claims under test:
 * unit semantics — statics are fixed, gauges are evaluated only at
   snapshot and ratchet their high-watermark, a gauge that blows up
   mid-teardown degrades to 0 without losing its watermark, workspace
   tracks the latest dispatch footprint plus its own high;
 * env gating follows the None-attribute idiom (HBM_LEDGER);
 * a live engine accounts the real trees: weights and the KV
   reservation are non-zero at init, kv_live rises with an occupied
   slot and returns to 0 after the stream finishes, the workspace
   watermark moves once a dispatch runs;
 * the paged engine prorates kv_live over allocator used-blocks.
"""

import jax
import pytest

from _engine_fixture import LIVE_TOKENS, PROMPT, live_config

from seldon_tpu.models import init_params
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import hbm_ledger
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

# A slot is recycled when the chunk that spends its budget is DISPATCHED,
# up to the pipeline's depth ahead of the first token's delivery: a test
# that looks for live KV "after the first token" needs a budget that
# outlasts that run-ahead (LIVE_TOKENS).
GREEDY = SamplingParams(temperature=0.0, max_new_tokens=LIVE_TOKENS)


def _engine(start=True, **ekw):
    cfg = live_config()
    params = init_params(cfg, jax.random.key(0))
    ekw.setdefault("max_slots", 4)
    ekw.setdefault("max_seq_len", 64)
    ekw.setdefault("prompt_buckets", (8, 32))
    eng = InferenceEngine(params, cfg, EngineConfig(**ekw))
    if start:
        eng.start()
    return eng


# ---------------------------------------------------------------------------
# Unit semantics
# ---------------------------------------------------------------------------


def test_static_gauge_and_workspace_accounting():
    led = hbm_ledger.HbmLedger()
    led.set_static("weights", 1000)
    live = {"n": 0}
    led.gauge("kv_live", lambda: live["n"])

    snap = led.snapshot()
    cats = snap["categories"]
    assert cats["weights"] == {"bytes": 1000, "bytes_per_device": 1000,
                               "high_bytes": 1000, "static": True}
    assert cats["kv_live"] == {"bytes": 0, "bytes_per_device": 0,
                               "high_bytes": 0, "static": False}
    assert "workspace" in cats
    # Single-chip ledger: per-device == full for every category.
    assert snap["devices"] == 1
    assert snap["total_bytes_per_device"] == snap["total_bytes"]

    # Gauge rises: bytes track it, high ratchets.
    live["n"] = 700
    assert led.snapshot()["categories"]["kv_live"]["bytes"] == 700
    live["n"] = 300
    kv = led.snapshot()["categories"]["kv_live"]
    assert kv["bytes"] == 300 and kv["high_bytes"] == 700

    # Workspace: latest footprint + its own watermark.
    led.note_workspace(5000)
    led.note_workspace(2000)
    ws = led.snapshot()["categories"]["workspace"]
    assert ws["bytes"] == 2000 and ws["high_bytes"] == 5000

    snap = led.snapshot()
    assert snap["total_bytes"] == 1000 + 300 + 2000
    assert snap["total_high_bytes"] == 1000 + 700 + 5000


def test_broken_gauge_degrades_to_zero_keeps_watermark():
    led = hbm_ledger.HbmLedger()
    state = {"obj": type("S", (), {"n": 400})()}
    led.gauge("kv_live", lambda: state["obj"].n)
    assert led.snapshot()["categories"]["kv_live"]["bytes"] == 400
    state["obj"] = None  # mid-teardown: attribute access raises
    kv = led.snapshot()["categories"]["kv_live"]
    assert kv["bytes"] == 0 and kv["high_bytes"] == 400


def test_from_env_gating(monkeypatch):
    monkeypatch.delenv("HBM_LEDGER", raising=False)
    assert hbm_ledger.from_env() is None
    monkeypatch.setenv("HBM_LEDGER", "0")
    assert hbm_ledger.from_env() is None
    monkeypatch.setenv("HBM_LEDGER", "1")
    assert hbm_ledger.from_env() is not None


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------


def test_engine_accounts_real_trees(monkeypatch):
    monkeypatch.setenv("HBM_LEDGER", "1")
    eng = _engine()
    try:
        hbm = eng.debug_hbm()
        cats = hbm["categories"]
        for name in ("weights", "kv_cache", "kv_live", "prefix_cache",
                     "workspace"):
            assert name in cats, name
        assert cats["weights"]["static"] is True
        assert cats["weights"]["bytes"] > 0
        assert cats["kv_cache"]["bytes"] > 0
        # Nothing admitted yet: no live KV, no dispatch footprint.
        assert cats["kv_live"]["bytes"] == 0
        assert cats["workspace"]["high_bytes"] == 0
        assert hbm["total_bytes"] == sum(
            c["bytes"] for c in cats.values())

        # Gauges are evaluated only at snapshot, so observe mid-stream:
        # after the first token the slot is still occupied.
        q = eng.submit(PROMPT, GREEDY)
        assert q.get(timeout=300) is not None
        cats = eng.debug_hbm()["categories"]
        assert cats["kv_live"]["bytes"] > 0
        while q.get(timeout=300) is not None:
            pass
        eng.drain(timeout=120)
        cats = eng.debug_hbm()["categories"]
        # The stream finished, so live KV is back to 0 — but its
        # watermark and the dispatch workspace recorded the traffic.
        assert cats["kv_live"]["bytes"] == 0
        assert cats["kv_live"]["high_bytes"] > 0
        assert cats["workspace"]["high_bytes"] > 0
        # Live fraction never exceeds the reservation.
        assert cats["kv_live"]["high_bytes"] <= cats["kv_cache"]["bytes"]
    finally:
        eng.stop()


def test_paged_engine_prorates_live_over_blocks(monkeypatch):
    monkeypatch.setenv("HBM_LEDGER", "1")
    eng = _engine(paged_kv=True, kv_block=16, kv_pool_blocks=9,
                  prompt_buckets=(16, 32))
    try:
        q = eng.submit(PROMPT, GREEDY)
        assert q.get(timeout=300) is not None  # admitted: blocks held
        live = eng.debug_hbm()["categories"]["kv_live"]["bytes"]
        while q.get(timeout=300) is not None:
            pass
        cats = eng.debug_hbm()["categories"]
        assert cats["kv_cache"]["bytes"] > 0
        assert 0 < live <= cats["kv_cache"]["bytes"]
        assert cats["kv_live"]["high_bytes"] >= live
    finally:
        eng.stop()


def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HBM_LEDGER", raising=False)
    eng = _engine(start=False)
    assert eng.debug_hbm() is None
