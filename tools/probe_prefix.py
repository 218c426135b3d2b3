"""Probe: prefix-cache admission economics. Prints ONE JSON line.

Measures what EngineConfig.prefix_cache actually buys at admission time:
cold admissions (disjoint prefixes, full-prompt prefill) vs warm
admissions (shared block-aligned prefix, suffix-only prefill off the
trie's retained KV), on the live engine path — submit -> TTFT — so the
delta includes the host-side trie lookup, the device gather/scatter of
reused KV, and the smaller prefill bucket. Requests run sequentially to
isolate admission cost from queueing.

Knobs (env): PB_PRESET (tiny), PB_PROMPT (128), PB_BLOCK (16),
PB_NREQ (16), PB_KV (cfg default), PB_SHARED_FRAC (0.5 of the prompt).
CPU smoke: JAX_PLATFORMS=cpu python tools/probe_prefix.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PRESET = os.environ.get("PB_PRESET", "tiny")
PROMPT_LEN = int(os.environ.get("PB_PROMPT", 128))
BLOCK = int(os.environ.get("PB_BLOCK", 16))
N_REQ = int(os.environ.get("PB_NREQ", 16))
KV = os.environ.get("PB_KV", "")
SHARED_FRAC = float(os.environ.get("PB_SHARED_FRAC", 0.5))


def main() -> None:
    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = get_config(PRESET)
    if KV:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=KV)
    shared = max(BLOCK, int(PROMPT_LEN * SHARED_FRAC) // BLOCK * BLOCK)
    params = init_params(cfg, jax.random.key(0))
    ecfg = EngineConfig(
        max_slots=8,
        max_seq_len=PROMPT_LEN + 16 + 1,
        prompt_buckets=(PROMPT_LEN - shared, PROMPT_LEN),
        max_admit=4,
        prefix_cache=True,
        prefix_block=BLOCK,
    )
    engine = InferenceEngine(params, cfg, ecfg)
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    engine.start()
    rng = np.random.default_rng(3)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)

    def prompt_row(prefix_seed: int):
        r = np.random.default_rng(prefix_seed)
        pre = r.integers(3, cfg.vocab_size, size=(shared,))
        suf = rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN - shared,))
        return np.concatenate([pre, suf]).tolist()

    def one_ttft(prompt) -> float:
        q = engine.submit(prompt, sp)
        first = q.get(timeout=300)
        ttft = first.get("ttft_ms", float("inf")) if first else float("inf")
        while first is not None:
            first = q.get()
        return ttft

    for i in range(3):  # host-side dispatch warm-in
        one_ttft(prompt_row(10_000 + i))

    cold = [one_ttft(prompt_row(20_000 + i)) for i in range(N_REQ)]
    s0 = engine.stats.snapshot()
    one_ttft(prompt_row(7))  # seed the shared prefix into the trie
    warm = [one_ttft(prompt_row(7)) for i in range(N_REQ)]
    s1 = engine.stats.snapshot()
    trie = engine._prefix.snapshot()
    engine.stop()

    hits = s1["prefix_hits"] - s0["prefix_hits"]
    cold_p50 = float(np.percentile(cold, 50))
    warm_p50 = float(np.percentile(warm, 50))
    print(json.dumps({
        "metric": "prefix_warm_admission_speedup",
        "value": round(cold_p50 / warm_p50, 3) if warm_p50 else 0.0,
        "unit": (
            f"x (cold/warm p50 TTFT, {PRESET} {cfg.kv_cache_dtype} kv, "
            f"prompt {PROMPT_LEN}, shared {shared}, block {BLOCK})"
        ),
        "detail": {
            "hit_rate": round(hits / (N_REQ + 1), 3),
            "tokens_saved": int(s1["prefix_tokens_saved"]
                                - s0["prefix_tokens_saved"]),
            "cold_p50_ttft_ms": round(cold_p50, 2),
            "cold_p99_ttft_ms": round(float(np.percentile(cold, 99)), 2),
            "warm_p50_ttft_ms": round(warm_p50, 2),
            "warm_p99_ttft_ms": round(float(np.percentile(warm, 99)), 2),
            "trie_nodes": trie["nodes"],
            "trie_bytes": trie["bytes"],
            "evictions": trie["evictions"],
            "warmup_s": round(warmup_s, 1),
            "device": str(jax.devices()[0]),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
