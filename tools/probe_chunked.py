"""Probe: chunked-prefill stall economics. Prints ONE JSON line.

Measures what EngineConfig.chunked_prefill actually buys under mixed
traffic: PC_STREAMS short-prompt decode streams run steadily, then ONE
long prompt (PC_LONG tokens) arrives mid-decode. The recorded number is
the p99 client-side burst gap (inter-token latency) of the short
streams AFTER the interloper lands — uninterleaved, the whole long
prefill runs before the next decode chunk; chunked, at most
PC_BUDGET prefill tokens separate consecutive decode chunks.

Knobs (env): PC_PRESET (tiny), PC_PROMPT (32), PC_LONG (8x prompt),
PC_CHUNK (= prompt), PC_BUDGET (= chunk), PC_STREAMS (4), PC_NEW (64),
PC_KV (cfg default).
CPU smoke: JAX_PLATFORMS=cpu python tools/probe_chunked.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PRESET = os.environ.get("PC_PRESET", "tiny")
PROMPT_LEN = int(os.environ.get("PC_PROMPT", 32))
LONG_LEN = int(os.environ.get("PC_LONG", 8 * PROMPT_LEN))
CHUNK = int(os.environ.get("PC_CHUNK", PROMPT_LEN))
BUDGET = int(os.environ.get("PC_BUDGET", CHUNK))
N_STREAMS = int(os.environ.get("PC_STREAMS", 4))
NEW_TOKENS = int(os.environ.get("PC_NEW", 64))
KV = os.environ.get("PC_KV", "")


def main() -> None:
    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = get_config(PRESET)
    if KV:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=KV)
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(17)
    shorts = [
        rng.integers(3, cfg.vocab_size, size=(PROMPT_LEN,)).tolist()
        for _ in range(N_STREAMS)
    ]
    long_prompt = rng.integers(3, cfg.vocab_size, size=(LONG_LEN,)).tolist()
    warm_s = [0.0]

    def run(chunked: bool):
        ecfg = EngineConfig(
            max_slots=N_STREAMS + 2,
            max_seq_len=LONG_LEN + NEW_TOKENS + 1,
            prompt_buckets=(PROMPT_LEN, LONG_LEN),
            max_admit=4,
            decode_chunk=4,
            adaptive_chunk=False,
            chunked_prefill=chunked,
            prefill_chunk=CHUNK,
            dispatch_token_budget=BUDGET,
        )
        engine = InferenceEngine(params, cfg, ecfg)
        t0 = time.perf_counter()
        engine.warmup()
        warm_s[0] += time.perf_counter() - t0
        engine.start()
        gaps: list = []
        glock = threading.Lock()
        first_burst = threading.Barrier(N_STREAMS + 1)

        def consume(q):
            last = None
            waited = False
            while True:
                item = q.get()
                if item is None:
                    break
                if "error" in item:
                    raise RuntimeError(item["error"])
                now = time.perf_counter()
                if last is not None and item["tokens"]:
                    with glock:
                        gaps.append((now, now - last))
                last = now
                if not waited:
                    waited = True
                    first_burst.wait(timeout=300)

        threads = []
        for i, p in enumerate(shorts):
            q = engine.submit(
                p,
                SamplingParams(
                    temperature=0.0, max_new_tokens=NEW_TOKENS, seed=i
                ),
            )
            t = threading.Thread(target=consume, args=(q,), daemon=True)
            t.start()
            threads.append(t)
        first_burst.wait(timeout=300)  # all streams mid-decode
        t_long = time.perf_counter()
        lq = engine.submit(
            long_prompt,
            SamplingParams(temperature=0.0, max_new_tokens=8, seed=99),
        )
        for t in threads:
            t.join(timeout=300)
        while lq.get(timeout=300) is not None:
            pass
        snap = engine.stats.snapshot()
        engine.stop()
        tail = [g for ts, g in gaps if ts >= t_long]
        p99 = 1000.0 * float(np.percentile(tail or [0.0], 99))
        return p99, snap

    base_p99, _ = run(chunked=False)
    chunked_p99, snap = run(chunked=True)
    print(json.dumps({
        "metric": "chunked_prefill_p99_itl_speedup",
        "value": (
            round(base_p99 / chunked_p99, 3) if chunked_p99 else 0.0
        ),
        "unit": (
            f"x (uninterleaved/chunked p99 ITL, {PRESET} "
            f"{cfg.kv_cache_dtype} kv, {N_STREAMS} streams prompt "
            f"{PROMPT_LEN}, interloper {LONG_LEN}, chunk {CHUNK}, "
            f"budget {BUDGET})"
        ),
        "detail": {
            "baseline_p99_itl_ms": round(base_p99, 2),
            "chunked_p99_itl_ms": round(chunked_p99, 2),
            "prefill_chunks": int(snap["prefill_chunks"]),
            "prefill_chunk_tokens": int(snap["prefill_chunk_tokens"]),
            "budget_utilization": round(
                float(snap["budget_utilization"]), 3
            ),
            "engine_itl_p99_ms": float(snap["itl_p99_ms"]),
            "mean_queue_wait_ms": round(
                float(snap["mean_queue_wait_ms"]), 2
            ),
            "warmup_s": round(warm_s[0], 1),
            "device": str(jax.devices()[0]),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
