"""Probe: chaos-soak the request lifecycle. Prints ONE JSON line.

Runs a mixed request stream (varied prompt/decode lengths, a slice of
requests carrying tight deadlines, a slice cancelled client-side
mid-stream) against an engine with deterministic fault injection
(servers/chaos.py: dispatch failures, allocator exhaustion, slow
boundaries, forced disconnects). Every request must land in exactly one
outcome bucket and the engine's slot/pool/trie accounting must return
to empty — the number reported is the completed fraction, the detail is
the full outcome ledger plus injected-fault counts and any leaks
(`leaks` non-empty means the lifecycle lost track of state: a bug).

Knobs (env): CH_PRESET (tiny), CH_N (200), CH_SEED (0),
CH_DISPATCH_FAIL (0.02), CH_ALLOC_FAIL (0.02), CH_SLOW (0.05),
CH_DISCONNECT (0.01), CH_PAGED (0 = dense), CH_DEADLINE_FRAC (0.1),
CH_CANCEL_FRAC (0.1).
CPU smoke: JAX_PLATFORMS=cpu CH_N=40 python tools/probe_chaos.py
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PRESET = os.environ.get("CH_PRESET", "tiny")
N_REQ = int(os.environ.get("CH_N", 200))
SEED = int(os.environ.get("CH_SEED", 0))
DISPATCH_FAIL = float(os.environ.get("CH_DISPATCH_FAIL", 0.02))
ALLOC_FAIL = float(os.environ.get("CH_ALLOC_FAIL", 0.02))
SLOW = float(os.environ.get("CH_SLOW", 0.05))
DISCONNECT = float(os.environ.get("CH_DISCONNECT", 0.01))
PAGED = int(os.environ.get("CH_PAGED", 0))
DEADLINE_FRAC = float(os.environ.get("CH_DEADLINE_FRAC", 0.1))
CANCEL_FRAC = float(os.environ.get("CH_CANCEL_FRAC", 0.1))


def main() -> None:
    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.chaos import ChaosConfig
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = get_config(PRESET)
    params = init_params(cfg, jax.random.key(0))
    ecfg = EngineConfig(
        max_slots=8,
        max_seq_len=64,
        prompt_buckets=(8, 16, 32),
        max_queue=4 * N_REQ,  # bounded but not the thing under test
        paged_kv=bool(PAGED),
        chaos=ChaosConfig(
            seed=SEED,
            dispatch_fail=DISPATCH_FAIL,
            alloc_fail=ALLOC_FAIL if PAGED else 0.0,
            slow_boundary=SLOW,
            slow_ms=2.0,
            disconnect=DISCONNECT,
        ),
    )
    engine = InferenceEngine(params, cfg, ecfg)
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    engine.start()

    rng = random.Random(SEED)
    nrng = np.random.default_rng(SEED)
    outcomes = {"completed": 0, "shed": 0, "deadline": 0,
                "cancelled": 0, "errored": 0}
    olock = threading.Lock()

    threads = []
    t_run = time.perf_counter()
    submitted = 0
    for i in range(N_REQ):
        plen = rng.choice((5, 8, 13, 21, 30))
        prompt = nrng.integers(3, cfg.vocab_size, size=(plen,)).tolist()
        sp = SamplingParams(
            temperature=0.0,
            max_new_tokens=rng.choice((4, 8, 16)),
            seed=i,
            deadline_ms=(
                rng.choice((30, 80)) if rng.random() < DEADLINE_FRAC else 0
            ),
        )
        try:
            q = engine.submit(prompt, sp)
        except Exception:
            with olock:
                outcomes["shed"] += 1
            continue
        submitted += 1
        cancels = rng.random() < CANCEL_FRAC

        def run(q=q, cancels=cancels):
            done_clean = True
            while True:
                item = q.get(timeout=120)
                if item is None:
                    break
                if "error" in item:
                    done_clean = False
                    kind = item.get("kind", "")
                    with olock:
                        if kind == "deadline":
                            outcomes["deadline"] += 1
                        elif kind == "cancelled":
                            outcomes["cancelled"] += 1
                        elif kind in ("draining", "shutdown"):
                            outcomes["shed"] += 1
                        else:
                            outcomes["errored"] += 1
                    continue
                if cancels and item.get("tokens"):
                    engine.cancel(q.rid)
                    cancels = False
            if done_clean:
                with olock:
                    outcomes["completed"] += 1

        t = threading.Thread(target=run, daemon=True)
        t.start()
        threads.append(t)
        if rng.random() < 0.3:
            time.sleep(0.002)  # mild arrival jitter

    for t in threads:
        t.join(timeout=300)
    hung = sum(1 for t in threads if t.is_alive())
    run_s = time.perf_counter() - t_run
    drained = engine.drain(timeout=60)
    leaks = engine.debug_lifecycle_check()
    chaos = engine.chaos_counts()
    snap = engine.stats.snapshot()
    engine.stop()

    total_outcomes = sum(outcomes.values())
    print(json.dumps({
        "metric": "chaos_soak_completed_frac",
        "value": round(outcomes["completed"] / max(1, N_REQ), 3),
        "unit": (
            f"fraction ({PRESET}, {N_REQ} req, seed {SEED}, "
            f"{'paged' if PAGED else 'dense'})"
        ),
        "detail": {
            "outcomes": outcomes,
            "outcomes_total": total_outcomes,
            "submitted_accepted": submitted,
            "hung_waiters": hung,
            "drained": bool(drained),
            "leaks": leaks,
            "chaos": chaos,
            "shed_total": int(snap["shed_total"]),
            "cancelled_total": int(snap["cancelled_total"]),
            "deadline_expired_total": int(snap["deadline_expired_total"]),
            "run_s": round(run_s, 1),
            "warmup_s": round(warm_s, 1),
            "device": str(jax.devices()[0]),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
