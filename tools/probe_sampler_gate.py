"""Chip probe for the sampler's gate inside the decode chunk: does the
`lax.cond` in models/sampling.sample_per_row skip what it guards on the
TPU, and what does each tier of the sampler cost a step?

Builds the engine the way the benchmark's unit does (JAXServer over the
cell's configuration file, 64 slots x 1024), stops the scheduler, arms 4
rows of the slab by hand and times the engine's own jitted `_chunk_impl`
(4 steps) with the rows' knobs set to each case below; then traces each
case and lists the chunk's longest device ops, so a full-vocabulary
`sort` shows by name where it runs.

    chiprun --timeout 1500 -- python3 tools/probe_sampler_gate.py
    JAX_PLATFORMS=cpu python3 tools/probe_sampler_gate.py --rehearse
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax
import jax.numpy as jnp

# (name, temperature, top_k, top_p) of the 4 live rows. The transports
# hand a request that names no top_p down as top_p = 0.0.
CASES = (
    ("greedy_top_p_1.0", 0.0, 0, 1.0),
    ("greedy_top_p_0.0", 0.0, 0, 0.0),
    ("greedy_top_k_5_top_p_0.9", 0.0, 5, 0.9),
    ("sampled_no_knobs", 0.8, 0, 1.0),
    ("sampled_top_p_0.9", 0.8, 0, 0.9),
)
LIVE, POS = 4, 256


def armed(state, temp, top_k, top_p, rows=LIVE):
    """The slab with `rows` rows running at position POS under one
    case's knobs; the other rows idle, carrying the same knobs (a freed
    slot keeps its last request's)."""
    B = state["active"].shape[0]
    live = jnp.arange(B) < rows
    return {
        **state,
        "active": live,
        "pos": jnp.where(live, POS, 0).astype(jnp.int32),
        "remaining": jnp.where(live, 1 << 20, 0).astype(jnp.int32),
        # distinct tokens: identical rows would route to the same experts
        "last_tok": jnp.where(live, 7 + jnp.arange(B),
                              state["last_tok"]).astype(jnp.int32),
        "temp": jnp.full((B,), temp, jnp.float32),
        "top_k": jnp.full((B,), top_k, jnp.int32),
        "top_p": jnp.full((B,), top_p, jnp.float32),
    }


def op_seconds(planes):
    """[(op, seconds)] of every device op of a trace, longest first: the
    sum xplane.reduce_planes keeps the first ten of."""
    import xplane

    ops = {}
    for pname, lines in planes:
        if not xplane.DEVICE_PLANE.match(pname):
            continue
        for name, _, d in dict(lines).get(xplane.OPS_LINE, []):
            if not xplane.CONTAINER.match(name):
                ops[xplane.clean(name)] = ops.get(xplane.clean(name), 0) + d / 1e9
    return sorted(ops.items(), key=lambda kv: -kv[1])


def run_chunks(chunk, params, state, n):
    out = None
    for _ in range(n):
        out = chunk(params, state)
        state = out[0]
    jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
    return state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lfm2-24b-a2b")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the tiny preset")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--live", default=str(LIVE),
                    help="rows armed; a comma-separated list runs every "
                         "case at each (case names gain /<rows>)")
    ap.add_argument("--cases", default="",
                    help="comma-separated case names (default: all)")
    ap.add_argument("--top", type=int, default=10,
                    help="device ops listed per case; past the trace "
                         "reducer's ten they are summed here by name")
    args = ap.parse_args(argv)

    from seldon_tpu.servers.jaxserver import JAXServer

    if args.rehearse:
        srv = JAXServer(preset="tiny-lfm2", max_slots=8, max_seq_len=512, tp=1)
    else:
        import launcher

        path = os.path.join(ROOT, "benchmark", "configs", args.config + ".json")
        with open(path) as f:
            serving = json.load(f)["serving"]
        srv = JAXServer(preset=launcher.register_preset(path), init_seed=1,
                        tp=1, max_slots=64, max_seq_len=1024, platform="tpu",
                        weight_dtype=serving["weight_dtype"])
    srv.load()
    eng = srv.engine
    eng.stop()
    n = min(eng._chunk_sizes)
    chunk = eng._jit_chunks[n]
    dev = jax.devices()[0]
    res = {"device": f"{dev.platform} {dev.device_kind}", "config": srv.preset,
           "vocab": srv.cfg.vocab_size, "slots": eng.ecfg.max_slots,
           "live_rows": args.live, "steps_per_chunk": n, "cases": {}}
    lives = [int(x) for x in args.live.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", "probe_sampler_gate")
    os.makedirs(out_dir, exist_ok=True)

    import xplane

    state = eng._state
    wanted = set(filter(None, args.cases.split(",")))
    for name, temp, top_k, top_p, rows in (
            c + (r,) for c in CASES for r in lives):
        if wanted and name not in wanted:
            continue
        if len(lives) > 1:
            name = f"{name}/{rows}"

        def arm(state):
            return armed(state, temp, top_k, top_p, rows)
        state = run_chunks(chunk, eng.params, arm(state), 3)
        state = arm(state)
        t = time.perf_counter()
        state = run_chunks(chunk, eng.params, state, args.calls)
        ms_step = 1000.0 * (time.perf_counter() - t) / (args.calls * n)
        case = {"host_clock_ms_per_step": ms_step}
        # The device's own reading (a CPU trace has no device plane:
        # the rehearsal reads zeros here).
        prof = os.path.join(out_dir, name.replace("/", "_"))
        shutil.rmtree(prof, ignore_errors=True)
        state = arm(state)
        jax.profiler.start_trace(prof)
        state = run_chunks(chunk, eng.params, state, 10)
        jax.profiler.stop_trace()
        planes = xplane.read_planes(prof)
        tr = xplane.reduce_planes(planes)
        mod = tr["modules"].get("_chunk_impl", {})
        chunks = mod.get("count", 0)
        case["device_ms_per_step"] = 1000.0 * mod.get("median_s", 0.0) / n
        case["chunks_traced"] = chunks
        case["top_ops_ms_per_step"] = [
            [op, 1000.0 * s / (max(chunks, 1) * n)]
            for op, s in op_seconds(planes)[:args.top]]
        case["sorts"] = [op for op, _ in tr["device_ops"] if "sort" in op]
        shutil.rmtree(prof, ignore_errors=True)
        res["cases"][name] = case
        print(name, json.dumps(case), flush=True)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
