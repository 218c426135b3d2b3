"""Chip probe for the rung of models/slot.block_step: what do the head,
the sampler and the confidence of a block-diffusion pass cost by the
number of slots they score, in the engine's own decode chunk?

Builds the engine the way the benchmark's unit does (JAXServer over the
cell's configuration file, 64 slots x 1024), stops the scheduler, arms
LIVE slots by hand one pass out of phase with each other (so that every
pass holds slots that denoise and one that commits, as the cell's do)
and times a fresh jit of `_chunk_impl` (one block's passes) for each
value of `slot.SCORED_SLOTS` in --slots; the last, the slab's own slot
count, is the branch that scores every slot, which is what the program
did before it had a rung. Per value: the chunk's device time a pass and
the device ops that hold the vocabulary in their shape (the head, the
argmax, the confidence's reductions), summed. One live slot alone
commits in a third of its passes, which then score nothing.

    chiprun --timeout 1500 -- python3 tools/probe_block_head.py
    JAX_PLATFORMS=cpu python3 tools/probe_block_head.py --rehearse
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

from probe_sampler_gate import op_seconds, run_chunks

LIVE, POS = 3, 256


def armed(state, rows, temp):
    """`rows` slots running at position POS with a whole budget, slot i
    holding 2 x (i % 3) decided positions of its block (0, 2, all 4: a
    pass of each kind a block)."""
    B, Bk = state["blk_tok"].shape
    live = jnp.arange(B) < rows
    decided = 2 * (jnp.arange(B) % 3)
    known = live[:, None] & (jnp.arange(Bk)[None, :] < decided[:, None])
    return {
        **state,
        "active": live,
        "pos": jnp.where(live, POS, 0).astype(jnp.int32),
        "remaining": jnp.where(live, 1 << 20, 0).astype(jnp.int32),
        # distinct tokens: identical rows would route to the same experts
        "blk_tok": jnp.where(known, 7 + jnp.arange(B * Bk).reshape(B, Bk),
                             0).astype(jnp.int32),
        "blk_known": known,
        "blk_skip": jnp.zeros((B,), jnp.int32),
        "temp": jnp.full((B,), temp, jnp.float32),
        "top_k": jnp.zeros((B,), jnp.int32),
        "top_p": jnp.ones((B,), jnp.float32),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sdar-30b-a3b-chat")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the tiny preset, 16 slots")
    ap.add_argument("--slots", default="4,8,16,32,64",
                    help="values of slot.SCORED_SLOTS; one at or past the "
                         "slab's slots scores every slot")
    ap.add_argument("--live", default=str(LIVE),
                    help="slots armed (a comma-separated list runs each)")
    ap.add_argument("--temp", type=float, default=0.0,
                    help="the armed slots' temperature (0: greedy)")
    ap.add_argument("--calls", type=int, default=30)
    args = ap.parse_args(argv)

    from seldon_tpu.models import slot
    from seldon_tpu.servers.jaxserver import JAXServer

    if args.rehearse:
        srv = JAXServer(preset="tiny-sdar", max_slots=16, max_seq_len=1024,
                        tp=1)
        args.slots = "4,8,16"
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
    cfg = srv.cfg
    n = cfg.denoise_steps + 1  # one block's passes
    dev = jax.devices()[0]
    res = {"device": f"{dev.platform} {dev.device_kind}", "config": srv.preset,
           "vocab": cfg.vocab_size, "d_model": cfg.d_model,
           "slab_slots": eng.ecfg.max_slots, "block": cfg.gen_block,
           "passes_per_chunk": n, "temp": args.temp, "cases": {}}
    out_dir = os.path.join(ROOT, "chiprun_out", "probe_block_head")
    os.makedirs(out_dir, exist_ok=True)

    import xplane

    state = eng._state
    rungs = [int(x) for x in args.slots.split(",")]
    chunks = {}
    for rung, live in ((r, k) for r in rungs
                       for k in map(int, args.live.split(","))):
        if rung not in chunks:  # the constant is read when the jit traces
            slot.SCORED_SLOTS = rung
            chunks[rung] = eng._chunk_jit(eng._chunk_impl, n)
            state = run_chunks(chunks[rung], eng.params,
                               armed(state, live, args.temp), 1)
        chunk = chunks[rung]
        name = f"scored_{rung}/live_{live}"

        def arm(state):
            return armed(state, live, args.temp)
        state = run_chunks(chunk, eng.params, arm(state), 3)
        state = arm(state)
        t = time.perf_counter()
        state = run_chunks(chunk, eng.params, state, args.calls)
        case = {"rows_scored": min(rung, eng.ecfg.max_slots) * cfg.gen_block,
                "host_clock_ms_per_pass":
                    1000.0 * (time.perf_counter() - t) / (args.calls * n)}
        # The device's own reading (a CPU trace has no device plane: the
        # rehearsal reads zeros here).
        prof = os.path.join(out_dir, name.replace("/", "_"))
        shutil.rmtree(prof, ignore_errors=True)
        state = arm(state)
        jax.profiler.start_trace(prof)
        state = run_chunks(chunk, eng.params, state, 12)
        jax.profiler.stop_trace()
        planes = xplane.read_planes(prof)
        mod = xplane.reduce_planes(planes)["modules"].get("_chunk_impl", {})
        traced = max(mod.get("count", 0), 1) * n
        case["device_ms_per_pass"] = 1000.0 * mod.get("median_s", 0.0) / n
        vocab = [(op, s) for op, s in op_seconds(planes)
                 if str(cfg.vocab_size) in op]
        case["vocab_ops_ms_per_pass"] = 1000.0 * sum(s for _, s in vocab) / traced
        case["vocab_ops"] = [[op, 1000.0 * s / traced] for op, s in vocab[:6]]
        shutil.rmtree(prof, ignore_errors=True)
        res["cases"][name] = case
        print(name, json.dumps(case), flush=True)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
