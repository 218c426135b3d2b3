"""Times one layer's decode attention at a configuration's shapes on the
chip: the kernel that reads the slab's live rows only
(ops/decode_attention.attend) against the einsums over the whole layer
(models/transformer.gqa_attention_decode), over a table of occupancy
(live rows of 64) x context (tokens each live row has reached), and
checks that the live rows agree.

    chiprun -- python3 tools/probe_decode_attention.py [--config mistral-7b-v0.3 ...]

Each time is of LAYERS layers scanned inside one program, REPEATS times
over, with the next query made from the last result (so nothing is
hoisted), divided down to one layer: the kernel's own fixed cost is in
it, the projections are not. About
a minute a configuration. `--rehearse` runs tiny shapes on the CPU with
the kernel interpreted (no time comes out of that).
"""

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIGS = ("mistral-7b-v0.3", "mixtral-8x7b", "lfm2-24b-a2b",
           "nemotron-3-nano-30b-a3b", "falcon-h1-34b-instruct")
LIVE = (1, 2, 4, 8, 16, 32, 64)
CONTEXTS = (128, 512, 1024)
LAYERS, REPEATS = 8, 10


def shape_of(raw):
    """(KV heads, head size, queries a KV head, int8 KV) of a file of
    benchmark/configs."""
    heads, kv = raw["num_attention_heads"], raw["num_key_value_heads"]
    dh = raw.get("head_dim") or raw["hidden_size"] // heads
    return kv, dh, heads // kv, raw["serving"].get("kv_cache_dtype") == "int8"


def probe(name, B, T, layers, repeats, live, contexts, block, interpret):
    """One configuration's row of the table."""
    import jax
    import jax.numpy as jnp

    from seldon_tpu.models import transformer
    from seldon_tpu.ops import decode_attention as da

    bf16 = jnp.bfloat16
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        Hkv, Dh, G, int8 = shape_of(json.load(f))
    H, C = Hkv * G, Hkv * Dh
    ks = jax.random.split(jax.random.key(0), 5)
    q0 = jax.random.normal(ks[0], (B, 1, H, Dh)).astype(bf16)
    kf = jax.random.normal(ks[1], (B, 1, Hkv, Dh)).astype(bf16)
    vf = (0.5 * jax.random.normal(ks[2], (B, 1, Hkv, Dh))).astype(bf16)
    cache = {"k": jax.random.normal(ks[3], (layers, B, 1, T, C), bf16),
             "v": 0.5 * jax.random.normal(ks[4], (layers, B, 1, T, C), bf16)}
    if int8:
        cache = transformer.kv_writes(
            cache, {}, type("c", (), {"kv_cache_dtype": "int8", "head_dim": Dh}))
    block = block or da.block_size(
        cache["k"].shape, Dh, cache["k"].dtype.itemsize)

    def einsums_layer(q, cl, mask_lt):
        return transformer.gqa_attention_decode(
            q, cl["k"], cl["v"], kf, vf, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"))

    def many(attend_layer, xs):
        """REPEATS passes over the layers, each result the next query."""
        def run(q, cache, active, pos):
            sched = da.schedule(active, pos, T, block)
            mask_lt = jnp.arange(T)[None, None, :] < pos[:, None, None]

            def layer(q, x):
                out = attend_layer(q, cache, x, sched, mask_lt)
                return (q + 1e-3 * out.reshape(q.shape)).astype(bf16), None

            def sweep(_, q):
                return jax.lax.scan(layer, q, xs(cache))[0]
            return jax.lax.fori_loop(0, repeats, sweep, q)
        return jax.jit(run)

    kernel = many(
        lambda q, cache, l, sched, _: da.attend(q, kf, vf, cache, l, sched),
        lambda cache: jnp.arange(layers))
    einsums = many(
        lambda q, _, cl, sched, mask_lt: einsums_layer(q, cl, mask_lt),
        lambda cache: cache)

    def time_of(fn, active, pos):
        out = fn(q0, cache, active, pos)  # compiles the first time
        jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
        t = time.perf_counter()
        out = fn(q0, cache, active, pos)
        jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
        return 1e6 * (time.perf_counter() - t) / (repeats * layers)

    row = {"config": name, "heads": [Hkv, Dh, G], "int8": int8,
           "slots": B, "window": T, "block": block, "kernel_us": {}}
    with interpret():
        for n in live:
            for ctx in contexts:
                active = jnp.arange(B) < n
                pos = jnp.full((B,), min(ctx, T - 1), jnp.int32)
                row["kernel_us"][f"{n}x{ctx}"] = round(
                    time_of(kernel, active, pos), 2)
        full = (jnp.ones((B,), bool), jnp.full((B,), T - 1, jnp.int32))
        row["einsums_us"] = round(time_of(einsums, *full), 2)
        # the chat mix's occupancy: 3 live rows, ragged contexts
        active = jnp.arange(B) % (B // min(B, 3)) == 0
        pos = (jnp.arange(B) * 37 % (T - 1)).astype(jnp.int32)
        sched = da.schedule(active, pos, T, block)
        a = jax.jit(lambda q, cache: da.attend(
            q, kf, vf, cache, jnp.int32(1), sched))(q0, cache)
        b = einsums_layer(
            q0, {key: val[1] for key, val in cache.items()},
            jnp.arange(T)[None, None, :] < pos[:, None, None])
        gap = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        row["max_gap_live_rows"] = float(
            jnp.max(jnp.where(sched.has_past[:, None, None], gap, 0.0)))
        row["dead_rows_finite"] = bool(jnp.all(jnp.isfinite(
            a.astype(jnp.float32))))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", nargs="*", default=list(CONFIGS))
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--block", type=int, default=0,
                    help="tokens a work item covers (0: the kernel's own)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    sizes = (args.slots, args.window, LAYERS, REPEATS, LIVE, CONTEXTS)
    interpret = contextlib.nullcontext
    if args.rehearse:
        from tests.pallas_interpret import pallas_interpret
        sizes = (4, 256, 2, 1, (1, 4), (128, 256))
        interpret = pallas_interpret
    elif jax.default_backend() != "tpu":
        print("no TPU here: --rehearse, or run through chiprun", file=sys.stderr)
        return 1
    table = []
    for name in args.config:
        row = probe(name, *sizes, args.block, interpret)
        if args.rehearse:
            del row["kernel_us"], row["einsums_us"]
        table.append(row)
        print("PROBE " + json.dumps(row), flush=True)
    if not args.rehearse:
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "probe_decode_attention.json"), "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
