"""Times one layer's decode attention at a configuration's shapes on the
chip: the kernel that reads the slab's live rows only
(ops/decode_attention.attend) against the einsums over the whole layer
(models/transformer.gqa_attention_decode), over a table of occupancy
(live rows of 64) x context (tokens each live row holds, the step at its
last row; "edge": every live row at a block's first row, where the
kernel fetches the tile it writes by itself: one step in `block`), and
checks that the live rows agree. The kernel's call also WRITES the
fresh token's K and V of the live slots (since PR 44); `scatter_us` is
what it took the place of, a layer's share of the step's scatter of a
fresh row of EVERY slot and layer into K and V (run the parent's copy of
this tool beside it for the call without the write).

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
import functools
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
    stored = None
    if int8:
        cache = transformer.kv_writes(
            cache, {}, type("c", (), {"kv_cache_dtype": "int8", "head_dim": Dh}))
        stored = {"k": transformer._quantize_kv(kf)[0].reshape(B, C),
                  "v": transformer._quantize_kv(vf)[0].reshape(B, C)}
    block = block or da.block_size(
        cache["k"].shape, Dh, cache["k"].dtype.itemsize)

    def einsums_layer(q, cl, mask_lt):
        return transformer.gqa_attention_decode(
            q, cl["k"], cl["v"], kf, vf, mask_lt,
            k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"))

    def step(q, out):
        """The next query, made from the last result: nothing is hoisted."""
        return (q + 1e-3 * out.reshape(q.shape)).astype(bf16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def kernel(cache, q, active, pos):
        """REPEATS passes over the layers, K and V carried through them
        as the decode step carries them: written where they lie."""
        sched = da.schedule(active, pos, T, block)

        def layer(carry, l):
            q, k, v = carry
            out, k, v = da.attend(q, kf, vf, {**cache, "k": k, "v": v}, l,
                                  sched, stored)
            return (step(q, out), k, v), None

        def sweep(_, carry):
            return jax.lax.scan(layer, carry, jnp.arange(layers))[0]
        q, k, v = jax.lax.fori_loop(0, repeats, sweep,
                                    (q, cache["k"], cache["v"]))
        return {**cache, "k": k, "v": v}, q

    @jax.jit
    def einsums(cache, q, pos):
        mask_lt = jnp.arange(T)[None, None, :] < pos[:, None, None]

        def layer(q, cl):
            return step(q, einsums_layer(q, cl, mask_lt)), None

        def sweep(_, q):
            return jax.lax.scan(layer, q, cache)[0]
        return None, jax.lax.fori_loop(0, repeats, sweep, q)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(cache, pos):
        """The step's scatter (transformer._run_blocks_decode where no
        kernel runs): a fresh row of every slot and layer into K and V,
        REPEATS times over at moving positions."""
        rows = {key: jnp.broadcast_to(
            (stored[key] if int8 else x.reshape(B, C).astype(bf16))[:, None, None],
            (B, layers, 1, C)) for key, x in (("k", kf), ("v", vf))}
        at = jnp.arange(layers)[None, :], jnp.arange(B)[:, None]

        def once(i, kv):
            p = ((pos + i) % T)[:, None]
            return tuple(a.at[at[0], at[1], :, p].set(
                rows[key], unique_indices=True) for key, a in zip("kv", kv))
        k, v = jax.lax.fori_loop(0, repeats, once, (cache["k"], cache["v"]))
        return {**cache, "k": k, "v": v}, None

    def time_of(fn, cache, *args):
        """Microseconds a layer of `fn`'s second run (the first compiles),
        and the cache to go on with: a run that writes it was given it to
        keep (nothing is copied on the way in) and hands it back."""
        for _ in range(2):
            t = time.perf_counter()
            out = fn(cache, *args)
            jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
            took = time.perf_counter() - t
            cache = out[0] or cache
        return 1e6 * took / (repeats * layers), cache

    row = {"config": name, "heads": [Hkv, Dh, G], "int8": int8,
           "slots": B, "window": T, "block": block, "kernel_us": {}}
    with interpret():
        for n in live:
            # a context's last row: the block in hand holds the row the
            # step writes, as in all but one step of `block`; "edge": a
            # block's first row, whose tile the kernel fetches by itself
            for ctx in contexts + ("edge",):
                active = jnp.arange(B) < n
                at = block if ctx == "edge" else min(ctx, T) - 1
                pos = jnp.full((B,), at, jnp.int32)
                us, cache = time_of(kernel, cache, q0, active, pos)
                row["kernel_us"][f"{n}x{ctx}"] = round(us, 2)
        full = jnp.full((B,), T - 1, jnp.int32)
        row["einsums_us"] = round(time_of(einsums, cache, q0, full)[0], 2)
        us, cache = time_of(scatter, cache,
                            jnp.arange(B, dtype=jnp.int32) * 7)
        row["scatter_us"] = round(us, 2)
        # the chat mix's occupancy: 3 live rows, uneven contexts
        active = jnp.arange(B) % (B // min(B, 3)) == 0
        pos = (jnp.arange(B) * 37 % (T - 1)).astype(jnp.int32)
        sched = da.schedule(active, pos, T, block)
        a, *_ = jax.jit(lambda q, cache: da.attend(
            q, kf, vf, cache, jnp.int32(1), sched, stored))(q0, cache)
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
            del row["kernel_us"], row["einsums_us"], row["scatter_us"]
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
