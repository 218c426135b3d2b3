"""Chip probe for ops/moe_dispatch.grouped_matmul: megablox against
jax.lax.ragged_dot at the LFM2-24B-A2B expert shapes (E=64, D=2048,
F=1536, top-4), decode (64 slab rows, a few live) and prefill (8 x 1024
tokens) read separately; prints ms per call of the whole sparse block and
the op names a profiler trace gives the grouped products.

    chiprun -- python3 tools/probe_moe_dispatch.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from seldon_tpu.ops import moe_dispatch as md

E, D, F, K = 64, 2048, 1536, 4


def block(impl):
    def f(x, rw, bias, wg, wu, wd, live):
        md.grouped_matmul = impl
        idx, w = md.route(x, rw, bias, top_k=K, router="sigmoid")
        return md.dispatch_experts(x, idx, w, wg, wu, wd, live, n_experts=E)
    return jax.jit(f)


def tiled(tm, tk, tn):
    """megablox at fixed tiles (the shipped choice is md._gmm_tiles)."""
    def impl(lhs, rhs, gs):
        saved = md._gmm_tiles
        md._gmm_tiles = lambda m, k, n: (min(tm, m), min(tk, k), tn)
        try:
            return md._megablox(lhs, rhs, gs)
        finally:
            md._gmm_tiles = saved
    return impl


IMPLS = (("megablox", md._megablox), ("ragged_dot", md._ragged_dot),
         ("megablox_128_1024_512", tiled(128, 1024, 512)),
         ("megablox_256_2048_512", tiled(256, 2048, 512)),
         ("megablox_512_2048_512", tiled(512, 2048, 512)),
         ("megablox_128_2048_1024", tiled(128, 2048, 1024)))


def timeit(fn, args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
    return 1000.0 * (time.perf_counter() - t) / n, out


def main():
    k = jax.random.split(jax.random.key(0), 8)
    rw = jax.random.normal(k[0], (D, E), jnp.float32) * 0.02
    bias = jax.random.normal(k[1], (E,), jnp.float32) * 0.1
    wg = (jax.random.normal(k[2], (E, D, F), jnp.float32) * 0.02).astype(jnp.bfloat16)
    wu = (jax.random.normal(k[3], (E, D, F), jnp.float32) * 0.02).astype(jnp.bfloat16)
    wd = (jax.random.normal(k[4], (E, F, D), jnp.float32) * 0.02).astype(jnp.bfloat16)
    res = {}
    cases = {
        "decode_64rows_4live": (64, 4),
        "decode_64rows_16live": (64, 16),
        "decode_64rows_64live": (64, 64),
        "prefill_1024": (1024, 1024),
        "prefill_8192": (8192, 8192),
    }
    outs = {}
    for name, (n, nlive) in cases.items():
        x = jax.random.normal(k[5], (n, D), jnp.float32).astype(jnp.bfloat16)
        live = jnp.arange(n) < nlive
        for impl_name, impl in IMPLS:
            try:
                ms, out = timeit(block(impl), (x, rw, bias, wg, wu, wd, live))
                res[f"{name}.{impl_name}_ms"] = ms
                res[f"{name}.{impl_name}_touched"] = int(out[1]["touched"])
                outs[(name, impl_name)] = out[0]
            except Exception as e:  # a refusal is a reading too
                res[f"{name}.{impl_name}_error"] = repr(e)[:300]
        if (name, "megablox") in outs and (name, "ragged_dot") in outs:
            a = outs[(name, "megablox")].astype(jnp.float32)
            b = outs[(name, "ragged_dot")].astype(jnp.float32)
            res[f"{name}.max_abs_diff"] = float(jnp.max(jnp.abs(a - b)))
            res[f"{name}.max_abs"] = float(jnp.max(jnp.abs(b)))
        print(json.dumps(res), flush=True)
    # names in a trace
    out_dir = os.path.join("chiprun_out", "probe_moe")
    os.makedirs(out_dir, exist_ok=True)
    x = jax.random.normal(k[5], (64, D), jnp.float32).astype(jnp.bfloat16)
    live = jnp.arange(64) < 4
    fns = [block(md._megablox), block(md._ragged_dot)]
    for f in fns:
        try:
            jax.block_until_ready(f(x, rw, bias, wg, wu, wd, live))  # graftlint: allow(hot-sync) a probe: compile before the trace starts
        except Exception:
            pass
    jax.profiler.start_trace(out_dir)
    for f in fns:
        try:
            for _ in range(3):
                jax.block_until_ready(f(x, rw, bias, wg, wu, wd, live))  # graftlint: allow(hot-sync) a probe: the traced calls
        except Exception:
            pass
    jax.profiler.stop_trace()
    sys.path.insert(0, "benchmark")
    import xplane
    planes = xplane.read_planes(out_dir)
    for pname, lines in planes:
        if not xplane.DEVICE_PLANE.match(pname):
            continue
        for lname, ev in lines:
            if lname == xplane.OPS_LINE:
                tot = {}
                for n, _, d in ev:
                    tot[n] = tot.get(n, 0) + d
                for n, d in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                    print(f"OP {d/1e3:10.1f} us  {n[:200]}")
    print("RESULT " + json.dumps(res))


if __name__ == "__main__":
    main()
