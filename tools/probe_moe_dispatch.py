"""Chip probe for ops/moe_dispatch.grouped_matmul, two shapes.

`--shape lfm2`: megablox against jax.lax.ragged_dot at the LFM2-24B-A2B
expert shapes (E=64, D=2048, F=1536, top-4, bf16), decode (64 slab rows,
a few live) and prefill (8 x 1024 tokens) read separately; prints ms per
call of the whole sparse block and the op names a profiler trace gives
the grouped products.

`--shape mixtral`: one Mixtral-8x7B sparse block (E=8, D=4096, F=14336,
top-2, int8 weights with their float32 scales): the token -> expert
dispatch over ops/gmm_int8 (the shipped tiles and a few others) against
moe_block's all-expert einsums on the same int8 tree, decode at 1 / 2 /
4 / 8 live rows of 64 and prefill at 256 / 1024 / 8192 tokens; ms a
call, GB/s of the touched experts' weights (share of the HBM peak) and
TFLOP/s of the routed products (share of the bf16 peak).

    chiprun -- python3 tools/probe_moe_dispatch.py [--shape lfm2|mixtral]

`--shape mixtral --rehearse` runs that half at a toy width on the CPU
with the kernel interpreted (no time comes out of that).
"""

import argparse
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


def lfm2():
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


# (tm, tk cap, tn cap) of ops/gmm_int8: the shipped rule first, then
# the neighbours the choice was made from (tm: decode's; prefill's is
# moe_dispatch._GMM_TILE_M_LARGE)
MIXTRAL_TILES = (None, (128, 4096, 1024), (128, 2048, 1024), (128, 2048, 512),
                 (64, 2048, 1024), (64, 2048, 2048), (32, 2048, 1024),
                 (32, 4096, 1024))

def mixtral(rehearse=False):
    from seldon_tpu.models import transformer
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import _quantize_leaf

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    import peaks
    peak = peaks.PEAKS["TPU v5 lite"] if rehearse else \
        peaks.peaks_for(jax.devices()[0].device_kind)

    E, D, F, K = 8, 4096, 14336, 2
    cases = [(f"decode_64rows_{n}live", 64, n) for n in (1, 2, 4, 8)] + \
        [(f"prefill_{n}", n, n) for n in (256, 1024, 8192)]
    tilings = MIXTRAL_TILES
    if rehearse:
        D, F, cases, tilings = 256, 512, cases[1:6:4], MIXTRAL_TILES[:2]
    cfg = ModelConfig(d_model=D, d_ff=F, n_experts=E, n_experts_per_token=K)
    k = jax.random.split(jax.random.key(0), 8)
    bp = {"router": jax.random.normal(k[0], (D, E), jnp.float32) * 0.02}
    # seeded int8 weights with their per-output-channel scales, as the
    # served tree stores them
    int8_stack = jax.jit(lambda key, shape: _quantize_leaf(
        jax.random.normal(key, shape, jnp.float32) * 0.02), static_argnums=1)
    for i, (name, shape) in enumerate(
            (("w_gate", (E, D, F)), ("w_up", (E, D, F)), ("w_down", (E, F, D)))):
        bp[name], bp[name + "_scale"] = int8_stack(k[1 + i], shape)
    stacks = ("w_gate", "w_up", "w_down")

    def dispatch(tiles):
        # the weights are arguments: closed over they would be 3.8 GB of
        # constants in every program
        def f(x, live, bp):
            md.grouped_matmul = md._megablox
            saved = (md._GMM_TILE_M_SMALL_INT8, md._GMM_TILE_K, md._GMM_TILE_N)
            if tiles is not None:
                md._GMM_TILE_M_SMALL_INT8 = tiles[0]
                md._GMM_TILE_K, md._GMM_TILE_N = tiles[1] // 2, tiles[2] // 2
            try:
                idx, w = md.route(x, bp["router"], None, top_k=K,
                                  router="softmax")
                return md.dispatch_experts(
                    x, idx, w, *(bp[n] for n in stacks), live, n_experts=E,
                    scales={n: bp[n + "_scale"] for n in stacks})
            finally:
                (md._GMM_TILE_M_SMALL_INT8, md._GMM_TILE_K,
                 md._GMM_TILE_N) = saved
        return jax.jit(f)

    @jax.jit
    def einsums(x, live, bp):
        # moe_block as the engine's admission groups hand it prompts
        rows = x.reshape(-1, min(x.shape[0], 1024), D)
        return transformer.moe_block(rows, bp, cfg)[0].reshape(x.shape)

    res = {}
    for name, n, nlive in cases:
        x = jax.random.normal(k[5], (n, D), jnp.float32).astype(jnp.bfloat16)
        live = jnp.arange(n) < nlive
        ref = None
        try:
            ms, ref = timeit(einsums, (x, live, bp))
            res[f"{name}.einsums_ms"] = ms
            res[f"{name}.einsums_GBps"] = 3 * E * D * F / ms / 1e6
        except Exception as e:
            res[f"{name}.einsums_error"] = repr(e)[:300]
        for tiles in tilings:
            tag = "gmm_int8" if tiles is None else "gmm_int8_%d_%d_%d" % tiles
            if tiles is not None and n * K >= md._GMM_LARGE_ROWS \
                    and tiles[0] != MIXTRAL_TILES[1][0]:
                continue  # prefill's m tile is not among these: once each
            try:
                ms, (out, stats) = timeit(dispatch(tiles), (x, live, bp))
            except Exception as e:  # a refusal is a reading too
                res[f"{name}.{tag}_error"] = repr(e)[:300]
                continue
            touched = int(stats["touched"])
            res[f"{name}.{tag}_ms"] = ms
            res[f"{name}.{tag}_GBps"] = gbps = touched * 3 * D * F / ms / 1e6
            res[f"{name}.{tag}_hbm_share"] = gbps * 1e9 / peak["hbm_bytes_per_s"]
            res[f"{name}.{tag}_bf16_share"] = \
                nlive * K * 3 * 2 * D * F / (ms / 1e3) / peak["bf16_flops"]
            if tiles is None:
                res[f"{name}.touched"] = touched
                if ref is not None:
                    a = out.astype(jnp.float32)[:nlive]
                    b = ref.astype(jnp.float32)[:nlive]
                    res[f"{name}.max_abs_diff"] = float(jnp.max(jnp.abs(a - b)))
                    res[f"{name}.max_abs"] = float(jnp.max(jnp.abs(b)))
        if not rehearse:
            print(json.dumps({k_: v for k_, v in res.items()
                              if k_.startswith(name)}), flush=True)
    if rehearse:
        res = {k_: v for k_, v in res.items()
               if not k_.endswith(("_ms", "_GBps", "_share"))}
    else:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "probe_moe_mixtral.json"),
                  "w") as f:
            json.dump(res, f, indent=1)
    print("RESULT " + json.dumps(res))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=("lfm2", "mixtral"), default="lfm2")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.shape == "lfm2":
        lfm2()
    elif args.rehearse:
        from tests.pallas_interpret import pallas_interpret
        with pallas_interpret():
            mixtral(rehearse=True)
    else:
        mixtral()
