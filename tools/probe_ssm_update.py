"""Times one decode step's Mamba-2 state update at a configuration's
shapes on the chip: the Pallas kernel (ops/ssm_update._pallas) against
the same arithmetic in jax.numpy (_xla), each over every Mamba-2 layer of
the state, in place (the state donated), and checks that they agree.

    chiprun -- python3 tools/probe_ssm_update.py [--config nemotron-3-nano-30b-a3b] [--slots 64]
    chiprun -- python3 tools/probe_ssm_update.py --config falcon-h1-34b-instruct

(a state of 64 x 64 x 128 a slot and layer, and of 32 x 128 x 256).
About a minute. `--rehearse` runs tiny shapes on the CPU with the kernel
interpreted (no time comes out of that).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def dims_of(raw):
    """(layers that hold an SSM state, heads, head width, groups, state
    size) of a file of benchmark/configs, by its family's key names."""
    if "hybrid_override_pattern" in raw:  # nemotron_h
        return (raw["hybrid_override_pattern"].count("M"), raw["mamba_num_heads"],
                raw["mamba_head_dim"], raw["n_groups"], raw["ssm_state_size"])
    return (raw["num_hidden_layers"], raw["mamba_n_heads"], raw["mamba_d_head"],
            raw["mamba_n_groups"], raw["mamba_d_state"])  # falcon_h1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="nemotron-3-nano-30b-a3b")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "benchmark", "configs", args.config + ".json")) as f:
        raw = json.load(f)

    import contextlib

    import jax
    import jax.numpy as jnp

    from seldon_tpu.ops import ssm_update

    Lm, H, P, G, N = dims_of(raw)
    B = args.slots
    interpret = contextlib.nullcontext()
    if args.rehearse:
        from tests.pallas_interpret import pallas_interpret
        Lm, B, H, P, G, N, args.steps = 2, 2, 4, 16, 2, 16, 2
        interpret = pallas_interpret()
    elif jax.default_backend() != "tpu":
        print("no TPU here: --rehearse, or run through chiprun", file=sys.stderr)
        return 1
    ks = jax.random.split(jax.random.key(0), 5)
    keep = jax.random.uniform(ks[1], (B, H), minval=0.5, maxval=1.0)
    dtx = jax.random.normal(ks[2], (B, H, P)) * 0.1
    b = jax.random.normal(ks[3], (B, G, N)).astype(jnp.bfloat16)
    c = jax.random.normal(ks[4], (B, G, N)).astype(jnp.bfloat16)

    def all_layers(fn):
        def step(state):
            ys = []
            for layer in range(Lm):
                y, state = fn(state, jnp.asarray(layer, jnp.int32), keep, dtx, b, c)
                ys.append(y)
            return state, jnp.stack(ys)
        return jax.jit(step, donate_argnums=(0,))

    out = {"device": jax.devices()[0].device_kind, "shape": [Lm, B, H, P, N]}
    results = {}
    with interpret:
        for name, fn in (("xla", ssm_update._xla), ("pallas", ssm_update._pallas)):
            step = all_layers(fn)
            state = jax.random.normal(ks[0], (Lm, B, H, P, N))
            state, ys = step(state)  # compiles
            jax.block_until_ready(state)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
            t = time.perf_counter()
            for _ in range(args.steps):
                state, ys = step(state)
            jax.block_until_ready(state)  # graftlint: allow(hot-sync) a probe: the sync is the measurement
            per = (time.perf_counter() - t) / args.steps
            results[name] = (state, ys)
            bytes_ = 2 * Lm * B * H * P * N * 4
            out[name] = {"ms_a_step_all_layers": 1e3 * per,
                         "gb_per_s_at_two_passes": bytes_ / per / 1e9}
    (s0, y0), (s1, y1) = results["xla"], results["pallas"]
    out["max_state_diff"] = float(jnp.max(jnp.abs(s0 - s1)))
    out["max_y_diff"] = float(jnp.max(jnp.abs(y0 - y1)))
    if args.rehearse:
        out = {k: v for k, v in out.items() if k in ("shape", "max_state_diff", "max_y_diff")}
    print("PROBE " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
