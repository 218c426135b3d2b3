"""What the TPU v5e's compiler makes of the engine's own programs, with
no chip: AOT-compile `_chunk_impl` (4 steps, or --steps) or `_admit_impl` of a
benchmark configuration at the cells' 64 slots x 1024 (--slots, --window:
laguna.code runs 32 x 4096) for the described
topology "v5e:2x2" (libtpu compiles for a chip that is not attached;
.claude/skills/verify/SKILL.md), print `memory_analysis()` and list the
instructions of the entry and loop computations (not the insides of
fusions) whose result is at least a quarter of one layer's K: a `copy`
or a stand-alone `dynamic-slice` fusion that large is a relayout the
chip runs on every chunk, step or layer (PERF.md section 6, PR 28, 32).

    JAX_PLATFORMS=cpu python3 tools/inspect_hlo.py mistral-7b-v0.3 chunk
    JAX_PLATFORMS=cpu python3 tools/inspect_hlo.py mixtral-8x7b admit/1024/8 \\
        --dump /root/scratch/admit.hlo.txt

About 20 s a program. Nothing runs: no time comes out of this.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax
import jax.numpy as jnp

SLOTS, WINDOW, STEPS = 64, 1024, 4

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\](\{[^}]*\})?.*? ([\w-]+)\(")
_QUIET = ("parameter", "get-tuple-element", "bitcast", "tuple")


def big_instructions(hlo: str, at_least: int):
    """[(computation, op, result type, name)] of the instructions outside
    fused computations with at least `at_least` result elements."""
    out, comp, skip = [], None, True
    for line in hlo.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            skip = "fused_computation" in comp
            continue
        m = None if skip else _INSTRUCTION.match(line)
        if not m:
            continue
        name, dtype, shape, layout, op = m.groups()
        n = 1
        for d in filter(None, shape.split(",")):
            n *= int(d)
        if n >= at_least and op not in _QUIET:
            out.append((comp, op, f"{dtype}[{shape}]{layout or ''}", name))
    return out


def computations(hlo: str):
    """{name: text} of a compiled program's computations."""
    out, name = {}, None
    for line in hlo.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
        if name:
            out[name] = out.get(name, "") + line + "\n"
    return out


def reachable(comps, name: str):
    """The computations `name` calls, itself among them (a fusion, a
    branch, a loop body, a reducer: whatever its text names)."""
    seen, todo = set(), [name]
    while todo:
        at = todo.pop()
        if at in seen:
            continue
        seen.add(at)
        todo += [c for c in re.findall(r"%([\w.\-]+)", comps[at])
                 if c in comps]
    return seen


def configuration(name: str):
    """(ModelConfig, the init that bears its tree) of a file of
    benchmark/configs, by name, as the file states it."""
    import family

    from seldon_tpu.models import transformer
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import init_params_int8

    here = os.path.join(ROOT, "benchmark")
    with open(os.path.join(here, "configs", name + ".json")) as f:
        raw = json.load(f)
    cfg = ModelConfig(
        **family.load(here, raw).model_config_kwargs(raw)).validate()
    init = init_params_int8 if raw["serving"]["weight_dtype"] == "int8" \
        else transformer.init_params
    return cfg, init


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", help="a file of benchmark/configs, by name")
    ap.add_argument("program", help="chunk, or admit/<bucket>/<group>")
    ap.add_argument("--dump", help="write the optimized HLO text here")
    ap.add_argument("--steps", type=int, default=STEPS,
                    help="decode steps of the chunk (the low rung may be "
                         "sized to 1 or 2: engine._size_low_rung)")
    ap.add_argument("--slots", type=int, default=SLOTS)
    ap.add_argument("--window", type=int, default=WINDOW,
                    help="the engine's max_seq_len (laguna.code: 32 x 4096)")
    args = ap.parse_args(argv)
    # quiets libtpu's search for a host it is not on
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from seldon_tpu.models import slot, transformer
    from seldon_tpu.ops import (decode_attention, moe_dispatch,
                                prefill_attention, ssm_update)
    from seldon_tpu.servers import engine
    from seldon_tpu.servers.engine import InferenceEngine

    # the chip's grouped product, state update and decode attention: the
    # program asks jax.default_backend(), which is the CPU here
    moe_dispatch.grouped_matmul = moe_dispatch._megablox
    ssm_update.update = ssm_update._pallas
    decode_attention.applies = decode_attention.reads
    prefill_attention.applies = prefill_attention.fits
    cfg, init = configuration(args.config)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(make):
        return jax.tree.map(lambda a: shaped(a.shape, a.dtype),
                            jax.eval_shape(make))

    params = shapes(lambda: init(cfg, jax.random.key(0)))
    state = shapes(lambda: slot.fresh(
        transformer.init_cache(cfg, args.slots, args.window), args.slots,
        cfg.gen_block))
    if args.program == "chunk":
        fn = engine._named_partial(InferenceEngine._chunk_impl, cfg=cfg,
                                   n_steps=args.steps)
        more = ()
    else:
        _, Sb, G = args.program.split("/")
        G = int(G)
        fn = engine._named_partial(InferenceEngine._admit_impl, cfg=cfg)
        more = (shaped((G, int(Sb)), jnp.int32),) + tuple(
            shaped((G,), dt) for dt in (
                jnp.int32, jnp.uint32, jnp.float32, jnp.int32, jnp.float32,
                jnp.int32, jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, state, *more).compile()
    hlo = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(hlo)
    ma = compiled.memory_analysis()
    print(f"{args.config} {args.program} for {topo.devices[0].device_kind}: "
          f"temporaries {ma.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, of which aliased to "
          f"outputs {ma.alias_size_in_bytes / 1e9:.3f} GB")
    layer_k = args.slots * args.window * cfg.n_kv_heads * cfg.head_dim
    counts = {}
    for comp, op, typ, _ in big_instructions(hlo, layer_k // 4):
        counts[comp[:32], op, typ] = counts.get((comp[:32], op, typ), 0) + 1
    for (comp, op, typ), n in sorted(counts.items()):
        print(f"  x{n}  {comp:32s} {op:20s} {typ}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
