"""Every position's gap of a benchmark parity job, for setting a
configuration's `parity` numbers: the engine's greedy tokens and the
family's lower-precision control against the plain reference
(benchmark/reference.logit_gaps), not reduced to the marker's counts.

    python3 tools/parity_gaps.py chiprun_out/benchmark/<cell>/parity_job.json [out.jsonl]

Run it after benchmark/run.py has left the job file (the unit has exited:
one process per chip); appends one JSON line {seed, config, gaps,
control_gaps, logit_std}.

benchmark/run.py's own probes end at position 132: they cross no
attention window and reach no prompt bucket over 128. The second mode
makes the job itself, at any lengths:

    python3 tools/parity_gaps.py --serve <configuration> --lengths 300,700,1500,3000 \\
        --new 32 --seeds 11,12,13,14 --slots 32 --window 4096 --out readings.jsonl

For each seed it starts the unit as benchmark/run.py does (a child:
benchmark/launcher.py -> the normal microservice entry point, REST,
platform "tpu"), sends one greedy probe of each length ALONE, then, behind
a long admission so that a group can form, each length again TOGETHER
with a second prompt of the same bucket (length - 41): admission groups
of two rows of unequal length. It stops the unit, and judges every probe
in a second child (this file's first mode; this process never imports
JAX, so the chip is free for each child in turn). Each line then also
holds `probe_lens` and `modes` ("alone" / "group"), in the order of the
gaps (`new` gaps a probe), and the admission variants that ran."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def _gaps(reference, fam, params, cfg, probes):
    """reference.logit_gaps with the control. A family whose
    teacher-forced logits depend on where the prompt ends (its
    forward_logits takes `prompt_len`: generation by diffusion over
    blocks, where a prompt that ends inside a block shifts which
    positions were decided together) is judged a probe at a time, told
    each probe's; the harness's own probes end on a block and need none."""
    import functools
    import inspect
    import types

    if "prompt_len" not in inspect.signature(fam.forward_logits).parameters:
        return reference.logit_gaps(fam, params, cfg, probes, control=True)
    gaps, control = [], []
    for prompt, toks in probes:
        told = types.SimpleNamespace(forward_logits=functools.partial(
            fam.forward_logits, prompt_len=len(prompt)))
        g, c = reference.logit_gaps(told, params, cfg, [(prompt, toks)], control=True)
        gaps += g
        control += c
    return gaps, control


def main(job_file: str, out_file: str = "") -> int:
    import family
    import reference

    with open(job_file) as f:
        job = json.load(f)
    with open(job["config"]) as f:
        cfg = json.load(f)
    from seldon_tpu import device

    device.enable_compile_cache()  # a probe's length is a shape: seeds share the programs
    fam = family.load(os.path.join(ROOT, "benchmark"), cfg)
    params = fam.build_params(cfg, int(job["seed"]))
    gaps, control = _gaps(reference, fam, params, cfg, job["probes"])
    # the scale the gaps are read against: the reference's own spread of
    # logits over the vocabulary at the first probe's generated positions
    import jax.numpy as jnp
    prompt, toks = job["probes"][0]
    seq = jnp.asarray(list(prompt) + list(toks[:-1]), jnp.int32)
    std = float(jnp.std(fam.forward_logits(params, seq, cfg)[len(prompt) - 1:]))
    line = json.dumps({"seed": job["seed"], "config": cfg["name"], "gaps": gaps,
                       "control_gaps": control, "logit_std": std,
                       **{k: job[k] for k in ("probe_lens", "modes", "new", "variants")
                          if k in job}})
    print(line, flush=True)
    if out_file:
        os.makedirs(os.path.dirname(os.path.abspath(out_file)), exist_ok=True)
        with open(out_file, "a") as f:
            f.write(line + "\n")
    return 0


def serve(argv) -> int:
    """The second mode: see the module's docstring."""
    import argparse
    import asyncio
    import random
    import subprocess

    import client
    import run as bench_run
    import traffic

    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", required=True, help="a file of benchmark/configs, by name")
    ap.add_argument("--lengths", default="300,700,1500,3000")
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--seeds", default="11,12,13,14")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset on the CPU; probes only, no judging")
    args = ap.parse_args(argv)
    bench = os.path.join(ROOT, "benchmark")
    config_file = os.path.join(bench, "configs", args.serve + ".json")
    with open(config_file) as f:
        cfg = json.load(f)
    lengths = [int(n) for n in args.lengths.split(",")]
    work = os.path.join(ROOT, "chiprun_out", "parity_probes", args.serve)
    os.makedirs(work, exist_ok=True)

    def request(plen, rng_seed):
        rng = random.Random(rng_seed)
        return traffic.Request(-1, "probe", plen, args.new, None,
                               [rng.randrange(256 if args.rehearse else cfg["vocab_size"])
                                for _ in range(plen)])

    async def probe(seed):
        port = bench_run.free_port()
        params = [
            {"name": "preset", "type": "STRING",
             "value": cfg["rehearse_preset"] if args.rehearse else cfg["name"]},
            {"name": "init_seed", "value": str(seed), "type": "INT"},
            {"name": "tp", "value": "1", "type": "INT"},
            {"name": "max_slots", "value": str(args.slots), "type": "INT"},
            {"name": "max_seq_len", "value": str(args.window), "type": "INT"},
            {"name": "platform", "value": "cpu" if args.rehearse else "tpu",
             "type": "STRING"}]
        if not args.rehearse:
            params.append({"name": "weight_dtype", "type": "STRING",
                           "value": cfg["serving"]["weight_dtype"]})
        env = dict(os.environ, PYTHONPATH=ROOT, COMPILE_LEDGER="1", SELDON_TPU_FASTPATH="0")
        if args.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        log = os.path.join(work, f"unit_{seed}.log")
        with open(log, "wb") as out:
            child = subprocess.Popen(
                [sys.executable, os.path.join(bench, "launcher.py"), "--config", config_file,
                 *(("--preset-name", "bench-" + cfg["name"]) if args.rehearse else ()), "--", "seldon_tpu.servers.jaxserver.JAXServer", "--api-type", "REST",
                 "--host", "127.0.0.1", "--http-port", str(port),
                 "--parameters", json.dumps(params)],
                cwd=ROOT, env=env, stdout=out, stderr=subprocess.STDOUT)
        try:
            async with client.Unit(f"http://127.0.0.1:{port}") as unit:
                while True:
                    if child.poll() is not None:
                        raise RuntimeError(f"unit exited rc={child.returncode}: "
                                           + bench_run.tail_of(log))
                    try:
                        if (await unit.get("/ready", timeout=5.0))[0] == 200:
                            break
                    except Exception:
                        pass
                    await asyncio.sleep(0.5)
                probes, lens, modes = [], [], []
                for i, n in enumerate(lengths):
                    r = await unit.generate(request(n, 1000 + i))
                    if not r.ok:
                        raise RuntimeError(f"probe of {n} failed: {r.error}")
                    probes.append((r.req.prompt_ids, r.tokens))
                    lens.append(n)
                    modes.append("alone")
                buckets = sorted((await unit.get_json("/metadata"))["engine"]["prompt_buckets"])

                async def formed(n):
                    key = f"admit/{next(b for b in buckets if n <= b)}/2"
                    snap = await unit.get_json("/debug/compile")
                    return any(v["key"] == key for v in snap["lattice"])

                for i, n in enumerate(lengths):
                    # a group forms from what waits at one chunk boundary: both
                    # arrive while the longest admission there is runs ahead of
                    # them; sent again (other prompts) until the variant has run
                    for attempt in range(4):
                        ahead = asyncio.create_task(unit.generate(
                            request(max(lengths), 7 + 10 * attempt + i)))
                        await asyncio.sleep(0.03)
                        pair = await asyncio.gather(
                            unit.generate(request(n, 2000 + 10 * attempt + i)),
                            unit.generate(request(n - 41, 3000 + 10 * attempt + i)))
                        await ahead
                        if await formed(n):
                            break
                    for r in pair:
                        if not r.ok:
                            raise RuntimeError(f"grouped probe of {n} failed: {r.error}")
                        probes.append((r.req.prompt_ids, r.tokens))
                        lens.append(r.req.prompt_len)
                        modes.append("group")
                snap = await unit.get_json("/debug/compile")
                md = await unit.get_json("/metadata")
        finally:
            bench_run.stop_child(child)
        variants = sorted(v["key"] for v in (snap or {}).get("lattice", [])
                          if v["key"].startswith("admit/"))
        peak = max((m["peak_bytes_in_use"] or 0 for m in md["device"]["memory"]), default=0)
        print(f"[probes] seed {seed}: {len(probes)} probes, variants {variants}, peak HBM "
              f"{peak / 1e9:.2f} GB, cache_bytes {md['cache_bytes']}", flush=True)
        return {"config": config_file, "seed": seed, "probes": probes, "probe_lens": lens,
                "modes": modes, "new": args.new, "variants": variants}

    for seed in (int(x) for x in args.seeds.split(",")):
        job_file = os.path.join(work, f"job_{seed}.json")
        with open(job_file, "w") as f:
            json.dump(asyncio.run(probe(seed)), f)
        if args.rehearse:
            continue
        rc = subprocess.run([sys.executable, os.path.abspath(__file__), job_file, args.out],
                            cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT)).returncode
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(serve(sys.argv[1:]) if "--serve" in sys.argv
             else main(*sys.argv[1:3]))
