"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, the way a deployment does, at the full width
and depth of llama3-8b with seeded random int8 weights:

  1. SERVER. A child process runs
     ``python -m seldon_tpu.runtime.microservice
     seldon_tpu.servers.jaxserver.JAXServer`` (REST) pinned to the
     expected platform and to ``tp`` devices. This process waits for
     /ready, reads /metadata (platform, device_kind, mesh, model width),
     then sends: one greedy request per prompt bucket (cold: compiles),
     the first prompt over /generate_stream (concatenated chunks must
     equal the /generate answer), a concurrent burst across both
     buckets, and the first request again (must repeat token for token).
     /metrics must then count every request completed and none failed.
     The child is stopped before anything else touches the chip.
  2. KERNEL. Only after the child has exited does this process import
     JAX: the flash-attention Pallas kernel runs COMPILED on the chip at
     this model's head geometry, at a prefill shape, and is compared
     with its jnp reference.

A chip belongs to one process at a time, hence the order. Set-up
seconds, compile seconds and peak HBM are printed as bring-up
information, not metrics. The last stdout line is one JSON object,
``{"ok": true, "device": {...}}``; any failed phase exits non-zero
without it, and so does any platform but a TPU.

CPU rehearsal (tiny preset, interpreted kernels; same code otherwise):
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# 16 GB of HBM: ~8.1 GB of int8 weights; bf16 KV is 128 KiB per token
# (2 x 32 layers x 8 kv heads x 128 x 2 B), so 16 slots x 1024 tokens
# reserve 2 GiB and leave >4 GB for prefill activations and XLA's
# temporaries. The preset default (32 x 8192) would be 34 GB.
SLOTS, WINDOW = 16, 1024
LOAD_TIMEOUT_S = 600.0
REQUEST_TIMEOUT_S = 420.0
NEW_TOKENS = 8

# What /metadata must report for llama3-8b to count as "full width".
FULL_WIDTH = dict(n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
                  d_ff=14336, vocab_size=128256)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------
# Phase 1: the server, in a child process; this process stays off JAX.
# --------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Loopback only: never through a proxy the environment may name.
_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 30.0) -> tuple[int, bytes]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with _OPENER.open(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _generate(base: str, prompt: str, **sampling) -> tuple[dict, float]:
    t0 = time.perf_counter()
    status, raw = _http(
        "POST", base + "/generate",
        {"prompt": prompt, "max_new_tokens": NEW_TOKENS, **sampling},
        timeout=REQUEST_TIMEOUT_S,
    )
    dt = time.perf_counter() - t0
    check(status == 200, f"/generate -> HTTP {status}: {raw[:300]!r}")
    return json.loads(raw), dt


def _generate_stream(base: str, prompt: str) -> tuple[list[int], int, float]:
    t0 = time.perf_counter()
    status, raw = _http(
        "POST", base + "/generate_stream",
        {"prompt": prompt, "max_new_tokens": NEW_TOKENS},
        timeout=REQUEST_TIMEOUT_S,
    )
    dt = time.perf_counter() - t0
    check(status == 200, f"/generate_stream -> HTTP {status}: {raw[:300]!r}")
    toks: list[int] = []
    chunks = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    for c in chunks:
        check("error" not in c, f"stream error trailer: {c}")
        toks.extend(c.get("token_ids", []))
    return toks, len(chunks), dt


def _check_tokens(out: dict, vocab: int, what: str) -> list[int]:
    toks = out.get("token_ids", [])
    check(1 <= len(toks) <= NEW_TOKENS,
          f"{what}: {len(toks)} tokens, wanted 1..{NEW_TOKENS}: {out}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"{what}: token id outside [0, {vocab}): {toks}")
    return toks


def _gauge(text: str, name: str) -> float:
    for ln in text.splitlines():
        if ln.startswith(name + " ") or ln.startswith(name + "{"):
            return float(ln.rsplit(" ", 1)[1])
    raise SmokeFailure(f"/metrics has no {name}")


def _tail(path: str, n: int = 25) -> str:
    try:
        with open(path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(
                "utf-8", "replace")
    except OSError:
        return "(no server log)"


def run_server_phase(args) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, "server.log")
    port = _free_port()
    params = [
        {"name": "preset", "value": args.preset, "type": "STRING"},
        {"name": "weight_dtype", "value": "int8", "type": "STRING"},
        {"name": "tp", "value": str(args.tp), "type": "INT"},
        {"name": "max_slots", "value": str(SLOTS), "type": "INT"},
        {"name": "max_seq_len", "value": str(args.window), "type": "INT"},
        {"name": "platform", "value": args.platform, "type": "STRING"},
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["COMPILE_LEDGER"] = "1"       # /debug/compile: seconds per variant
    env["SELDON_TPU_FASTPATH"] = "0"  # REST only: no second listener
    cmd = [
        sys.executable, "-m", "seldon_tpu.runtime.microservice",
        "seldon_tpu.servers.jaxserver.JAXServer",
        "--api-type", "REST", "--host", "127.0.0.1",
        "--http-port", str(port), "--parameters", json.dumps(params),
    ]
    info("server: " + " ".join(cmd[:4]) + f" ... (log {log_path})")
    t_spawn = time.perf_counter()
    with open(log_path, "wb") as log:
        child = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log,
                                 stderr=subprocess.STDOUT)
    try:
        return _drive(child, f"http://127.0.0.1:{port}", t_spawn, log_path,
                      args)
    finally:
        _stop(child)


def _stop(child: subprocess.Popen) -> None:
    """SIGINT (the CLI's clean exit: listeners closed, runtime torn
    down), then SIGKILL: the chip must be free before phase 2."""
    if child.poll() is None:
        child.send_signal(signal.SIGINT)
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(timeout=30)
    info(f"server child exited rc={child.returncode}")


def _drive(child, base: str, t_spawn: float, log_path: str, args) -> dict:
    # -- load: the port opens only once load() has returned ---------------
    while True:
        if child.poll() is not None:
            raise SmokeFailure(
                f"server exited rc={child.returncode} before /ready; "
                f"log tail:\n{_tail(log_path)}")
        if time.perf_counter() - t_spawn > LOAD_TIMEOUT_S:
            raise SmokeFailure(
                f"no /ready within {LOAD_TIMEOUT_S:.0f}s; log tail:\n"
                f"{_tail(log_path)}")
        try:
            status, _ = _http("GET", base + "/ready", timeout=5.0)
            if status == 200:
                break
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    load_s = time.perf_counter() - t_spawn
    info(f"load: /ready 200 after {load_s:.1f}s (process start, JAX "
         f"init, int8 weight init, engine init)")

    # -- where did it run, and is it the model we asked for? --------------
    status, raw = _http("GET", base + "/metadata")
    check(status == 200, f"/metadata -> HTTP {status}")
    md = json.loads(raw)
    check("device" in md, f"/metadata carries no device: {md}")
    dev = md["device"]
    info(f"device: platform={dev['platform']} kind={dev['device_kind']!r} "
         f"visible={dev['count']} mesh={md['mesh']} "
         f"mesh_devices={md['mesh_devices']}")
    check(dev["platform"] == args.platform,
          f"server ran on platform {dev['platform']!r}, not "
          f"{args.platform!r}")
    check(len(md["mesh_devices"]) == args.tp,
          f"mesh spans {md['mesh_devices']}, wanted {args.tp} device(s)")
    cfg = md["config"]
    check(cfg["weight_dtype"] == "int8", f"weights are {cfg['weight_dtype']}")
    for k, v in ({} if args.rehearse else FULL_WIDTH).items():
        check(cfg[k] == v, f"{args.preset} {k}={cfg[k]}, full width is {v}")
    eng = md["engine"]
    check((eng["max_slots"], eng["max_seq_len"]) == (SLOTS, args.window),
          f"engine is {eng}, asked for {SLOTS} x {args.window}")
    buckets = sorted(eng["prompt_buckets"])
    check(len(buckets) >= 2, f"need two prompt buckets, have {buckets}")
    info(f"model: {args.preset} L={cfg['n_layers']} d={cfg['d_model']} "
         f"H={cfg['n_heads']}/{cfg['n_kv_heads']} ff={cfg['d_ff']} "
         f"V={cfg['vocab_size']} weights={cfg['weight_dtype']} "
         f"kv={cfg['kv_cache_dtype']}; engine {SLOTS} slots x "
         f"{args.window} window, buckets {buckets}")
    vocab = cfg["vocab_size"]

    # One prompt per bucket: the byte tokenizer makes tokens == bytes.
    short = "s" * (buckets[0] - 12)
    long_ = "l" * (buckets[1] - 12)
    sent = 0

    # -- cold: first request of each shape pays trace + compile -----------
    a, cold_short_s = _generate(base, short)
    greedy = _check_tokens(a, vocab, "cold short")
    b, cold_long_s = _generate(base, long_)
    _check_tokens(b, vocab, "cold long")
    sent += 2
    check(a["prompt_tokens"] == len(short)
          and b["prompt_tokens"] == len(long_),
          f"prompt lengths {a['prompt_tokens']}, {b['prompt_tokens']}")
    info(f"cold: bucket {buckets[0]} {cold_short_s:.1f}s, bucket "
         f"{buckets[1]} {cold_long_s:.1f}s (trace + compile + run)")

    # -- warm: the stream must equal the /generate answer ------------------
    stoks, n_chunks, stream_s = _generate_stream(base, short)
    sent += 1
    check(stoks == greedy,
          f"stream {stoks} != /generate {greedy} ({n_chunks} chunks)")
    info(f"warm: stream identical in {stream_s:.2f}s over {n_chunks} "
         f"chunk(s); tokens {greedy}")

    # -- concurrent burst across both buckets (may compile new group
    #    sizes: its time is set-up information too) ------------------------
    prompts = [short] + [short[:-1 - i] + "x" for i in range(2)] \
        + [long_[:-1 - i] + "y" for i in range(3)]
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = [pool.submit(_generate, base, p, temperature=0.7, seed=i + 1)
                if i else pool.submit(_generate, base, p)
                for i, p in enumerate(prompts)]
        outs = [f.result()[0] for f in futs]
    burst_s = time.perf_counter() - t0
    sent += len(prompts)
    for i, o in enumerate(outs):
        _check_tokens(o, vocab, f"burst[{i}]")
    # Observation, not a gate: a co-batched prefill is a different
    # compiled program (group size) from the solo one.
    info(f"burst: {len(prompts)} concurrent answered in {burst_s:.1f}s; "
         f"greedy row {'==' if outs[0]['token_ids'] == greedy else '!='} "
         f"its solo answer")

    # -- warm: greedy must repeat, token for token, after all of that. It
    #    is also the LAST request and a solo one: user gauges are absorbed
    #    after each /generate response, so /metrics below has seen it all. -
    a2, warm_s = _generate(base, short)
    sent += 1
    check(_check_tokens(a2, vocab, "warm short") == greedy,
          f"greedy repeat differs: {greedy} vs {a2['token_ids']}")
    info(f"warm: greedy repeat identical in {warm_s:.2f}s")

    # -- /metrics: every request completed, none failed --------------------
    status, raw = _http("GET", base + "/metrics")
    check(status == 200, f"/metrics -> HTTP {status}")
    text = raw.decode()
    completed = _gauge(text, "jaxserver_completed")
    failed = _gauge(text, "jaxserver_failed_total")
    check(failed == 0, f"/metrics: jaxserver_failed_total={failed}")
    check(completed == sent,
          f"/metrics: jaxserver_completed={completed}, sent {sent}")
    info(f"metrics: completed={completed:.0f} of {sent} sent, failed=0")

    # -- set-up information: compile seconds, peak HBM ---------------------
    status, raw = _http("GET", base + "/debug/compile")
    check(status == 200, f"/debug/compile -> HTTP {status}")
    comp = json.loads(raw)
    lattice = ", ".join(
        f"{v['key']}={v['first_dispatch_ms'] / 1000.0:.1f}s"
        for v in comp["lattice"])
    info(f"compile: {comp['dispatched_variants']} variants, first "
         f"dispatches total {comp['compile_s_total']:.1f}s [{lattice}]")
    status, raw = _http("GET", base + "/metadata")
    mem = json.loads(raw)["device"]["memory"]
    peaks = [m["peak_bytes_in_use"] for m in mem]
    info("hbm: " + "; ".join(
        f"dev{m['id']} in_use={_gb(m['bytes_in_use'])} "
        f"peak={_gb(m['peak_bytes_in_use'])} limit={_gb(m['bytes_limit'])}"
        for m in mem))
    if not args.rehearse:
        check(all(p is not None for p in peaks), "no memory_stats on TPU")
        check(max(peaks) < 16e9, f"peak HBM {max(peaks)} B is not under 16 GB")
    return {"load_s": load_s, "compile_s": comp["compile_s_total"]}


def _gb(n) -> str:
    return "n/a" if n is None else f"{n / 1e9:.2f}GB"


# --------------------------------------------------------------------------
# Phase 2: the kernels, in this process, after the child has gone.
# --------------------------------------------------------------------------


def run_kernel_phase(args) -> dict:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    from seldon_tpu import device
    from seldon_tpu.ops.flash_attention import (
        attention_reference,
        flash_attention,
    )

    cache_dir = device.enable_compile_cache()
    dev = jax.devices()[0]
    found = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    check(found["platform"] == args.platform,
          f"kernel phase found platform {found['platform']!r}, not "
          f"{args.platform!r}")
    info(f"kernels: on {found}, compile cache {cache_dir}")
    if not args.rehearse:
        # llama3-8b head geometry; prefill at the 512 bucket.
        Hkv, G, Dh, S = 8, 4, 128, 512
        mode, how = contextlib.nullcontext(), "compiled"
    else:
        # Rehearsal: same code, interpreted, at a size a CPU finishes.
        from jax.experimental.pallas import tpu as pltpu

        Hkv, G, Dh, S = 2, 2, 16, 32
        mode, how = pltpu.force_tpu_interpret_mode(), "INTERPRETED"
    key = jax.random.key(0)

    def close(got, want, what, atol, rtol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape, f"{what}: shape {got.shape}")
        check(np.isfinite(got).all(), f"{what}: non-finite output")
        err = float(np.abs(got - want).max())
        check(np.allclose(got, want, atol=atol, rtol=rtol),
              f"{what}: max |err| {err:.3g} vs reference")
        info(f"kernel {what}: {how}, matches reference "
             f"(max |err| {err:.2e})")

    with mode:
        # flash attention, causal GQA prefill: q [H, S, Dh], kv [Hkv, S, Dh]
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (Hkv * G, S, Dh), jnp.bfloat16)
        k = jax.random.normal(kk, (Hkv, S, Dh), jnp.bfloat16)
        v = jax.random.normal(kv, (Hkv, S, Dh), jnp.bfloat16)
        got = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, q_per_kv=G))(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = attention_reference(
                q.astype(jnp.float32),
                jnp.repeat(k, G, axis=0).astype(jnp.float32),
                jnp.repeat(v, G, axis=0).astype(jnp.float32), causal=True)
        close(got, want, f"flash_attention[{Hkv * G}x{S}x{Dh}]", 3e-2, 3e-2)
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny preset, interpreted kernels")
    ap.add_argument("--tp", type=int, default=1,
                    help="devices the unit serves from")
    args = ap.parse_args(argv)
    args.preset, args.platform, args.window = (
        ("tiny", "cpu", 128) if args.rehearse
        else ("llama3-8b", "tpu", WINDOW))
    if not os.path.isdir(os.path.join(HERE, "seldon_tpu")):
        print("chip_smoke: no seldon_tpu/ next to this script — nothing to "
              "smoke", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    try:
        setup = run_server_phase(args)
        found = run_kernel_phase(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    info(f"done in {time.perf_counter() - t0:.0f}s: load {setup['load_s']:.1f}s, "
         f"first dispatches {setup['compile_s']:.1f}s")
    print(json.dumps({"ok": True, "device": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
