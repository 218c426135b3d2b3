"""Continuous-batching engine + JAXServer tests (tiny config, CPU mesh)."""

import queue
import threading
import time

import numpy as np
import pytest

from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from seldon_tpu.servers.jaxserver import JAXServer
from seldon_tpu.servers.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def engine():
    import jax

    from seldon_tpu.models import init_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(
        params,
        cfg,
        EngineConfig(max_slots=4, max_seq_len=64, prompt_buckets=(8, 16, 32)),
    )
    eng.start()
    yield eng
    eng.stop()


def test_engine_single_request(engine):
    res = engine.generate_blocking(
        [3, 4, 5], SamplingParams(temperature=0.0, max_new_tokens=8)
    )
    assert 1 <= len(res["token_ids"]) <= 8
    assert res["ttft_ms"] is not None and res["ttft_ms"] > 0


def test_engine_deterministic_greedy(engine):
    a = engine.generate_blocking(
        [7, 8, 9], SamplingParams(temperature=0.0, max_new_tokens=6)
    )
    b = engine.generate_blocking(
        [7, 8, 9], SamplingParams(temperature=0.0, max_new_tokens=6)
    )
    assert a["token_ids"] == b["token_ids"]


def test_engine_concurrent_matches_solo(engine):
    """Continuous batching must not change greedy outputs: run the same
    prompt alone vs alongside 3 other concurrent requests."""
    solo = engine.generate_blocking(
        [11, 12, 13], SamplingParams(temperature=0.0, max_new_tokens=6)
    )

    results = {}

    def worker(i, prompt):
        results[i] = engine.generate_blocking(
            prompt, SamplingParams(temperature=0.0, max_new_tokens=6)
        )

    threads = [
        threading.Thread(target=worker, args=(i, p))
        for i, p in enumerate(
            [[11, 12, 13], [20, 21], [30, 31, 32, 33], [40]]
        )
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert results[0]["token_ids"] == solo["token_ids"]


def test_engine_more_requests_than_slots(engine):
    """8 requests through 4 slots: all complete."""
    qs = [
        engine.submit([i + 2, i + 3], SamplingParams(temperature=0.5,
                                                     max_new_tokens=4))
        for i in range(8)
    ]
    done = 0
    for q_ in qs:
        while True:
            item = q_.get(timeout=60)
            if item is None:
                done += 1
                break
    assert done == 8


def test_engine_rejects_oversized_prompt(engine):
    with pytest.raises(ValueError):
        engine.submit(list(range(64)), SamplingParams())


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "hello TPU ⚡"
    assert tok.decode(tok.encode(s)) == s


@pytest.fixture(scope="module")
def server():
    srv = JAXServer(preset="tiny", max_slots=4, max_seq_len=64)
    srv.load()
    yield srv
    srv.engine.stop()


def test_jaxserver_generate(server):
    out = server.generate(
        {"prompt": "hi", "max_new_tokens": 8, "temperature": 0.0}
    )
    assert out["completion_tokens"] >= 1
    assert out["ttft_ms"] > 0
    assert out["prompt_tokens"] == 2


def test_jaxserver_generate_stream(server):
    # None chunks are heartbeats (disconnect poll points between token
    # bursts) — transports drop them, and so do direct consumers.
    chunks = [
        c for c in server.generate_stream(
            {"prompt": "abc", "max_new_tokens": 5, "temperature": 0.0}
        ) if c is not None
    ]
    assert 1 <= len(chunks) <= 5
    assert chunks[0]["ttft_ms"] > 0


def test_loadtester_generate_against_live_server(server, capsys):
    """`loadtester --transport generate` driven at a LIVE /generate
    endpoint (the tiny JAXServer fixture behind the real REST app):
    tokens/s and completion accounting must be sane."""
    import asyncio
    import json as _json
    import threading

    from aiohttp import web

    from seldon_tpu.loadtester import main as lt_main
    from seldon_tpu.runtime.wrapper import build_rest_app

    holder, started = {}, threading.Event()

    async def amain():
        runner = web.AppRunner(build_rest_app(server))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        while not holder.get("stop"):
            await asyncio.sleep(0.05)
        await runner.cleanup()

    t = threading.Thread(target=lambda: asyncio.run(amain()), daemon=True)
    t.start()
    assert started.wait(30)
    try:
        lt_main([
            f"http://127.0.0.1:{holder['port']}", "--transport", "generate",
            "--clients", "2", "--seconds", "2", "--prompt", "hi",
            "--max-new-tokens", "4",
        ])
    finally:
        holder["stop"] = True
        t.join(timeout=10)
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "loadtest_generate_req_per_s"
    assert out["value"] > 0
    d = out["detail"]
    assert d["errors"] == 0
    # Closed-loop accounting: every completed request produced >= 1 and
    # <= max_new_tokens tokens.
    assert d["requests"] >= 1
    assert d["requests"] <= d["completion_tokens"] <= 4 * d["requests"]
    assert d["tokens_per_s"] > 0
    # Default transport is now the NDJSON stream: per-stream TTFT/ITL
    # percentiles ride along in the summary.
    for q in (50, 95, 99):
        assert d[f"ttft_p{q}_ms"] > 0
        assert d[f"itl_p{q}_ms"] >= 0


def test_jaxserver_metadata_says_where_it_ran(server):
    """/metadata carries platform / device_kind / device count, so a
    client tells a TPU from a CPU without importing JAX."""
    import asyncio

    import jax
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_tpu.runtime.wrapper import build_rest_app

    async def fetch():
        async with TestClient(TestServer(build_rest_app(server))) as c:
            r = await c.get("/metadata")
            assert r.status == 200
            return await r.json()

    md = asyncio.run(fetch())
    dev = md["device"]
    assert dev["platform"] == "cpu"
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices())
    assert sorted(md["mesh_devices"]) == list(range(len(jax.devices())))
    assert md["engine"] == {"max_slots": 4, "max_seq_len": 64,
                            "prompt_buckets": [32], "max_admit": 4,
                            "decode_chunk": [4, 8], "rest_workers": 8}


def test_jaxserver_int8_preset_is_born_int8(monkeypatch):
    """Synthetic int8 weights never pass through a bf16 tree: the bf16
    initialiser and the whole-tree quantiser are both off limits (a
    bf16 llama3-8b is 16 GB on a 16 GB chip), every matmul leaf lands
    int8, and the server generates."""
    import jax.numpy as jnp

    from seldon_tpu.models import quantize, transformer

    def forbidden(*a, **kw):
        raise AssertionError("a bf16 weight tree was materialised")

    monkeypatch.setattr(transformer, "init_params", forbidden)
    real_leaf = quantize._quantize_leaf

    def slice_only(w):
        assert w.ndim == 2, f"quantised a stacked leaf {w.shape}"
        return real_leaf(w)

    monkeypatch.setattr(quantize, "_quantize_leaf", slice_only)
    srv = JAXServer(preset="tiny", weight_dtype="int8", max_slots=2,
                    max_seq_len=64, tp=1)
    srv.load()
    try:
        assert srv.cfg.weight_dtype == "int8"
        blocks = srv.params["blocks"]
        for name in quantize._BLOCK_WEIGHTS:
            assert blocks[name].dtype == jnp.int8, name
        assert srv.params["embed"].dtype == jnp.int8
        assert srv.init_metadata()["mesh_devices"] == [0]
        out = srv.generate(
            {"prompt": "hi", "max_new_tokens": 4, "temperature": 0.0})
        assert out["completion_tokens"] >= 1
        # One compile per variant: the engine state is committed next to
        # the mesh-committed weights, so the second request re-uses the
        # first one's admission program.
        srv.generate(
            {"prompt": "hi", "max_new_tokens": 4, "temperature": 0.0})
        assert srv.engine._jit_admit._cache_size() == 1
    finally:
        srv.engine.stop()


def test_jaxserver_platform_pin_refuses_another_platform():
    srv = JAXServer(preset="tiny", platform="tpu")
    with pytest.raises(RuntimeError, match="requires platform 'tpu'.*'cpu'"):
        srv.load()


def test_jaxserver_predict_scores(server):
    scores = server.predict(np.array([[3, 4, 5, 6]]), [])
    assert scores.shape == (1,)
    assert np.isfinite(scores).all()


def test_jaxserver_metrics_tags(server):
    server.generate({"prompt": "x", "max_new_tokens": 2})
    m = server.metrics()
    keys = {d["key"] for d in m}
    assert {"jaxserver_mean_ttft_ms", "jaxserver_slots_busy",
            "jaxserver_decode_dispatches",
            "jaxserver_decode_steps"} <= keys
    stats = {d["key"]: d["value"] for d in m}
    assert stats["jaxserver_decode_dispatches"] >= 1
    assert stats["jaxserver_decode_steps"] >= stats[
        "jaxserver_decode_dispatches"]
    assert server.tags()["server"] == "jaxserver"


def test_checkpoint_roundtrip(tmp_path):
    import jax

    from seldon_tpu.models import init_params
    from seldon_tpu.servers import checkpoint as ckpt

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    path = str(tmp_path / "ckpt")
    ckpt.save_checkpoint(path, params, cfg)
    params2, cfg2 = ckpt.load_checkpoint(path)
    assert cfg2 == cfg
    flat1 = jax.tree.leaves(params)
    flat2 = jax.tree.leaves(params2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_seed_reproducible_across_traffic(engine):
    """Same (seed, prompt) must reproduce the completion regardless of what
    else shares the batch (per-row position-keyed sampling)."""
    sp = SamplingParams(temperature=1.0, max_new_tokens=6, seed=42)
    solo = engine.generate_blocking([5, 6, 7], sp)
    # Re-run with 3 noisy co-scheduled requests.
    noise = [
        engine.submit([9, 9], SamplingParams(temperature=1.0, max_new_tokens=6,
                                             seed=i))
        for i in range(3)
    ]
    busy = engine.generate_blocking([5, 6, 7], sp)
    for q_ in noise:
        while q_.get(timeout=60) is not None:
            pass
    assert solo["token_ids"] == busy["token_ids"]


def test_engine_restart():
    import jax

    from seldon_tpu.models import init_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_slots=2, max_seq_len=32,
                                  prompt_buckets=(8,))
    )
    eng.start()
    r1 = eng.generate_blocking([3, 4], SamplingParams(temperature=0.0,
                                                      max_new_tokens=3))
    eng.stop()
    eng.start()
    r2 = eng.generate_blocking([3, 4], SamplingParams(temperature=0.0,
                                                      max_new_tokens=3))
    eng.stop()
    assert r1["token_ids"] == r2["token_ids"]


def test_engine_buckets_clamped_to_window():
    import jax

    from seldon_tpu.models import init_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    # No bucket fits the window: engine must clamp, not crash on submit.
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_slots=2, max_seq_len=16,
                                  prompt_buckets=(32, 128))
    )
    eng.start()
    r = eng.generate_blocking([3, 4], SamplingParams(temperature=0.0,
                                                     max_new_tokens=2))
    eng.stop()
    assert len(r["token_ids"]) >= 1


def test_jaxserver_explicit_greedy(server):
    """temperature=0.0 must be honored (not replaced by a default)."""
    a = server.generate({"prompt": "zz", "max_new_tokens": 4, "temperature": 0.0})
    b = server.generate({"prompt": "zz", "max_new_tokens": 4, "temperature": 0.0})
    assert a["token_ids"] == b["token_ids"]


def test_storage_relative_key():
    from seldon_tpu.servers.storage import _relative_key

    assert _relative_key("models/a/x.bin", "models/a") == "x.bin"
    assert _relative_key("models/ab/x.bin", "models/a") is None
    assert _relative_key("models/a", "models/a") == "a"
    assert _relative_key("k", "") == "k"


def test_engine_bad_request_fails_cleanly(engine):
    """An admission failure must fail that request only (no wedged loop);
    the engine keeps serving afterwards. Also: absurd seeds are clamped,
    not fatal."""
    real_admit = engine._jit_admit

    def boom(*a, **k):
        raise ValueError("injected prefill failure")

    engine._jit_admit = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            engine.generate_blocking(
                [3, 4], SamplingParams(temperature=0.0, max_new_tokens=2)
            )
    finally:
        engine._jit_admit = real_admit
    # Engine still serves, including a seed far beyond uint32.
    ok = engine.generate_blocking(
        [3, 4], SamplingParams(temperature=1.0, max_new_tokens=2, seed=2**80)
    )
    assert len(ok["token_ids"]) >= 1


def test_engine_async_dispatch_failure_fails_all_clients():
    """A dispatch error must fail EVERY in-flight request — including ones
    optimistically recycled out of the slot table and ones whose
    boundaries sit in the fetch queue — with an error + terminator, never
    a hang (round-3 review finding on the async fetcher)."""
    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.models.sampling import SamplingParams
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=48, prompt_buckets=(8,), decode_chunk=4))
    eng.warmup()

    real_chunks = dict(eng._jit_chunks)
    calls = {"n": 0}

    def flaky_for(n):
        def flaky(*a, **k):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("injected device error")
            return real_chunks[n](*a, **k)
        return flaky

    eng._jit_chunks = {n: flaky_for(n) for n in eng._chunk_sizes}
    # 8 requests / 4 slots: two waves, so the failure lands while some
    # requests wait and some are mid-decode/recycled.
    qs = [eng.submit([3 + i] * 5, SamplingParams(
        temperature=0.5, max_new_tokens=12, seed=i)) for i in range(8)]
    eng.start()
    outcomes = []
    for q in qs:
        saw_error, toks, terminated = False, 0, False
        while True:
            item = q.get(timeout=60)  # a hang here IS the failure mode
            if item is None:
                terminated = True
                break
            if "error" in item:
                saw_error = True
            else:
                toks += len(item["tokens"])
            assert not (saw_error and "tokens" in item), \
                "tokens after error"
        outcomes.append((saw_error, toks, terminated))
    eng.stop()
    assert all(t for _, _, t in outcomes), outcomes
    # The injected error must have actually failed someone (not all
    # requests can have finished cleanly before call #3).
    assert any(e for e, _, _ in outcomes), outcomes


def test_engine_adaptive_chunk_policy():
    """Prefill-priority scheduling: chunk length scales with occupancy —
    empty slots -> min_chunk (frequent admission boundaries), full ->
    decode_chunk; adaptive_chunk=False pins the single configured size."""
    import jax

    from seldon_tpu.models import get_config, init_params
    from seldon_tpu.servers.engine import EngineConfig, InferenceEngine

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=8, max_seq_len=48, prompt_buckets=(8,),
        decode_chunk=32, min_chunk=4))
    assert eng._chunk_sizes == (4, 8, 32)
    assert eng._pick_chunk() == 4  # all free

    class _Stub:  # occupancy is counted from non-None slot entries
        finished = False

    eng._slots = [_Stub()] * 8
    assert eng._pick_chunk() == 32  # full -> saturated
    eng._slots = [_Stub()] * 4 + [None] * 4
    assert eng._pick_chunk() == 4  # real capacity -> fast admission
    # Bigger pool: free below max_admit -> saturated; free below a
    # quarter of the pool -> mid rung; plenty free -> min.
    big = InferenceEngine(params, cfg, EngineConfig(
        max_slots=64, max_seq_len=48, prompt_buckets=(8,),
        decode_chunk=32, min_chunk=4, max_admit=8))
    big._slots = [_Stub()] * 60 + [None] * 4
    assert big._pick_chunk() == 32
    big._slots = [_Stub()] * 52 + [None] * 12
    assert big._pick_chunk() == 8
    big._slots = [_Stub()] * 30 + [None] * 34
    assert big._pick_chunk() == 4

    fixed = InferenceEngine(params, cfg, EngineConfig(
        max_slots=8, max_seq_len=48, prompt_buckets=(8,),
        decode_chunk=32, adaptive_chunk=False))
    assert fixed._chunk_sizes == (32,)
    assert fixed._pick_chunk() == 32


def test_engine_ring_prefill_matches_xla():
    """Context-parallel (ring) prefill in the serving engine: greedy
    completions over an sp=4 mesh must match the plain XLA-attention
    engine bit-for-bit (ring attention is exact, not approximate) —
    SURVEY §5.7 long-context serving."""
    import dataclasses

    import jax

    from seldon_tpu.models import init_params
    from seldon_tpu.parallel import MeshPlan, make_mesh
    from seldon_tpu.parallel import sharding as shd

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompts = [[7, 8, 9, 10, 11], [3, 4, 5]]

    def complete(cfg_used, mesh):
        if mesh is not None:
            shardings = shd.named_shardings(mesh, shd.param_pspecs(cfg_used))
            p = jax.device_put(params, shardings)
        else:
            p = params
        eng = InferenceEngine(
            p, cfg_used,
            EngineConfig(max_slots=2, max_seq_len=48, prompt_buckets=(8,),
                         max_admit=2, decode_chunk=4),
            mesh=mesh,
        )
        eng.start()
        try:
            return [
                eng.generate_blocking(
                    pr, SamplingParams(temperature=0.0, max_new_tokens=6)
                )["token_ids"]
                for pr in prompts
            ]
        finally:
            eng.stop()

    base = complete(cfg, None)

    ring_cfg = dataclasses.replace(cfg, attn_impl="ring")
    mesh = make_mesh(MeshPlan(sp=4, tp=2))
    ring = complete(ring_cfg, mesh)
    assert ring == base, (ring, base)
