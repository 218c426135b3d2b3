"""JAXServer — the TPU-native prepackaged model server.

The reference's closest thing is the TensorRT proxy
(/root/reference/integrations/nvidia-inference-server/TRTProxy.py:31-81) plus
per-framework CPU servers (/root/reference/servers/*). JAXServer replaces
that whole route: it loads a transformer checkpoint (orbax dir via
`model_uri`, or a named preset with synthetic weights), shards it over the
local device mesh (auto TP×DP plan), and serves:

 * `generate` / `generate_stream` — continuous-batched text generation
   through the InferenceEngine (TTFT measured server-side),
 * `predict` — sequence scoring: token ids [B, S] -> per-row mean NLL
   (teacher-forced), the LM equivalent of a model server's score output,
 * custom metrics (engine stats) surfaced through the standard
   `Meta.metrics` channel the reference's engine aggregates.

Works as a `SeldonComponent`, so the microservice CLI, graph orchestrator,
and contract tester all drive it like any other unit.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from seldon_tpu.core import tracing
from seldon_tpu.models.config import ModelConfig, get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.runtime import REST_WORKERS
from seldon_tpu.runtime.user_model import SeldonComponent
from seldon_tpu.servers.engine import (
    KIND_HTTP_STATUS,
    KV_COUNTERS,
    MOE_COUNTERS,
    SHARE_COUNTERS,
    WINDOW_COUNTERS,
    DIFF_COUNTERS,
    EngineConfig,
    InferenceEngine,
    access_log,
)
from seldon_tpu.servers.tokenizer import ByteTokenizer, load_tokenizer

logger = logging.getLogger(__name__)


def _process_age_s() -> Optional[float]:
    """Seconds since this process started, from /proc (None where there
    is none): load() runs after the interpreter's start-up and every
    import, which the start-up line counts with the device's init."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class JAXServer(SeldonComponent):
    supports_batching = True

    def __init__(
        self,
        model_uri: Optional[str] = None,
        preset: str = "bench-1b",
        max_slots: int = 32,
        max_seq_len: int = 0,
        init_seed: int = 0,
        warmup: int = 0,
        weight_dtype: str = "",
        act_dtype: str = "",
        mesh_sp: int = 0,
        tp: int = 0,
        prefix_cache: int = -1,
        prefix_cache_mb: int = 0,
        chunked_prefill: int = -1,
        prefill_chunk: int = 0,
        dispatch_token_budget: int = 0,
        paged_kv: int = -1,
        kv_block: int = 0,
        kv_pool_mb: int = 0,
        spec: int = -1,
        spec_k: int = 0,
        spec_draft: str = "",
        max_queue: int = 0,
        default_deadline_ms: int = 0,
        platform: str = "",
    ):
        self.model_uri = model_uri
        self.preset = preset
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.init_seed = int(init_seed)
        self.warmup = int(warmup)
        # Context-parallel axis width for long-prompt serving: with
        # attn_impl=="ring", admissions prefill with the sequence
        # sharded over 'sp' (ring attention); 0 = no sp axis.
        self.mesh_sp = int(mesh_sp)
        # graftmesh exact tensor parallelism (servers/mesh_engine.py +
        # models/tp_sharding.py): unit parameter, or TP env. 0 (the
        # default) keeps the legacy auto mesh plan; 1 pins an explicit
        # single-chip mesh (the bit-exact reference leg mesh-audit
        # compares against); tp > 1 builds a dedicated 'tp' mesh over
        # the first tp devices (MESH_DEVICES env caps the claimable
        # count) and shards weights + KV under the exact-TP table —
        # greedy output stays bit-identical to tp=1. Mutually exclusive
        # with mesh_sp (ring attention is not tp-threaded; the engine
        # also rejects attn_impl=ring/flash).
        # Overrides the checkpoint config's weight_dtype: HF checkpoints
        # are always bf16 on disk, so serving them int8 (the llama3-8b-
        # on-one-16GB-chip config) is selected HERE (or via the
        # weight_dtype unit parameter / WEIGHT_DTYPE env).
        import os as _os

        self.tp = int(tp or _os.environ.get("TP", "0") or 0)
        if self.tp > 1 and self.mesh_sp > 1:
            raise ValueError(
                f"tp={self.tp} and mesh_sp={self.mesh_sp} are mutually "
                "exclusive (ring attention is not tp-threaded)")

        self.weight_dtype = (
            weight_dtype or _os.environ.get("WEIGHT_DTYPE", "")
        )
        # W8A8 matmuls (models/transformer._qdot); only meaningful when
        # the weights are int8 — selected like weight_dtype (unit
        # parameter / ACT_DTYPE env).
        self.act_dtype = act_dtype or _os.environ.get("ACT_DTYPE", "")
        # Prompt prefix KV reuse (servers/engine.py prefix cache): unit
        # parameter, or PREFIX_CACHE=1 / PREFIX_CACHE_MB env. -1 / 0 =
        # follow the env (default off).
        if int(prefix_cache) < 0:
            prefix_cache = int(_os.environ.get("PREFIX_CACHE", "0") or 0)
        self.prefix_cache = bool(int(prefix_cache))
        self.prefix_cache_mb = int(
            prefix_cache_mb or _os.environ.get("PREFIX_CACHE_MB", "0") or 0
        )
        # Stall-free chunked prefill (servers/engine.py): unit parameter,
        # or CHUNKED_PREFILL=1 / PREFILL_CHUNK / DISPATCH_TOKEN_BUDGET
        # env. -1 / 0 = follow the env (default off).
        if int(chunked_prefill) < 0:
            chunked_prefill = int(
                _os.environ.get("CHUNKED_PREFILL", "0") or 0
            )
        self.chunked_prefill = bool(int(chunked_prefill))
        self.prefill_chunk = int(
            prefill_chunk or _os.environ.get("PREFILL_CHUNK", "0") or 0
        )
        self.dispatch_token_budget = int(
            dispatch_token_budget
            or _os.environ.get("DISPATCH_TOKEN_BUDGET", "0") or 0
        )
        # Paged KV cache (servers/engine.py block pool): unit parameter,
        # or PAGED_KV=1 / KV_BLOCK / KV_POOL_MB env. KV_POOL_MB sizes the
        # pool in HBM megabytes (converted to blocks once the model
        # config is known in load()); 0 keeps the dense-equivalent
        # budget of max_slots * max_seq_len tokens.
        if int(paged_kv) < 0:
            paged_kv = int(_os.environ.get("PAGED_KV", "0") or 0)
        self.paged_kv = bool(int(paged_kv))
        self.kv_block = int(
            kv_block or _os.environ.get("KV_BLOCK", "0") or 0
        )
        self.kv_pool_mb = int(
            kv_pool_mb or _os.environ.get("KV_POOL_MB", "0") or 0
        )
        # graftspec speculative decoding (servers/engine.py
        # _dispatch_spec + models/spec_decode.py): unit parameter, or
        # SPEC=1 / SPEC_K / SPEC_DRAFT env. Implies paged_kv (rollback
        # after a rejected draft is a host-side block-table tail trim),
        # so SPEC=1 alone is a complete switch. SPEC_DRAFT names a
        # preset for the resident draft model (e.g. the 1B next to an
        # 8B target); empty uses the zero-dispatch n-gram drafter.
        # -1 / 0 = follow the env (default off).
        if int(spec) < 0:
            spec = int(_os.environ.get("SPEC", "0") or 0)
        self.spec = bool(int(spec))
        self.spec_k = int(
            spec_k or _os.environ.get("SPEC_K", "0") or 0
        )
        self.spec_draft = (
            spec_draft or _os.environ.get("SPEC_DRAFT", "")
        )
        if self.spec:
            self.paged_kv = True
        # Request-lifecycle hardening (servers/engine.py): bounded
        # admission queue (submit sheds with 429 EngineOverloaded past
        # this depth; 0 = unbounded) and a default per-request TTL in ms
        # (0 = none; per-request deadline_ms still applies). Chaos fault
        # injection is env-only (CHAOS=1 + CHAOS_* knobs, read by the
        # engine itself via ChaosConfig.from_env) — never a unit param,
        # so a deployment manifest can't enable it by accident. The
        # graftheal supervisor (servers/supervisor.py) follows the same
        # pattern: HEAL=1 + HEAL_MAX_RETRIES / HEAL_WATCHDOG_MS env,
        # read by the engine via supervisor.build.
        self.max_queue = int(
            max_queue or _os.environ.get("MAX_QUEUE", "0") or 0
        )
        self.default_deadline_ms = int(
            default_deadline_ms
            or _os.environ.get("DEFAULT_DEADLINE_MS", "0") or 0
        )
        # Deployment pin: the JAX platform this unit must find ("tpu").
        # load() raises on any other, before a weight is built — a pod
        # whose accelerator did not come up must crash, not serve an 8B
        # model from the host CPU. Empty = serve wherever JAX landed
        # (/metadata says where).
        self.platform = platform
        self._loaded = False
        self._load_lock = threading.Lock()
        self.engine: Optional[InferenceEngine] = None
        self.cfg: Optional[ModelConfig] = None
        self._tracer = tracing.get_tracer("jaxserver")
        self._slice_ready = None  # set by load() (SliceReadiness)

    # --- lifecycle ----------------------------------------------------------

    def load(self) -> None:
        with self._load_lock:
            if self._loaded:
                return
            t_load = time.perf_counter()
            age = _process_age_s()
            import jax

            from seldon_tpu import device
            from seldon_tpu.parallel import sharding as shd
            from seldon_tpu.parallel import distributed

            device.enable_compile_cache()
            # Multi-host slice: join via the StatefulSet env the operator
            # injects (no-op single-host). Must happen before any backend
            # query — jax.devices() is global after initialize.
            distributed.ensure_initialized()
            self._slice_ready = distributed.SliceReadiness()
            found = jax.devices()[0].platform
            if self.platform and found != self.platform:
                raise RuntimeError(
                    f"JAXServer requires platform {self.platform!r} but "
                    f"JAX found {found!r}"
                )
            t_device = time.perf_counter()

            if self.model_uri:
                import os as _os

                from seldon_tpu.servers import checkpoint as ckpt
                from seldon_tpu.servers.storage import download

                local = download(self.model_uri)
                self.tokenizer = load_tokenizer(local)
                if _os.path.exists(_os.path.join(local, "config.json")) and any(
                    f.endswith(".safetensors") for f in _os.listdir(local)
                ):
                    # HF Llama-family checkpoint (config.json +
                    # safetensors): each stacked tensor is placed SHARDED
                    # on the serving mesh as it streams in — a model
                    # bigger than one chip's HBM never sits whole anywhere.
                    from seldon_tpu.servers.hf_loader import load_hf_checkpoint

                    mesh_holder = {}

                    def _shardings(loaded_cfg):
                        mesh_holder["mesh"] = self._serving_mesh(loaded_cfg)
                        return shd.named_shardings(
                            mesh_holder["mesh"],
                            shd.param_pspecs(loaded_cfg),
                        )

                    params, cfg = load_hf_checkpoint(
                        local, make_shardings=_shardings
                    )
                    mesh = mesh_holder["mesh"]
                else:
                    mesh = self._serving_mesh(ckpt.load_config(local))
                    params, cfg = ckpt.load_checkpoint(local, mesh)
                # Checkpoints are bf16 on disk: int8 is selected here
                # and applied by quantize_params below.
                cfg = self._dtype_overrides(cfg)
            else:
                cfg = get_config(self.preset)
                self.tokenizer = ByteTokenizer()
                if cfg.vocab_size >= ByteTokenizer.vocab_size:
                    cfg = get_config(
                        cfg,
                        eos_token_id=self.tokenizer.eos_token_id,
                        pad_token_id=self.tokenizer.pad_token_id,
                    )
                cfg = self._dtype_overrides(cfg)
                mesh = self._serving_mesh(cfg)
                params = self._synthetic_params(cfg, mesh, self.init_seed)
            if cfg.act_dtype == "int8" and self.model_uri:
                # Real (trained) checkpoints carry activation outliers in
                # the down-projection inputs that per-token int8 clips —
                # random-init presets don't show this, so a bench pass
                # proves nothing about quality. W8A8 a trained model only
                # with an accuracy eval in hand.
                logger.warning(
                    "act_dtype=int8 (W8A8) enabled for loaded checkpoint "
                    "%s: down-proj activation outliers can degrade output "
                    "quality — validate accuracy before serving traffic "
                    "(weights-only int8 is the safe default)",
                    self.model_uri,
                )
            if cfg.weight_dtype == "int8":
                from seldon_tpu.models.quantize import quantize_params

                params = quantize_params(params)
            # The tree is built by asynchronous dispatches: wait, so that
            # "weights on the device" in the start-up line is that.
            jax.block_until_ready(params)  # graftlint: allow(hot-sync) load time, before the engine exists; the sync IS the stamp
            t_weights = time.perf_counter()
            self.cfg = cfg
            self.mesh = mesh
            seq = self.max_seq_len or cfg.max_seq_len
            buckets = tuple(
                b for b in (32, 128, 512, 1024, 2048, 4096) if b <= seq
            ) or (seq,)
            ekw: Dict[str, Any] = {}
            if self.prefix_cache:
                ekw["prefix_cache"] = True
                if self.prefix_cache_mb:
                    ekw["prefix_cache_bytes"] = self.prefix_cache_mb << 20
            if self.chunked_prefill:
                ekw["chunked_prefill"] = True
                if self.prefill_chunk:
                    ekw["prefill_chunk"] = self.prefill_chunk
                if self.dispatch_token_budget:
                    ekw["dispatch_token_budget"] = self.dispatch_token_budget
            if self.paged_kv:
                ekw["paged_kv"] = True
                kb = self.kv_block or EngineConfig.kv_block
                ekw["kv_block"] = kb
                # Warm prefix widths are bucketed and must cover whole
                # pool blocks (EngineConfig validation).
                buckets = tuple(b for b in buckets if b % kb == 0) \
                    or (seq,)
                if self.kv_pool_mb:
                    # blocks = pool_bytes /
                    #   (2 * layers * kv_heads * head_dim * kv_block * B)
                    # where B is the KV dtype width; int8 adds one bf16
                    # scale per (head, token) on top of the 1-byte values.
                    per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * (
                        cfg.head_dim * (1 if cfg.kv_cache_dtype == "int8"
                                        else 2)
                        + (2 if cfg.kv_cache_dtype == "int8" else 0)
                    )
                    blocks = (self.kv_pool_mb << 20) // (per_tok * kb)
                    ekw["kv_pool_blocks"] = max(2, int(blocks))
            draft = None
            if self.spec:
                ekw["spec_decode"] = True
                if self.spec_k:
                    ekw["spec_k"] = self.spec_k
                if self.spec_draft:
                    # Resident draft model: preset-only (the draft rides
                    # the target's mesh and tokenizer — its proposals
                    # must be valid target token ids, so eos/pad are
                    # aligned to the target config here).
                    ekw["spec_draft"] = self.spec_draft
                    dcfg = get_config(
                        self.spec_draft,
                        eos_token_id=cfg.eos_token_id,
                        pad_token_id=cfg.pad_token_id,
                    )
                    draft = (
                        self._synthetic_params(dcfg, mesh,
                                               self.init_seed + 1),
                        dcfg,
                    )
            if self.max_queue:
                ekw["max_queue"] = self.max_queue
            if self.default_deadline_ms:
                ekw["default_deadline_ms"] = self.default_deadline_ms
            if self.tp > 1:
                # The engine re-commits the params under the exact-TP
                # table (models/tp_sharding) on the mesh
                # _serving_mesh built — init/load placement above is
                # just a staging layout.
                ekw["tp"] = self.tp
            self.engine = InferenceEngine(
                params,
                cfg,
                EngineConfig(
                    max_slots=self.max_slots,
                    max_seq_len=seq,
                    prompt_buckets=buckets,
                    **ekw,
                ),
                mesh=mesh,
                draft=draft,
            )
            t_engine = time.perf_counter()
            if self.warmup:
                self.engine.warmup()
            t_warm = time.perf_counter()
            self.engine.start()
            # The engine's tree, not the loader's: under tp > 1 the
            # engine re-committed the weights across the group, and a
            # second reference would pin the whole staging copy on the
            # first device.
            self.params = self.engine.params

            # One compiled scorer for predict() (cfg baked in statically).
            import functools

            import jax as _jax
            import jax.numpy as _jnp

            from seldon_tpu.models import transformer as _tf

            # Long-context scoring rides ring attention when the config
            # asks for it and the serving mesh has a real 'sp' axis.
            ring = (
                mesh if (cfg.attn_impl == "ring"
                         and dict(mesh.shape).get("sp", 1) > 1)
                else None
            )

            def _score(params, toks, *, _cfg):
                logits = _tf.forward(params, toks, _cfg, ring_mesh=ring)
                lp = _jax.nn.log_softmax(
                    logits[:, :-1].astype(_jnp.float32), -1
                )
                nll = -_jnp.take_along_axis(
                    lp, toks[:, 1:, None], axis=-1
                )[..., 0]
                return nll.mean(axis=-1)

            self._score_fn = _jax.jit(functools.partial(_score, _cfg=cfg))
            self._loaded = True
            # Start-up phases on the access log, beside the request
            # lines (docs/distributed-tracing.md): seconds from the
            # process's start (from load()'s, where /proc has no answer)
            # through imports and device init, then weights, engine
            # construction and the engine's own warm-up.
            before = age if age is not None else 0.0
            access_log.info("startup %s", json.dumps({
                "since_process_start": age is not None,
                "imports_device_s": round(before + t_device - t_load, 3),
                "weights_s": round(t_weights - t_device, 3),
                "weights_ready_s": round(before + t_weights - t_load, 3),
                "engine_s": round(t_engine - t_weights, 3),
                "warmup_s": round(t_warm - t_engine, 3),
                "warmup_variants": (
                    len(self.engine.static_lattice()) if self.warmup else 0
                ),
            }))
            logger.info(
                "JAXServer loaded: cfg=%s mesh=%s slots=%d seq=%d",
                self.preset if not self.model_uri else self.model_uri,
                mesh.shape if mesh else None,
                self.max_slots,
                seq,
            )

    def _dtype_overrides(self, cfg):
        """cfg with the unit's weight_dtype / act_dtype applied (W8A8
        only rides int8 weights)."""
        import dataclasses

        if self.weight_dtype:
            cfg = dataclasses.replace(cfg, weight_dtype=self.weight_dtype)
        if self.act_dtype and cfg.weight_dtype == "int8":
            cfg = dataclasses.replace(cfg, act_dtype=self.act_dtype)
        return cfg

    @staticmethod
    def _synthetic_params(cfg, mesh, seed: int):
        """Seeded random weights for a preset, committed on `mesh`
        under the GSPMD specs. int8 configs are BORN int8
        (quantize.init_params_int8, layer slice by layer slice): a bf16
        llama3-8b tree is 16 GB and quantize_params' f32 copy of one
        stacked leaf another 7.5 GB — neither fits the 16 GB chip the
        8 GB int8 tree serves from."""
        import jax

        from seldon_tpu.models import transformer
        from seldon_tpu.parallel import sharding as shd

        if cfg.patterned:
            # Leaf by leaf (each leaf its own small program: a whole-tree
            # jit may hold several stacked expert matrices in float32 at
            # once), replicated: a patterned stack is served on one chip.
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                transformer.init_params(cfg, jax.random.key(seed)),
                NamedSharding(mesh, PartitionSpec()),
            )
        int8 = cfg.weight_dtype == "int8"
        shardings = shd.named_shardings(
            mesh, shd.param_pspecs(cfg, quantized=int8)
        )
        key = jax.random.key(seed)
        if int8:
            from seldon_tpu.models.quantize import init_params_int8

            return jax.device_put(init_params_int8(cfg, key), shardings)
        with mesh:
            return jax.jit(
                lambda k: transformer.init_params(cfg, k),
                out_shardings=shardings,
            )(key)

    def _serving_mesh(self, cfg):
        """The mesh load() commits onto: a dedicated tp-wide 'tp' mesh
        when the graftmesh knob is set (first tp devices, MESH_DEVICES-
        capped), the auto TPxDP plan otherwise. tp=1 is meaningful —
        an explicit single-chip mesh, the bit-exact reference leg the
        mesh-audit parity gate compares a TP group against — while
        tp=0 (the default) keeps the legacy auto plan."""
        if self.tp >= 1:
            from seldon_tpu.servers import mesh_engine

            return mesh_engine.build_tp_mesh(self.tp)
        return self._mesh_for(cfg)

    def _mesh_for(self, cfg):
        import math

        import jax

        from seldon_tpu.parallel import MeshPlan, make_mesh

        n = len(jax.devices())
        if self.mesh_sp > 1 and cfg.attn_impl == "ring" and n % self.mesh_sp == 0:
            rem = n // self.mesh_sp
            tp = math.gcd(rem, cfg.n_kv_heads)
            return make_mesh(MeshPlan(
                sp=self.mesh_sp, tp=tp, dp=rem // tp
            ))
        return make_mesh(MeshPlan.auto(n, cfg))

    def _ensure_loaded(self):
        if not self._loaded:
            self.load()

    def health_status(self):
        # Probes must NEVER block on (or trigger) load: during multi-host
        # slice formation, load() sits inside jax.distributed.initialize
        # holding the load lock — a probe that joined it would hang until
        # kubelet's timeout instead of returning a crisp 503. Not loaded
        # (including "waiting for slice peers") IS not-ready.
        if not self._loaded:
            raise RuntimeError("model loading (or slice forming)")
        if self.engine is not None and self.engine.draining:
            # Readiness flips off the moment drain starts so the load
            # balancer stops routing here while in-flight work finishes.
            raise RuntimeError("engine draining")
        if self._slice_ready is not None:
            self._slice_ready.check()  # local accelerator sanity
        out = {"engine": self.engine.stats.snapshot()}
        heal = self.engine.debug_health()
        if heal is not None:
            # Recovering/degraded is still READY — the engine is serving
            # (that is the point of graftheal); operators read the state
            # here and at /debug/health rather than losing the replica.
            out["heal"] = {
                "state": heal["state"],
                "pressure": heal["pressure"],
            }
        return out

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting, shed the queue (retriable errors), wait for
        in-flight requests; readiness goes 503 immediately. Returns True
        once the engine is quiescent."""
        if not self._loaded or self.engine is None:
            return True
        return self.engine.drain(timeout=timeout)

    def init_metadata(self) -> Dict:
        self._ensure_loaded()
        import dataclasses

        from seldon_tpu import device

        # `device` is where this unit ran, as JAX reports it — a client
        # tells a TPU from a CPU here without importing JAX. `count` is
        # what JAX sees; `mesh_devices` is what this unit serves from.
        return {
            "name": "jaxserver",
            "config": dataclasses.asdict(self.cfg),
            "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
            "mesh_devices": [int(d.id) for d in self.mesh.devices.flat],
            "device": device.describe(),
            # Bytes of the per-slot cache by kind (transformer.cache_spec:
            # "kv" over the layers that hold KV, "kv_window" the rings of
            # the layers that attend inside a window (as long as the
            # window, not as max_seq_len), "conv" the fixed-size
            # state of a patterned stack's conv layers, "ssm" and
            # "ssm_conv" that of its Mamba-2 layers).
            "cache_bytes": self.engine.cache_bytes(),
            # What a client's warm-up otherwise has to discover: the
            # largest admission group, the decode-chunk rungs and how
            # many requests the REST transport runs at once.
            "engine": {
                "max_slots": self.engine.ecfg.max_slots,
                "max_seq_len": self.engine.ecfg.max_seq_len,
                "prompt_buckets": list(self.engine.ecfg.prompt_buckets),
                "max_admit": self.engine.max_admit,
                "decode_chunk": list(self.engine.chunk_sizes),
                "rest_workers": REST_WORKERS,
            },
        }

    # --- text generation ----------------------------------------------------

    def _to_sampling(self, request: Dict) -> SamplingParams:
        # Explicit falsy values are honored (temperature 0.0 = greedy);
        # only absent/None keys fall back to defaults.
        def get(key, default):
            v = request.get(key)
            return default if v is None else v

        # Trace context: an explicit traceparent (stamped into the
        # request dict by the transport edge from the HTTP header / gRPC
        # metadata) wins; otherwise adopt whatever span is open on this
        # thread of control (e.g. jaxserver.generate below, or the
        # orchestrator's unit span for in-process graphs) so the
        # engine's lifecycle spans join the same trace.
        tp = str(get("traceparent", "") or "")
        if not tp:
            cur = tracing.current_span()
            if cur is not None:
                tp = cur.context.to_traceparent()
        return SamplingParams(
            temperature=float(get("temperature", 0.7)),
            top_k=int(get("top_k", 0)),
            top_p=float(get("top_p", 1.0)),
            max_new_tokens=int(get("max_new_tokens", 16) or 16),
            seed=int(get("seed", 0)),
            deadline_ms=int(get("deadline_ms", 0) or 0),
            traceparent=tp,
            received_at=request.get("received_at"),
        )

    def _prompt_ids(self, request: Dict) -> List[int]:
        ids = list(request.get("prompt_token_ids") or [])
        if not ids and request.get("prompt"):
            ids = self.tokenizer.encode(request["prompt"])
        if not ids:
            raise ValueError("generate request has no prompt")
        return ids

    def generate(self, request: Dict) -> Dict:
        self._ensure_loaded()
        t0 = time.perf_counter()
        ids = self._prompt_ids(request)
        with self._tracer.span(
            "jaxserver.generate", attributes={"prompt_tokens": len(ids)}
        ) as span:
            result = self.engine.generate_blocking(
                ids, self._to_sampling(request)
            )
            toks = result["token_ids"]
            if toks and toks[-1] == self.cfg.eos_token_id:
                toks = toks[:-1]
            # ttft splits the span into its prefill/decode phases.
            span.set_attribute("prefill_ms", result["ttft_ms"] or 0.0)
            span.set_attribute("completion_tokens", len(toks))
        return {
            "text": self.tokenizer.decode(toks),
            "token_ids": toks,
            "ttft_ms": result["ttft_ms"] or 0.0,
            "total_ms": 1000.0 * (time.perf_counter() - t0),
            "prompt_tokens": len(ids),
            "completion_tokens": len(toks),
            "timings": result["timings"] or {},
        }

    def generate_stream(self, request: Dict):
        self._ensure_loaded()
        t0 = time.perf_counter()
        ids = self._prompt_ids(request)
        # Submission span: short-lived (covers the enqueue only — tokens
        # stream for seconds after it closes), but it puts a jaxserver
        # span in the trace and the engine's lifecycle spans parent
        # under the same trace id via _to_sampling's adoption.
        with self._tracer.span(
            "jaxserver.generate_stream",
            attributes={"prompt_tokens": len(ids)},
        ):
            out_q = self.engine.submit(ids, self._to_sampling(request))
        n = 0
        done = False
        try:
            while True:
                try:
                    item = out_q.get(timeout=0.1)
                except queue.Empty:
                    # Heartbeat: gives the transport a poll point so a
                    # vanished client is noticed (and this generator
                    # closed -> finally -> cancel) even while the engine
                    # is between token bursts. Transports drop Nones.
                    yield None
                    continue
                if item is None:
                    done = True
                    break
                if "error" in item:
                    done = True
                    err = RuntimeError(
                        f"generation failed: {item['error']}"
                    )
                    err.kind = item.get("kind", "internal")
                    err.retriable = bool(item.get("retriable", False))
                    err.http_status = KIND_HTTP_STATUS.get(err.kind, 500)
                    raise err
                # Tokens arrive in decode-chunk bursts; emit one stream
                # chunk per burst (EOS stripped).
                toks = [
                    t for t in item["tokens"] if t != self.cfg.eos_token_id
                ]
                if not toks:
                    continue
                n += len(toks)
                yield {
                    "text": self.tokenizer.decode(toks),
                    "token_ids": toks,
                    "ttft_ms": item.get("ttft_ms", 0.0),
                    "total_ms": 1000.0 * (time.perf_counter() - t0),
                    "prompt_tokens": len(ids),
                    "completion_tokens": n,
                    "timings": item.get("timings") or {},
                }
        finally:
            if not done:
                # Closed mid-stream (client disconnect / GeneratorExit):
                # stop decoding for a reader that's gone.
                self.engine.cancel(getattr(out_q, "rid", -1))

    # --- scoring (MODEL predict parity) -------------------------------------

    def predict(
        self, X: np.ndarray, names: Iterable[str], meta: Optional[Dict] = None
    ) -> np.ndarray:
        """Token ids [B, S] -> per-row mean next-token NLL [B] (lower =
        model finds the sequence more likely)."""
        self._ensure_loaded()
        import jax.numpy as jnp

        toks = jnp.asarray(np.asarray(X, dtype=np.int32))
        if toks.ndim == 1:
            toks = toks[None]
        return np.asarray(self._score_fn(self.params, toks))

    # --- observability ------------------------------------------------------

    def debug_timeline(self) -> Optional[Dict]:
        """Engine flight-recorder snapshot for the /debug/timeline
        endpoint (None when FLIGHT_RECORDER is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_timeline()

    def debug_compile(self) -> Optional[Dict]:
        """Engine compile-ledger snapshot for the /debug/compile
        endpoint (None when COMPILE_LEDGER is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_compile()

    def debug_hbm(self) -> Optional[Dict]:
        """Engine HBM-ledger snapshot for the /debug/hbm endpoint
        (None when HBM_LEDGER is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_hbm()

    def debug_sched(self) -> Optional[Dict]:
        """Engine sched-ledger snapshot for the /debug/sched endpoint
        (None when SCHED_LEDGER is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_sched()

    def debug_pilot(self) -> Optional[Dict]:
        """Engine pilot-controller snapshot for the /debug/pilot
        endpoint (None when PILOT is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_pilot()

    def debug_roof(self) -> Optional[Dict]:
        """Engine roofline snapshot for the /debug/roof endpoint
        (None when ROOF_LEDGER is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_roof()

    def debug_health(self) -> Optional[Dict]:
        """Heal-supervisor snapshot for the /debug/health endpoint
        (None when HEAL is off or nothing loaded)."""
        if not self._loaded or self.engine is None:
            return None
        return self.engine.debug_health()

    def _observatory_metrics(self, s: Dict) -> List[Dict]:
        """Compile/HBM/sched-ledger and per-variant dispatch gauges.
        Empty when the observatory is off — the Prometheus surface only
        grows for operators who turned the knobs on."""
        out: List[Dict] = []
        comp = self.engine.debug_compile()
        if comp is not None:
            out.extend([
                {"type": "GAUGE", "key": "jaxserver_compile_variants",
                 "value": float(comp["dispatched_variants"])},
                {"type": "GAUGE", "key": "jaxserver_live_retraces",
                 "value": float(comp["live_retrace_count"])},
                {"type": "GAUGE", "key": "jaxserver_compile_seconds_total",
                 "value": float(comp["compile_s_total"])},
            ])
        for key, h in sorted(s.get("variant_timing", {}).items()):
            out.extend([
                {"type": "GAUGE",
                 "key": "jaxserver_dispatch_ms_count",
                 "value": float(h["count"]),
                 "tags": {"variant": key}},
                {"type": "GAUGE",
                 "key": "jaxserver_dispatch_ms_sum",
                 "value": float(h["sum_ms"]),
                 "tags": {"variant": key}},
            ])
        hbm = self.engine.debug_hbm()
        if hbm is not None:
            for name, cat in sorted(hbm["categories"].items()):
                out.append({
                    "type": "GAUGE", "key": "jaxserver_hbm_bytes",
                    "value": float(cat["bytes"]),
                    "tags": {"category": name},
                })
        sched = self.engine.debug_sched()
        if sched is not None:
            out.extend([
                {"type": "GAUGE", "key": "jaxserver_padding_waste_frac",
                 "value": float(sched["padding_waste_frac"])},
                {"type": "GAUGE",
                 "key": "jaxserver_sched_budget_utilization",
                 "value": float(sched["budget_utilization"])},
                {"type": "GAUGE", "key": "jaxserver_sched_idle_boundaries",
                 "value": float(sched["idle_boundaries"])},
                {"type": "GAUGE", "key": "jaxserver_preempted_tokens",
                 "value": float(sched["preempted_tokens"])},
                {"type": "GAUGE",
                 "key": "jaxserver_sched_conservation_breaches",
                 "value": float(sched["conservation"]["breaches"])},
            ])
            for cause, frac in sorted(sched["goodput_gap"].items()):
                out.append({
                    "type": "GAUGE", "key": "jaxserver_goodput_gap",
                    "value": float(frac),
                    "tags": {"cause": cause},
                })
            for comp in ("pool_ms", "bucket_ms", "budget_ms", "sched_ms"):
                out.append({
                    "type": "GAUGE", "key": "jaxserver_queue_wait_ms_total",
                    "value": float(sched["wait"][comp]),
                    "tags": {"component": comp},
                })
            if self.spec:
                spec = sched["spec"]
                out.extend([
                    {"type": "GAUGE",
                     "key": "jaxserver_spec_acceptance_rate",
                     "value": float(spec["acceptance_rate"])},
                    {"type": "GAUGE",
                     "key": "jaxserver_spec_drafted_tokens",
                     "value": float(spec["drafted_tokens"])},
                    {"type": "GAUGE",
                     "key": "jaxserver_spec_accepted_tokens",
                     "value": float(spec["accepted_tokens"])},
                    {"type": "GAUGE",
                     "key": "jaxserver_spec_rejected_tokens",
                     "value": float(spec["rejected_tokens"])},
                    {"type": "GAUGE",
                     "key": "jaxserver_spec_verify_waves",
                     "value": float(spec["verify_waves"])},
                ])
        pilot = self.engine.debug_pilot()
        if pilot is not None:
            for knob, n in sorted(pilot["decisions_by_knob"].items()):
                out.append({
                    "type": "GAUGE",
                    "key": "jaxserver_pilot_decisions_total",
                    "value": float(n),
                    "tags": {"knob": knob},
                })
            out.extend([
                {"type": "GAUGE", "key": "jaxserver_pilot_budget_current",
                 "value": float(pilot["knobs"]["dispatch_token_budget"])},
                {"type": "GAUGE", "key": "jaxserver_pilot_admit_current",
                 "value": float(pilot["knobs"]["max_admit"])},
                {"type": "GAUGE", "key": "jaxserver_pilot_spec_k_current",
                 "value": float(pilot["knobs"]["spec_k"])},
                {"type": "GAUGE", "key": "jaxserver_pilot_edf_inversions",
                 "value": float(pilot["edf"]["inversions"])},
                {"type": "GAUGE", "key": "jaxserver_pilot_goodput_delta",
                 "value": float(
                     pilot["counterfactual"]["goodput_delta"])},
            ])
        roof = self.engine.debug_roof()
        if roof is not None:
            for v in roof["variants"]:
                out.extend([
                    {"type": "GAUGE", "key": "jaxserver_mfu",
                     "value": float(v["mfu"]),
                     "tags": {"variant": v["key"]}},
                    {"type": "GAUGE", "key": "jaxserver_mbu",
                     "value": float(v["mbu"]),
                     "tags": {"variant": v["key"]}},
                ])
            out.extend([
                {"type": "GAUGE", "key": "jaxserver_host_frac",
                 "value": float(roof["host_frac"])},
                {"type": "GAUGE",
                 "key": "jaxserver_roof_conservation_breaches",
                 "value": float(roof["conservation"]["breaches"])},
            ])
        heal = self.engine.debug_health()
        if heal is not None:
            out.extend([
                {"type": "GAUGE", "key": "jaxserver_heal_resurrected",
                 "value": float(heal["resurrected"])},
                {"type": "GAUGE", "key": "jaxserver_heal_quarantined",
                 "value": float(heal["quarantined"])},
                {"type": "GAUGE", "key": "jaxserver_heal_watchdog_trips",
                 "value": float(heal["watchdog_trips"])},
                {"type": "GAUGE", "key": "jaxserver_heal_retry_exhausted",
                 "value": float(heal["retry_exhausted"])},
                {"type": "GAUGE", "key": "jaxserver_heal_pressure",
                 "value": float(heal["pressure"])},
            ])
        return out

    def _slo_metrics(self, s: Dict) -> List[Dict]:
        """SLO attainment as a real Prometheus histogram: cumulative
        `_bucket{le=...}` series (+Inf included) plus `_count`/`_sum`,
        and the goodput counters, all from the stats snapshot."""
        out: List[Dict] = []
        cum = 0
        edges = s["deadline_margin_edges_ms"]
        counts = s["deadline_margin_counts"]
        for edge, c in zip(list(edges) + ["+Inf"], counts):
            cum += c
            out.append({
                "type": "GAUGE",
                "key": "jaxserver_deadline_margin_ms_bucket",
                "value": float(cum),
                "tags": {"le": str(edge)},
            })
        out.extend([
            {"type": "GAUGE", "key": "jaxserver_deadline_margin_ms_count",
             "value": float(cum)},
            {"type": "GAUGE", "key": "jaxserver_deadline_margin_ms_sum",
             "value": float(s["deadline_margin_sum_ms"])},
            {"type": "GAUGE", "key": "jaxserver_deadline_met_total",
             "value": float(s["deadline_met_total"])},
            {"type": "GAUGE", "key": "jaxserver_deadline_missed_total",
             "value": float(s["deadline_missed_total"])},
            {"type": "GAUGE", "key": "jaxserver_completed_no_deadline_total",
             "value": float(s["completed_no_deadline_total"])},
            {"type": "GAUGE", "key": "jaxserver_goodput",
             "value": float(s["goodput"])},
        ])
        return out

    def metrics(self) -> List[Dict]:
        if not self._loaded:
            return []
        s = self.engine.stats.snapshot()
        # A first token's phases (engine._Request's five instants) as
        # rate-able sums and counts; ms, but waves_ahead in waves.
        phases: List[Dict] = []
        for phase, (total, count) in s["ttft_phases"].items():
            phases.extend([
                {"type": "GAUGE", "key": f"jaxserver_ttft_{phase}_sum",
                 "value": float(total)},
                {"type": "GAUGE", "key": f"jaxserver_ttft_{phase}_count",
                 "value": float(count)},
            ])
        # What bounds device_wait: the scheduler's depth (waves_ahead is
        # at most one less) and the wave period / host turn it is
        # derived from (engine._DepthEstimator).
        pipeline = [
            {"type": "GAUGE", "key": f"jaxserver_sched_{name}",
             "value": float(value)}
            for name, value in self.engine.pipeline_gauges().items()
        ]
        return self._slo_metrics(s) + self._observatory_metrics(s) \
            + phases + pipeline + [
            {"type": "GAUGE", "key": "jaxserver_mean_ttft_ms",
             "value": s["mean_ttft_ms"]},
            {"type": "GAUGE", "key": "jaxserver_tokens_out",
             "value": float(s["tokens_out"])},
            {"type": "GAUGE", "key": "jaxserver_completed",
             "value": float(s["completed"])},
            {"type": "GAUGE", "key": "jaxserver_failed_total",
             "value": float(s["failed_total"])},
            {"type": "GAUGE", "key": "jaxserver_slots_busy",
             "value": float(self.engine.slots_busy())},
            {"type": "GAUGE", "key": "jaxserver_decode_dispatches",
             "value": float(s["decode_dispatches"])},
            {"type": "GAUGE", "key": "jaxserver_decode_steps",
             "value": float(s["decode_steps"])},
            # What decode attention read of the dense slab (read / held =
            # the share of it a step touches) and what routing did in
            # decode (0 unless the model dispatches tokens to experts):
            # touched / sparse_layer_steps = experts a sparse layer
            # reads per decode step.
            # Decode steps by the tier the sampler took (exclusive:
            # they add up to the steps whose tier a chunk reported).
            *({"type": "GAUGE", "key": "jaxserver_sampler_steps_total",
               "value": float(v), "tags": {"tier": tier}}
              for tier, v in (
                  ("greedy", s["sampler_steps"] - s["sampler_drawn_steps"]),
                  ("drawn", s["sampler_drawn_steps"]
                   - s["sampler_masked_steps"]),
                  ("masked", s["sampler_masked_steps"]))),
            *({"type": "GAUGE", "key": "jaxserver_" + name,
               "value": float(s[name])}
              for name in KV_COUNTERS + WINDOW_COUNTERS + MOE_COUNTERS
              + SHARE_COUNTERS + DIFF_COUNTERS),
            # Prompt tokens admitted, by the bucket the group was padded to.
            *({"type": "GAUGE", "key": "jaxserver_attn_prefill_tokens",
               "value": float(n), "tags": {"bucket": str(b)}}
              for b, n in sorted(s["attn_prefill_tokens"].items())),
            {"type": "GAUGE", "key": "jaxserver_prefix_hits",
             "value": float(s["prefix_hits"])},
            {"type": "GAUGE", "key": "jaxserver_prefix_tokens_saved",
             "value": float(s["prefix_tokens_saved"])},
            {"type": "GAUGE", "key": "jaxserver_prefix_evictions",
             "value": float(s["prefix_evictions"])},
            {"type": "GAUGE", "key": "jaxserver_queue_depth",
             "value": float(s["queue_depth"])},
            {"type": "GAUGE", "key": "jaxserver_mean_queue_wait_ms",
             "value": s["mean_queue_wait_ms"]},
            {"type": "GAUGE", "key": "jaxserver_itl_p50_ms",
             "value": s["itl_p50_ms"]},
            {"type": "GAUGE", "key": "jaxserver_itl_p95_ms",
             "value": s["itl_p95_ms"]},
            {"type": "GAUGE", "key": "jaxserver_itl_p99_ms",
             "value": s["itl_p99_ms"]},
            {"type": "GAUGE", "key": "jaxserver_prefill_chunks",
             "value": float(s["prefill_chunks"])},
            {"type": "GAUGE", "key": "jaxserver_prefill_chunk_tokens",
             "value": float(s["prefill_chunk_tokens"])},
            {"type": "GAUGE", "key": "jaxserver_budget_utilization",
             "value": s["budget_utilization"]},
            {"type": "GAUGE", "key": "jaxserver_pool_blocks_used",
             "value": float(s["pool_blocks_used"])},
            {"type": "GAUGE", "key": "jaxserver_pool_blocks_free",
             "value": float(s["pool_blocks_free"])},
            {"type": "GAUGE", "key": "jaxserver_pool_blocks_shared",
             "value": float(s["pool_blocks_shared"])},
            {"type": "GAUGE", "key": "jaxserver_zero_copy_admissions",
             "value": float(s["zero_copy_admissions"])},
            {"type": "GAUGE", "key": "jaxserver_cow_copies",
             "value": float(s["cow_copies"])},
            {"type": "GAUGE", "key": "jaxserver_pool_stalls",
             "value": float(s["pool_stalls"])},
            {"type": "GAUGE", "key": "jaxserver_preemptions",
             "value": float(s["preemptions"])},
            {"type": "GAUGE", "key": "jaxserver_shed_total",
             "value": float(s["shed_total"])},
            {"type": "GAUGE", "key": "jaxserver_cancelled_total",
             "value": float(s["cancelled_total"])},
            {"type": "GAUGE", "key": "jaxserver_deadline_expired_total",
             "value": float(s["deadline_expired_total"])},
            {"type": "GAUGE", "key": "jaxserver_queue_rejects",
             "value": float(s["queue_rejects"])},
        ]

    def tags(self) -> Dict:
        return {"server": "jaxserver", "preset": self.preset}
