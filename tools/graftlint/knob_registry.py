"""The env-knob registry: every environment variable the tree may read.

``docs/knobs.md`` is generated from this table plus the read sites the
env-knob pass discovers (``python -m tools.graftlint --gen-knobs``).
Adding an ``os.environ`` read without registering it here fails
``make lint``.

Groups in ``EXTERNAL_GROUPS`` are exempt from the stale-entry check: the
value is owned by the platform (JAX, the kubelet, cloud SDKs) so a knob
may stay registered even when no scanned file currently reads it.

``bench.py`` / ``bench_orchestrator.py`` are part of the linted tree, so
the bench-harness phase knobs (``BENCH_*`` / ``BENCH_ORCH_*``) are
registered here like everything else; the measurement methodology behind
them stays in ``docs/benchmarking.md``.
"""

EXTERNAL_GROUPS = {"platform"}


def _k(group, default, desc):
    return {"group": group, "default": default, "desc": desc}


KNOBS = {
    # --- engine serving (servers/jaxserver.py unit-param fallbacks) -------
    "WEIGHT_DTYPE": _k("engine-serving", "(checkpoint dtype)",
                       "Override weight dtype at load, e.g. `int8` to serve a "
                       "bf16 HF checkpoint quantized."),
    "ACT_DTYPE": _k("engine-serving", "(follows weights)",
                    "W8A8 activation dtype for int8 weights (`int8`/`bf16`)."),
    "PREFIX_CACHE": _k("engine-serving", "0",
                       "Enable prompt-prefix KV reuse (radix trie over "
                       "block-aligned prefixes)."),
    "PREFIX_CACHE_MB": _k("engine-serving", "0 (auto)",
                          "HBM budget for retained prefix KV, in MiB."),
    "CHUNKED_PREFILL": _k("engine-serving", "0",
                          "Interleave prefill chunks with decode steps "
                          "(stall-free scheduling)."),
    "PREFILL_CHUNK": _k("engine-serving", "0 (model block)",
                        "Prefill chunk length in tokens."),
    "DISPATCH_TOKEN_BUDGET": _k("engine-serving", "0 (auto)",
                                "Per-dispatch token budget shared by decode "
                                "and prefill chunks."),
    "PAGED_KV": _k("engine-serving", "0",
                   "Paged KV cache: global block pool + per-slot block "
                   "tables instead of dense per-slot slabs."),
    "KV_BLOCK": _k("engine-serving", "0 (model default)",
                   "KV block size in tokens (paged mode)."),
    "KV_POOL_MB": _k("engine-serving", "0 (dense-equivalent)",
                     "KV pool size in HBM MiB (paged mode)."),
    "SPEC": _k("engine-serving", "0",
               "graftspec speculative decoding: a drafter proposes k "
               "tokens per live decode row and ONE wide verify "
               "wave scores all k + 1 positions against the paged "
               "block tables; exact-match acceptance keeps output "
               "bit-identical to SPEC=0 at any temperature. Requires "
               "paged_kv (forced on)."),
    "SPEC_K": _k("engine-serving", "0 (engine default 4)",
                 "Draft tokens per verify wave (power of two); the "
                 "compiled pow2 verify ladder spans 1..spec_k and "
                 "PILOT=1 auto-tunes the live rung from the windowed "
                 "acceptance rate."),
    "SPEC_DRAFT": _k("engine-serving", "(empty: host n-gram drafter)",
                     "Draft model preset (e.g. `bench-1b` under an 8B "
                     "target): loads a resident draft model and "
                     "compiles the (\"draft\", k) ladder; empty uses "
                     "the zero-cost host n-gram drafter."),
    "TP": _k("engine-serving", "0 (legacy auto mesh)",
             "graftmesh tensor-parallel group size. 0 keeps the legacy "
             "auto mesh; 1 pins an explicit single-chip ('tp',) mesh — "
             "the bit-exact parity reference every TP gate compares "
             "against; N>1 shards weights and the paged KV pool over N "
             "devices (exact-TP: greedy output stays bit-identical to "
             "tp=1). Requires tp | n_kv_heads, n_heads, d_ff; mutually "
             "exclusive with mesh_sp>1."),
    "MESH_DEVICES": _k("engine-serving", "0 (no cap)",
                       "Caps the devices graftmesh may claim "
                       "(device_budget()); operator guard for sharing "
                       "a host between engines — e.g. MESH_DEVICES=4 "
                       "keeps a tp=2 engine off the back half of a "
                       "v5e-8."),
    "MAX_QUEUE": _k("engine-serving", "0 (unbounded)",
                    "Admission queue bound; past it submit() sheds with "
                    "a retriable 429 EngineOverloaded."),
    "DEFAULT_DEADLINE_MS": _k("engine-serving", "0 (none)",
                              "Default per-request TTL in ms; per-request "
                              "deadline_ms still wins."),
    "HEAL": _k("engine-serving", "0",
               "graftheal supervised fault recovery: a faulted wave "
               "rebuilds device state and RESURRECTS every innocent "
               "in-flight request by replaying its committed tokens "
               "(deterministic per-position sampling keys make the "
               "continued stream bit-identical, greedy or sampled); "
               "repeat faulters are bisected down to a poison "
               "quarantine. Off (the default) leaves the raw "
               "fail-everything path byte-identical to the pre-heal "
               "engine. State machine at /debug/health; gated by "
               "`make heal-audit`."),
    "HEAL_MAX_RETRIES": _k("engine-serving", "4",
                           "Per-request replay budget: how many times one "
                           "request may ride a faulted wave before its "
                           "next fault fails it terminally "
                           "(kind=internal, retriable=false) instead of "
                           "re-entering the backoff pen. Must be >= 1."),
    "HEAL_WATCHDOG_MS": _k("engine-serving", "0 (off)",
                           "Bound every boundary fetch to this wall-clock "
                           "budget: a fetch that exceeds it is declared a "
                           "hung wave and recovered like a dispatch "
                           "fault (the wedged worker thread is "
                           "abandoned, never joined). 0 fetches inline "
                           "with no watchdog thread."),

    # --- chaos fault injection (servers/chaos.py, env-only by design) -----
    "CHAOS": _k("chaos", "0", "Master switch (`1`/`true`/`yes`); never a "
                "unit parameter, so manifests cannot enable it by accident."),
    "CHAOS_SEED": _k("chaos", "0", "Seed for the deterministic fault "
                     "sequence; replays a failure byte-for-byte."),
    "CHAOS_DISPATCH_FAIL": _k("chaos", "0", "Probability a dispatch raises "
                              "(drives _fail_all rebuild)."),
    "CHAOS_ALLOC_FAIL": _k("chaos", "0", "Probability a paged-pool "
                           "allocation is refused."),
    "CHAOS_SLOW_BOUNDARY": _k("chaos", "0", "Probability a boundary fetch "
                              "is artificially delayed."),
    "CHAOS_SLOW_MS": _k("chaos", "5", "Delay for a slow boundary, ms."),
    "CHAOS_DISCONNECT": _k("chaos", "0", "Probability a client disconnect "
                           "is injected (stream close -> cancel)."),
    "CHAOS_NAN_INJECT": _k("chaos", "0", "Probability a fetched boundary's "
                           "token ids are overwritten out-of-vocab (what "
                           "NaN logits / corrupt DMA look like to the "
                           "host; drives the graftheal sentinel)."),
    "CHAOS_HANG": _k("chaos", "0", "Probability a boundary fetch sleeps "
                     "CHAOS_HANG_MS (drives the graftheal watchdog's "
                     "hung-wave declaration)."),
    "CHAOS_HANG_MS": _k("chaos", "200", "Duration of an injected fetch "
                        "hang, ms; set past HEAL_WATCHDOG_MS to trip the "
                        "watchdog."),
    "CHAOS_STICKY_RID": _k("chaos", "-1 (off)", "Request id that faults "
                           "EVERY whole-batch wave it rides — the "
                           "deterministic poison-quarantine bisection "
                           "test vector."),

    # --- runtime concurrency sanitizer (servers/graftsan.py) --------------
    "GRAFTSAN": _k("sanitizer", "0",
                   "Enable the runtime concurrency sanitizer: "
                   "order-asserting lock proxies, boundary refcount "
                   "audits, terminal-item enforcement (`make sanitize`). "
                   "Env-only by design; zero overhead when unset."),
    "GRAFTSAN_SEED": _k("sanitizer", "0",
                        "Seed for the sanitizer's interleaving explorer; "
                        "a fixed seed replays the same perturbation "
                        "sequence."),

    # --- runtime microservice / persistence / tracing ---------------------
    "API_TYPE": _k("runtime", "REST,GRPC", "Transports to serve."),
    "SERVICE_TYPE": _k("runtime", "MODEL",
                       "Role of this unit (MODEL/ROUTER/TRANSFORMER/...)."),
    "PERSISTENCE": _k("runtime", "0", "Enable model-state persistence "
                      "(Redis-backed save/restore)."),
    "PREDICTIVE_UNIT_PARAMETERS": _k("runtime", "[]",
                                     "JSON list of unit parameters injected "
                                     "by the operator."),
    "PREDICTIVE_UNIT_SERVICE_PORT": _k("runtime", "9000",
                                       "Microservice listen port."),
    "PREDICTIVE_UNIT_ID": _k("runtime", "model/unit",
                             "Unit name stamped on responses and state keys."),
    "PREDICTOR_ID": _k("runtime", "predictor", "Predictor name for state "
                       "keys."),
    "SELDON_DEPLOYMENT_ID": _k("runtime", "dep", "Deployment name for state "
                               "keys."),
    "SELDON_TPU_FASTPATH": _k("runtime", "1", "Skip flask/reloader overhead "
                              "on the REST data path (`0` disables)."),
    "SELDON_TPU_STATE_DIR": _k("runtime", "/tmp/seldon-tpu-state",
                               "Local fallback directory for persisted "
                               "state when Redis is absent."),
    "PERSISTENCE_PUSH_FREQUENCY": _k("runtime", "300",
                                     "Seconds between persistence pushes."),
    "REDIS_SERVICE_HOST": _k("runtime", "(unset)", "Redis host; unset "
                             "selects the local-file persistence fallback."),
    "REDIS_SERVICE_PORT": _k("runtime", "6379", "Redis port."),
    "TRACING": _k("runtime", "0", "Enable request tracing."),
    "TRACING_FILE": _k("runtime", "(stdout)", "JSONL trace sink path."),
    "FLIGHT_RECORDER": _k("runtime", "0",
                          "Enable the engine flight recorder: a bounded "
                          "ring of lifecycle/boundary records served at "
                          "/debug/timeline (tools/trace_view.py renders "
                          "Perfetto JSON from it)."),
    "FLIGHT_RECORDER_SIZE": _k("runtime", "4096",
                               "Flight-recorder ring capacity (records); "
                               "older records are overwritten."),
    "COMPILE_LEDGER": _k("runtime", "0",
                         "Enable the compile ledger: every jitted engine "
                         "entry point registers its static-shape variant "
                         "key; post-warmup dispatches on undeclared keys "
                         "are recorded as live-retrace witnesses. Served "
                         "at /debug/compile; gated by `make "
                         "compile-audit`."),
    "HBM_LEDGER": _k("runtime", "0",
                     "Enable the HBM ledger: weights / KV reservation / "
                     "live KV / prefix cache / workspace live-byte "
                     "accounting with high-watermarks, served at "
                     "/debug/hbm and folded into probe_hbm."),
    "SCHED_LEDGER": _k("runtime", "0",
                       "Enable the scheduler waste ledger: per-boundary "
                       "goodput attribution (bucket/group padding, chunk "
                       "fragmentation, idle boundaries, preemption "
                       "churn), queue-wait decomposition, and a "
                       "conservation audit run under the bookkeeping "
                       "lock. Served at /debug/sched; gated by `make "
                       "sched-audit`."),
    "PILOT": _k("runtime", "0",
                "Enable graftpilot, the scheduler's feedback controller: "
                "\"1\" auto-tunes dispatch_token_budget / admission group "
                "size / the adaptive-chunk rung from the sched ledger's "
                "stall-vs-contention split (hysteresis, clamped envelope, "
                "cooldowns) and schedules EDF deadline-first with "
                "starvation-proof aging; \"hold\" keeps EDF + the decision "
                "ledger but freezes every knob (operator pinning). "
                "Implies a sched ledger. Every decision lands in the "
                "/debug/pilot ledger with its signal snapshot, rationale "
                "and counterfactual effect; gated by `make pilot-audit`."),
    "DISPATCH_TIMING": _k("runtime", "0",
                          "Per-variant dispatch duration histograms, "
                          "measured at the scheduler's deliberate sync "
                          "boundary; lands in EngineStats, Prometheus "
                          "(jaxserver_dispatch_ms_*), and the flight "
                          "recorder's dispatch records (per-variant "
                          "Perfetto lanes via tools/trace_view.py)."),
    "ROOF_LEDGER": _k("runtime", "0",
                      "Enable graftroof, the MFU/MBU roofline ledger: "
                      "closed-form FLOPs + HBM-bytes pricing of every "
                      "dispatch key joined with the measured wave timing "
                      "(implies DISPATCH_TIMING) into per-variant "
                      "compute/bandwidth/host-bound classification, plus "
                      "the host-pre / device / host-post boundary "
                      "decomposition with a 1% conservation audit. "
                      "Served at /debug/roof, mirrored as jaxserver_mfu "
                      "/ jaxserver_mbu / jaxserver_host_frac gauges and "
                      "flight-recorder roof records (Perfetto host/"
                      "device lanes); gated by `make roof-audit`."),
    "ROOF_PEAK_TFLOPS": _k("runtime", "(unset)",
                           "Operator override for the roofline's peak "
                           "dense TFLOPS (the MFU denominator). Unset: "
                           "the builtin per-platform table keyed on the "
                           "JAX device_kind, falling back to a one-shot "
                           "numpy microbench on unknown platforms."),
    "ROOF_PEAK_GBS": _k("runtime", "(unset)",
                        "Operator override for the roofline's peak HBM "
                        "GB/s (the MBU denominator). Resolution order "
                        "matches ROOF_PEAK_TFLOPS."),
    "TRACE_PROFILE_N": _k("runtime", "0",
                          "Capture a jax.profiler device trace over the "
                          "first N dispatched scheduler boundaries "
                          "(0 = off); profile-start/-stop markers land "
                          "in the flight recording."),
    "TRACE_PROFILE_DIR": _k("runtime", "/tmp/seldon-tpu-profile",
                            "Output directory for the TRACE_PROFILE_N "
                            "capture."),
    "PODINFO_ANNOTATIONS": _k("runtime", "/etc/podinfo/annotations",
                              "Downward-API annotations file."),
    "PREDICTOR_HOST": _k("runtime", "(unset)",
                         "Predictor endpoint an explainer calls back into."),

    # --- orchestrator -----------------------------------------------------
    "ENGINE_PREDICTOR": _k("orchestrator", "(unset)",
                           "Base64 predictor spec the service orchestrator "
                           "deserializes at boot."),
    "ENGINE_WORKERS": _k("orchestrator", "1",
                         "Orchestrator worker processes."),
    "SELDON_TPU_GRPC_WORKERS": _k("orchestrator", "8",
                                  "gRPC server thread-pool size."),
    "PORT": _k("orchestrator", "8080", "Request-logger listen port."),
    "SELDON_MESSAGE_LOGGING_SERVICE": _k("orchestrator", "(disabled)",
                                         "URL of the request/response "
                                         "logging sink."),

    # --- operator / storage ----------------------------------------------
    "WEBHOOK_CERT_DIR": _k("operator-storage",
                           "/tmp/k8s-webhook-server/serving-certs",
                           "Admission-webhook TLS cert directory."),
    "KUBECONFIG": _k("operator-storage", "~/.kube/config",
                     "Kubeconfig path when running out-of-cluster."),
    "SELDON_TPU_LOCALSTORE_DEBUG": _k("operator-storage", "0",
                                      "Verbose local object-store logging."),
    "SELDON_TPU_MODEL_DIR": _k("operator-storage", "/mnt/models",
                               "Download target for model artifacts."),
    "AZURE_SAS_TOKEN": _k("operator-storage", "(unset)",
                          "SAS token appended to Azure blob downloads."),
    "SAGEMAKER_ENDPOINT_NAME": _k("operator-storage", "(unset)",
                                  "SageMaker endpoint the proxy server "
                                  "invokes."),
    "SAGEMAKER_RUNTIME_URL": _k("operator-storage", "(regional default)",
                                "Override for the SageMaker runtime URL."),

    # --- multi-host TPU slice (parallel/distributed.py) -------------------
    "TPU_WORKER_HOSTNAMES_SVC": _k("distributed", "(unset)",
                                   "Headless-service name enumerating slice "
                                   "workers."),
    "TPU_WORKER_COUNT": _k("distributed", "1",
                           "Expected process count in the slice."),
    "TPU_COORDINATOR_PORT": _k("distributed", "(jax default)",
                               "Coordinator port for "
                               "jax.distributed.initialize."),

    # --- bench & probe tools (tools/*.py, CPU-smoke friendly) -------------
    "MB_PRESET": _k("bench-tools", "bench-1b", "Decode microbench model "
                    "preset (also profile_decode)."),
    "MB_SLOTS": _k("bench-tools", "160", "Microbench batch slots."),
    "MB_WINDOW": _k("bench-tools", "257", "Microbench KV window."),
    "MB_ACT": _k("bench-tools", "(follows weights)", "Microbench activation "
                 "dtype."),
    "MB_DRAFT": _k("bench-tools", "(unset)", "Draft-model preset for the "
                   "`--spec k` microbench mode; adds the draft dispatch "
                   "to the wave cost."),
    "TUNE_ACT": _k("bench-tools", "int8", "Activation dtype for the 8b "
                   "tuning sweep."),
    "PROBE_PRESET": _k("bench-tools", "llama3-8b", "Slot-cliff probe preset "
                       "(`tiny` = CPU smoke)."),
    "PROBE_PAGED": _k("bench-tools", "0", "Add the paged-KV sweep to "
                      "probe_hbm / probe_slot_cliff."),
    "PB_PRESET": _k("bench-tools", "tiny", "Prefix-cache probe preset."),
    "PB_PROMPT": _k("bench-tools", "128", "Prefix probe prompt length."),
    "PB_BLOCK": _k("bench-tools", "16", "Prefix probe trie block size."),
    "PB_NREQ": _k("bench-tools", "16", "Prefix probe request count."),
    "PB_KV": _k("bench-tools", "(preset dtype)", "Prefix probe KV dtype."),
    "PB_SHARED_FRAC": _k("bench-tools", "0.5", "Fraction of requests "
                         "sharing the warm prefix."),
    "PC_PRESET": _k("bench-tools", "tiny", "Chunked-prefill probe preset."),
    "PC_PROMPT": _k("bench-tools", "32", "Chunked probe short-prompt "
                    "length."),
    "PC_LONG": _k("bench-tools", "8*PC_PROMPT", "Chunked probe interloper "
                  "prompt length."),
    "PC_CHUNK": _k("bench-tools", "PC_PROMPT", "Prefill chunk length."),
    "PC_BUDGET": _k("bench-tools", "PC_CHUNK", "Dispatch token budget."),
    "PC_STREAMS": _k("bench-tools", "4", "Concurrent decode streams."),
    "PC_NEW": _k("bench-tools", "64", "New tokens per stream."),
    "PC_KV": _k("bench-tools", "(preset dtype)", "Chunked probe KV dtype."),
    "CH_PRESET": _k("bench-tools", "tiny", "Chaos probe preset."),
    "CH_N": _k("bench-tools", "200", "Chaos probe request count."),
    "CH_SEED": _k("bench-tools", "0", "Chaos probe fault seed."),
    "CH_DISPATCH_FAIL": _k("bench-tools", "0.02", "Chaos probe dispatch "
                           "fault rate."),
    "CH_ALLOC_FAIL": _k("bench-tools", "0.02", "Chaos probe alloc fault "
                        "rate."),
    "CH_SLOW": _k("bench-tools", "0.05", "Chaos probe slow-boundary rate."),
    "CH_DISCONNECT": _k("bench-tools", "0.01", "Chaos probe disconnect "
                        "rate."),
    "CH_PAGED": _k("bench-tools", "0", "Chaos probe paged-KV mode."),
    "CH_DEADLINE_FRAC": _k("bench-tools", "0.1", "Fraction of chaos probe "
                           "requests given tight deadlines."),
    "CH_CANCEL_FRAC": _k("bench-tools", "0.1", "Fraction of chaos probe "
                         "requests cancelled mid-flight."),

    # --- bench harness (bench.py / bench_orchestrator.py) -----------------
    "BENCH_PRESET": _k("bench-harness", "llama3-8b",
                       "Model preset for the headline bench run "
                       "(`tiny` = CPU smoke)."),
    "BENCH_SLOTS": _k("bench-harness", "0 (192 for llama3-8b, else 160)",
                      "Decode batch slots; 0 picks the measured per-preset "
                      "knee."),
    "BENCH_NREQ": _k("bench-harness", "0 (2x slots)",
                     "Requests in the throughput phase."),
    "BENCH_ADMIT": _k("bench-harness", "0 (16 for llama3-8b, else 8)",
                      "Max admissions per scheduler step."),
    "BENCH_PROMPT": _k("bench-harness", "128", "Prompt length in tokens."),
    "BENCH_NEW": _k("bench-harness", "128", "New tokens per request."),
    "BENCH_CHUNK": _k("bench-harness", "64", "Decode dispatch chunk."),
    "BENCH_KV": _k("bench-harness", "int8", "KV cache dtype."),
    "BENCH_ATTN": _k("bench-harness", "(model default)",
                     "Attention kernel override."),
    "BENCH_WEIGHTS": _k("bench-harness", "int8",
                        "Weight dtype (`bf16` reverts weight-only int8)."),
    "BENCH_ACT": _k("bench-harness", "int8",
                    "W8A8 matmul activation dtype (`bf16` reverts)."),
    "BENCH_PREFIX": _k("bench-harness", "0",
                       "Run the shared-prefix cache phase."),
    "BENCH_PREFIX_BLOCK": _k("bench-harness", "16",
                             "Prefix phase trie block size."),
    "BENCH_PREFIX_NREQ": _k("bench-harness", "24",
                            "Prefix phase request count."),
    "BENCH_CHUNKED": _k("bench-harness", "0",
                        "Run the chunked-prefill interference phase."),
    "BENCH_CHUNKED_STREAMS": _k("bench-harness", "6",
                                "Chunked phase concurrent decode streams."),
    "BENCH_CHUNKED_LONG_X": _k("bench-harness", "8",
                               "Chunked phase interloper prompt length, as "
                               "a multiple of BENCH_PROMPT."),
    "BENCH_PAGED": _k("bench-harness", "0",
                      "Run the paged-vs-dense fixed-HBM phase."),
    "BENCH_PAGED_DENSE_SLOTS": _k("bench-harness", "4",
                                  "Dense-slab slot count the paged phase "
                                  "compares against."),
    "BENCH_PAGED_KV_BLOCK": _k("bench-harness", "16",
                               "Paged phase KV block size."),
    "BENCH_SPEC": _k("bench-harness", "0",
                     "Run the speculative-decoding phase: the same "
                     "greedy closed wave SPEC on vs off at equal "
                     "hardware, asserting bit-identical streams and "
                     "reporting per-leg decode tok/s, dispatches/token "
                     "and the acceptance rate (bench_compare gates "
                     "acceptance_rate higher-is-better and tok_s "
                     "no-regression)."),
    "BENCH_SPEC_K": _k("bench-harness", "4",
                       "Draft tokens per verify wave in the spec "
                       "phase."),
    "BENCH_SPEC_DRAFT": _k("bench-harness", "self",
                           "Spec phase drafter: `self` (target weights "
                           "— the acceptance upper bound), empty for "
                           "the host n-gram drafter, or a preset name "
                           "for a resident draft model."),
    "BENCH_MESH": _k("bench-harness", "0",
                     "Run the graftmesh phase: the same greedy paged + "
                     "chunked closed wave tp=BENCH_MESH_TP vs single-chip at "
                     "EQUAL engine config, asserting bit-identical "
                     "streams and recording per-device HBM "
                     "(bench_compare gates bytes_per_device and "
                     "kv_per_device_frac lower-is-better). On fake "
                     "devices the speedup is not meaningful; the parity "
                     "and sharding-dividend record is."),
    "BENCH_MESH_TP": _k("bench-harness", "2",
                        "TP group size for the mesh phase leg."),
    "BENCH_HEAL": _k("bench-harness", "0",
                     "Run the graftheal phase: the same greedy closed "
                     "wave clean vs under seeded CHAOS dispatch faults "
                     "with HEAL on, asserting resurrected streams "
                     "bit-identical to the clean leg and reporting "
                     "goodput_retained_frac (bench_compare gates it "
                     "higher-is-better) and user_visible_errors "
                     "(lower-is-better, exact)."),
    "BENCH_HEAL_FAULT": _k("bench-harness", "0.05",
                           "Dispatch-fault probability for the heal "
                           "phase's chaos leg."),
    "BENCH_SLO": _k("bench-harness", "1 for bench-1b, else 0",
                    "Run the TTFT SLO search phase."),
    "BENCH_SLO_CHUNK": _k("bench-harness", "0 (adaptive)",
                          "Pin a fixed dispatch chunk for the SLO search "
                          "instead of occupancy-adaptive chunking."),
    "BENCH_PILOT": _k("bench-harness", "0",
                      "Run the pilot phase: a mixed-deadline closed wave "
                      "twice at equal hardware — PILOT=1 vs pilot off — "
                      "reporting slo_goodput, decision count, EDF "
                      "inversions and final knob values for both legs."),
    "BENCH_SECOND_PRESET": _k("bench-harness",
                              "bench-1b for llama3-8b, else (empty)",
                              "Trailing deployment-proxy preset; empty "
                              "disables the second phase."),
    "BENCH_SECOND_SLOTS": _k("bench-harness", "0 (160)",
                             "Slots for the trailing preset run."),
    "BENCH_SECOND_SLO": _k("bench-harness", "1",
                           "Run the SLO search in the trailing phase."),
    "BENCH_ORCH_CLIENTS": _k("bench-harness", "32",
                             "Orchestrator bench concurrent clients."),
    "BENCH_ORCH_CLIENT_PROCS": _k("bench-harness", "2",
                                  "Client processes generating load."),
    "BENCH_ORCH_SECONDS": _k("bench-harness", "12",
                             "Measurement window per configuration."),
    "BENCH_ORCH_REPEATS": _k("bench-harness", "3",
                             "Repeats per configuration (best kept)."),
    "BENCH_ORCH_TRANSPORTS": _k("bench-harness", "rest,grpc",
                                "Transports to sweep."),
    "BENCH_ORCH_PAYLOADS": _k("bench-harness", "ndarray,dense",
                              "Payload shapes to sweep."),
    "BENCH_ORCH_GRAPHS": _k("bench-harness", "inproc,netunit",
                            "Graph topologies to sweep (in-process stub vs "
                            "real microservice subprocess)."),
    "BENCH_ORCH_FAST": _k("bench-harness", "1",
                          "Expose the framed-proto fast lane on port+1; "
                          "`0` pins the hop to full gRPC for A/B."),

    # --- platform (owned by JAX / Kubernetes / cloud SDKs) ----------------
    "JAX_PLATFORMS": _k("platform", "(auto)", "JAX backend selection; "
                        "`cpu` pins tests and probes off the TPU, and is "
                        "the only way bench.py runs without one."),
    "JAX_COMPILATION_CACHE_DIR": _k("platform", "<checkout>/.jax_cache",
                                    "Persistent XLA compile cache. Set: JAX "
                                    "reads it and the code sets no other. "
                                    "Unset: seldon_tpu.device."
                                    "enable_compile_cache points JAX at the "
                                    "fixed default."),
    "XLA_FLAGS": _k("platform", "(unset)", "XLA compiler flags; the entry "
                    "shim appends host-platform device-count flags for "
                    "CPU smoke runs."),
    "KUBERNETES_SERVICE_HOST": _k("platform", "kubernetes.default.svc",
                                  "In-cluster API host (set by the "
                                  "kubelet)."),
    "KUBERNETES_SERVICE_PORT": _k("platform", "443", "In-cluster API port."),
    "AWS_ACCESS_KEY_ID": _k("platform", "(unset)", "SageMaker proxy "
                            "credentials."),
    "AWS_SECRET_ACCESS_KEY": _k("platform", "(unset)", "SageMaker proxy "
                                "credentials."),
    "AWS_SESSION_TOKEN": _k("platform", "(unset)", "SageMaker proxy "
                            "credentials."),
    "AWS_REGION": _k("platform", "us-east-1", "SageMaker proxy region."),
    "HOSTNAME": _k("platform", "(pod name)", "Used to derive the process "
                   "index within a TPU slice."),
    "PYTHONPATH": _k("platform", "(inherited)", "Propagated to operator "
                     "local-mode child processes."),
}
