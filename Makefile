# CI targets (reference: Jenkinsfile -> Makefile.ci + per-module Makefiles).
.PHONY: proto test test-e2e tier1 lint sanitize trace-smoke compile-audit sched-audit pilot-audit spec-audit roof-audit mesh-audit heal-audit bench bench-compare bench-orchestrator native native-tsan ci fuzz-alloc fuzz-chaos fuzz-graftsan

# tier1 uses PIPESTATUS / pipefail (bash-isms).
tier1: SHELL := /bin/bash

proto:
	protoc --python_out=seldon_tpu/proto -I seldon_tpu/proto seldon_tpu/proto/prediction.proto

native:
	$(MAKE) -C native

# Static invariants (docs/operations.md "Static invariants: graftlint"):
# hot-sync, lock-guard, lockorder, retrace, outcome, env-knob vs the
# checked-in baseline, plus the graftflow dataflow trio (docs/operations.md
# "Static dataflow: graftflow"): shape-lattice certification, the
# (paged, chunked, prefix) config-reachability matrix with its dense-slab
# kill-list, and the sharding-consistency rules — plus the graftnum
# numerics/lifetime certifier (docs/operations.md "Numerics invariants:
# graftnum"): num-barrier (quantize scales + int8 dequant products must be
# optimization_barrier-pinned before materialization boundaries),
# use-after-donate (reads of donated jit buffers + host-side captures),
# and einsum-broadcast/mask-dtype (silent size-1 label broadcast, bf16
# mask fill). Prints per-pass graftnum counts next to the kill-list
# needle and fails if the lint run itself exceeds its 60 s self-runtime
# budget — then a bytecode-compile sweep of the serving + tools trees.
lint:
	python -m tools.graftlint
	python -m compileall -q seldon_tpu tools

# Dynamic half of the concurrency contract (docs/operations.md "Dynamic
# sanitizer: graftsan"): the engine-facing tier-1 subset re-run under
# GRAFTSAN=1 — order-asserting lock proxies, boundary refcount/slot
# audits, terminal-item enforcement, seeded interleaving perturbation.
sanitize:
	env JAX_PLATFORMS=cpu GRAFTSAN=1 GRAFTSAN_SEED=$${GRAFTSAN_SEED:-0} \
	  python -m pytest tests/test_graftsan.py tests/test_lifecycle.py \
	  tests/test_chaos.py tests/test_paged_kv.py \
	  tests/test_chunked_prefill.py tests/test_prefix_cache.py \
	  -x -q -m "not slow"

test:
	python -m pytest tests/ -x -q -m "not e2e"

test-e2e:
	python -m pytest tests/ -x -q -m e2e

# The ROADMAP.md tier-1 verify line, verbatim: CPU-pinned, no -x (full
# count), log at /tmp/_t1.log, prints DOTS_PASSED for the driver.
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

# Long-haul randomized sweep of the paged-KV block allocator. The fast
# tier runs the same test at FUZZ_EXAMPLES=300 (the pytest default).
fuzz-alloc:
	env JAX_PLATFORMS=cpu FUZZ_EXAMPLES=20000 \
	  python -m pytest tests/test_paged_kv.py -q -m fuzz

# Long-haul chaos soak of the request lifecycle (deadlines, cancels,
# injected dispatch/alloc faults, drain). Seeded: CHAOS_SEED replays a
# failing fault sequence byte-for-byte; FUZZ_EXAMPLES scales the number
# of requests per soak. tier-1 runs only the fast deterministic chaos
# tests (the soak here is marked slow).
fuzz-chaos:
	env JAX_PLATFORMS=cpu FUZZ_EXAMPLES=1000 CHAOS_SEED=$${CHAOS_SEED:-0} \
	  python -m pytest tests/test_chaos.py -q -m fuzz

# Long-haul graftsan soak: >=200 mixed dense/paged/chunked requests per
# run under the sanitizer. GRAFTSAN_SEED replays an interleaving
# schedule; FUZZ_EXAMPLES scales the request count (split across modes).
fuzz-graftsan:
	env JAX_PLATFORMS=cpu GRAFTSAN_SEED=$${GRAFTSAN_SEED:-0} \
	  FUZZ_EXAMPLES=$${FUZZ_EXAMPLES:-600} \
	  python -m pytest tests/test_graftsan.py -q -m fuzz

# Observability smoke (docs/operations.md "Reading a flight recording"):
# short loadtester run against the tiny server with TRACING=1 +
# FLIGHT_RECORDER=1 + GRAFTSAN=1 — asserts a non-empty span sink,
# end-to-end trace-id adoption, a valid Perfetto conversion of
# /debug/timeline, and zero graftsan violations.
trace-smoke:
	env JAX_PLATFORMS=cpu python -m tools.trace_smoke

# Compile/device observatory gate (docs/operations.md "Diagnosing a
# retrace storm"): warmed tiny server + loadtester with COMPILE_LEDGER +
# HBM_LEDGER + DISPATCH_TIMING on — asserts ZERO live retraces after
# warmup, a dispatched-variant count within the budget, per-variant
# timing reaching stats/recorder/trace_view, and the /debug/compile +
# /debug/hbm schemas. --static-xcheck additionally proves the runtime
# dispatch set is contained in graftflow's closed-form static lattice
# (engine.static_lattice()) and that warmup declared exactly that set.
compile-audit:
	env JAX_PLATFORMS=cpu python -m tools.compile_audit --static-xcheck

# Scheduler waste observatory gate (docs/benchmarking.md "Reading the
# waste report"): warmed tiny server + loadtester with SCHED_LEDGER +
# FLIGHT_RECORDER on — asserts zero attribution on the idle engine, the
# conservation invariant (useful + pad tokens re-sum to dispatched
# cells; wait components re-sum to total wait), loadtester/route schema
# parity, the EngineStats mirror, and the trace_view waste counter lane.
sched-audit:
	env JAX_PLATFORMS=cpu python -m tools.sched_audit

# Pilot controller gate (docs/operations.md "Flying with the
# autopilot"): warmed tiny chunked server + mixed-deadline loadtester
# under PILOT=1 + GRAFTSAN=1 — asserts the controller converges to a
# ledgered decision, every knob stays inside its clamp envelope, the
# conservation audit and sanitizer stay clean under the pilot, route /
# loadtester parity, the jaxserver_pilot_* gauges, and the trace_view
# decision lane.
pilot-audit:
	env JAX_PLATFORMS=cpu python -m tools.pilot_audit

# Speculative-decoding gate (docs/benchmarking.md "Speculative
# decoding"): the tiny server booted twice — plain, then SPEC=1 behind
# the real REST app under a loadtester window with GRAFTSAN +
# SCHED_LEDGER + COMPILE_LEDGER on — asserts bit-exact greedy parity,
# zero live retraces with the verify ladder inside the static lattice,
# the acceptance identity (accepted + rejected == drafted) and four-way
# conservation, loadtester/route parity, the jaxserver_spec_* gauges,
# and the trace_view verify lanes + acceptance counter.
spec-audit:
	env JAX_PLATFORMS=cpu python -m tools.spec_audit

# Roofline observatory gate (docs/benchmarking.md "Reading the
# roofline"): warmed tiny server + loadtester with ROOF_LEDGER +
# FLIGHT_RECORDER on — asserts the /debug index lists every surface,
# zero attribution on the idle engine, per-variant mfu/mbu in [0, 1]
# with sane compute/bandwidth/host bound labels, the step-decomposition
# conservation invariant (host-pre + device + host-post + overlap
# re-sum to the boundary wall within 1%), predicted-vs-measured inside
# a generous CPU band, loadtester/route parity, the jaxserver_mfu/mbu/
# host_frac gauges, and the trace_view host/device lanes.
roof-audit:
	env JAX_PLATFORMS=cpu python -m tools.roof_audit

# Tensor-parallel serving gate (docs/operations.md "Serving on the
# mesh"): the tiny paged + chunked server booted twice on the fake 8-device CPU
# mesh — pinned to an explicit single chip (tp=1), then as a TP=2
# group via the env knob behind the real REST app — under a loadtester
# window with GRAFTSAN + SCHED_LEDGER + COMPILE_LEDGER + HBM_LEDGER +
# ROOF_LEDGER on. Asserts bit-exact greedy parity across a mixed-length
# prompt matrix, one sealed lattice with zero live retraces for the
# whole group, four-way sched + roofline conservation, zero sanitizer
# violations, zero live KV bytes after the drain (leak-free), and the
# per-device HBM invariants (weights = per-device x devices, KV
# reservation halved per chip).
mesh-audit:
	env JAX_PLATFORMS=cpu python -m tools.mesh_audit

# Supervised fault-recovery gate (docs/operations.md "Surviving a wave
# fault"): the tiny server under HEAL=1 + CHAOS=1 — a seeded storm of
# dispatch faults, watchdog-length hangs and NaN injections with no
# poison source — asserts a greedy + sampled wave stays byte-identical
# to a clean reference engine, zero user-visible errors, /healthz ready
# through the storm, zero sanitizer violations and live retraces, the
# frozen /debug/health schema, the jaxserver_heal_* gauges, and the
# flight-recorder heal records + trace_view heal lane.
heal-audit:
	env JAX_PLATFORMS=cpu python -m tools.heal_audit

bench:
	python bench.py

# Perf-regression diff of two bench JSON files (docs/benchmarking.md
# "Comparing runs"): make bench-compare BASE=base.json CAND=cand.json
bench-compare:
	python -m tools.bench_compare $(BASE) $(CAND)

bench-orchestrator:
	python bench_orchestrator.py

ci: lint test test-e2e sanitize trace-smoke compile-audit sched-audit pilot-audit spec-audit roof-audit mesh-audit heal-audit

native-tsan:
	$(MAKE) -C native tsan
