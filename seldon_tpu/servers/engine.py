"""Continuous-batching inference engine (the TPU serving hot loop).

Reference comparison: the reference has NO batching anywhere — each request
walks the graph and hits a Flask worker alone (SURVEY.md §7 "dynamic
batching ... the key new hot-loop component"). This engine is the TPU-native
answer, vLLM-style iteration-level scheduling mapped onto XLA's static-shape
world:

 * A fixed pool of B slots shares one pre-allocated KV cache
   [L, B, 1, Smax, Hkv*Dh] (one row a token, transformer.cache_spec);
   decode runs in CHUNKS of `decode_chunk` steps —
   one jitted `lax.scan` over all slots per dispatch — so the host pays
   one dispatch + one sync per K tokens/slot instead of per token.
   Per-row EOS/length termination inside the chunk is value-level masking.
 * Admission is ONE fused jitted call per group: waiting requests with the
   same prompt bucket are prefilled together [G, Sb] (G padded to a power
   of two, bounding compile variants), scattered into their slots, first
   tokens sampled, and slot state armed — all device-side, no host sync
   until the boundary read.
 * The scheduler dispatches all admissions, then the decode chunk, then
   reads everything in one wave — device stays busy while the host waits,
   and host round-trip latency is amortized over K steps x B slots.
 * `warmup()` pre-compiles every (prompt-bucket x group-size) admission
   variant plus the chunk step, so first requests never eat a compile.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import json
import logging
import math
import os
import queue
import secrets
import statistics
import threading
import time
from typing import (Any, Deque, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from seldon_tpu.core import tracing
from seldon_tpu.models import tp_sharding
from seldon_tpu.models import slot as slot_rules
from seldon_tpu.models import transformer
from seldon_tpu.models import spec_decode as spec_model
from seldon_tpu.models.config import OP_ATTN_MAMBA, ModelConfig
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import compile_ledger, controller, cost_model
from seldon_tpu.servers import flight_recorder, graftsan, hbm_ledger
from seldon_tpu.servers import sched_ledger, shape_lattice, supervisor
from seldon_tpu.servers.chaos import ChaosConfig, ChaosMonkey

logger = logging.getLogger(__name__)
# One INFO line per finished request and one per load (docs/
# distributed-tracing.md "The access line"); standard logging
# configuration silences it.
access_log = logging.getLogger("seldon_tpu.access")


def _spread(mesh) -> bool:
    """The program lies over several devices: the compiler partitions
    it, so no kernel reads the slab or the expert stack whole there."""
    return mesh is not None and mesh.size > 1


def _named_partial(impl, /, *args, **bound):
    """functools.partial carrying `impl`'s name. jax.jit names the XLA
    module after its callable and a bare partial has none (every engine
    program then reads `jit__unknown` in a profile); with the name the
    "XLA Modules" line reads jit__admit_impl, jit__chunk_impl, ... and
    a trace reduction finds programs by name. Every engine jit that
    binds arguments is built over one of these."""
    fn = functools.partial(impl, *args, **bound)
    fn.__name__ = fn.__qualname__ = impl.__name__
    return fn


# HTTP status per error-item kind, for errors that surface BEFORE any
# stream bytes went out (after that they ride the in-band trailer).
# Transports duck-read `http_status` off the exception, so attaching it
# where the typed exception is built keeps the wrapper engine-agnostic.
KIND_HTTP_STATUS = {
    "capacity": 429,
    "draining": 503,
    "shutdown": 503,
    "preempted": 503,
    "deadline": 504,  # client-set TTL lapsed — not a server fault
    "cancelled": 499,  # client closed the connection (nginx convention)
    "poison": 500,  # quarantined: deterministically faults the wave
}


class EngineOverloaded(RuntimeError):
    """Admission queue is full — the request was shed at submit time.
    Retriable with backoff; transports map it to HTTP 429."""

    http_status = 429
    retriable = True


class EngineDraining(RuntimeError):
    """The engine is draining or stopped and not admitting new work.
    Retriable against another replica; transports map it to HTTP 503."""

    http_status = 503
    retriable = True


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 32
    max_seq_len: int = 2048
    prompt_buckets: Sequence[int] = (32, 128, 512, 1024)
    max_admit: int = 8  # largest batched-prefill group (power of two)
    decode_chunk: int = 8  # decode steps per dispatch (latency/thruput knob)
    idle_sleep_s: float = 0.002
    # Boundary fetches on a dedicated thread so dispatches never wait on
    # a host<->device round trip (auto-disabled on multi-process meshes:
    # SPMD dispatch decisions must not depend on fetch timing).
    async_fetch: bool = True
    # Prefill-priority scheduling: scale the dispatched chunk length with
    # slot occupancy so ONE engine holds both the TTFT SLO and saturated
    # throughput. A request can only be admitted at a chunk boundary;
    # with mostly-free slots (under-capacity, latency-sensitive regime) a
    # long chunk is pure admission latency, while at saturation nothing
    # can be admitted mid-chunk anyway — so: near-empty -> the low rung,
    # near-full -> decode_chunk. Compiles one chunk variant per
    # power-of-two rung (min_chunk..decode_chunk). min_chunk is no
    # latency knob: it is the low rung's CAP and its cold-start value.
    # The async scheduler sizes the rung once, from the step and the
    # host turn it measures on itself, to the fewest steps whose wave
    # still covers the turn (_chunk_steps), and compiles that one
    # further variant when it does.
    adaptive_chunk: bool = True
    min_chunk: int = 4
    # Prompt prefix KV cache (opt-in): reuse device-resident KV of
    # previously-seen block-aligned prompt prefixes so admissions prefill
    # only the uncached suffix (servers/prefix_cache.py). False keeps the
    # admission path byte-identical to the pre-prefix engine. Single-
    # process meshes only (the index is host-side; multi-process SPMD
    # dispatch must not depend on per-host trie state).
    prefix_cache: bool = False
    prefix_block: int = 16  # trie granularity; reuse is block-aligned
    prefix_cache_bytes: int = 256 << 20  # HBM budget for retained KV
    # Stall-free scheduling (opt-in): split admissions into block-aligned
    # prefill CHUNKS of `prefill_chunk` tokens and pack at most
    # `dispatch_token_budget` prefill tokens into each scheduler dispatch
    # alongside the decode chunk, instead of draining the admission queue
    # first — a long-prompt arrival no longer stalls in-flight streams
    # for its whole prefill, so tail ITL stays flat under mixed traffic
    # (Sarathi-style chunked prefill). Chunk k prefills against the KV of
    # chunks 0..k-1 already resident in the slot cache
    # (transformer.prefill_with_prefix); the final chunk samples the
    # first token exactly like the one-shot path, so greedy outputs stay
    # bit-identical. False keeps the dispatch path byte-identical to the
    # uninterleaved engine.
    chunked_prefill: bool = False
    prefill_chunk: int = 128  # power of two, multiple of prefix_block
    dispatch_token_budget: int = 0  # prefill tokens per dispatch; 0 -> chunk
    # Paged KV cache (opt-in): replace the per-slot contiguous KV slab
    # with a global block pool + per-slot block tables, so a stream
    # allocates KV in `kv_block`-token blocks as it decodes instead of
    # reserving max_seq_len up front — short-decode traffic packs several
    # times more concurrent streams into the same HBM budget, and prefix-
    # cache hits share prompt blocks zero-copy (refcounts, not device
    # copies; copy-on-write when a stream writes into a partially-filled
    # shared block). False keeps the dense dispatch path byte-identical.
    # Single-process meshes only (host-side allocator, like prefix_cache).
    paged_kv: bool = False
    kv_block: int = 16  # tokens per pool block; power of two
    kv_pool_blocks: int = 0  # pool size incl. trash block; 0 -> dense-equiv
    # Speculative decoding (opt-in; graftspec): a resident drafter
    # proposes up to `spec_k` tokens per live slot each wave and the
    # target model verifies all k+1 positions in ONE wide dispatch
    # (models/spec_decode.py) — accepted prefixes commit, the first
    # mismatch rolls the row back by a host-side block-table trim.
    # Sampling keys are sequential per position, so verification is
    # EXACT: outputs are bit-identical to the spec-off engine at any
    # temperature. Requires paged_kv (rollback is a table trim); the
    # verify wave takes the decode chunk's place in a scheduler wave.
    # `spec_draft` names a draft checkpoint preset (the 1B next to an 8B
    # target); "" uses the zero-dispatch n-gram drafter
    # (servers/spec_decode.py). False keeps every dispatch
    # byte-identical to the spec-off engine.
    spec_decode: bool = False
    spec_k: int = 4  # max drafted tokens/wave; rungs are pow2 1..spec_k
    spec_draft: str = ""  # draft model preset; "" -> n-gram drafter
    # graftmesh (opt-in): exact tensor parallelism over the mesh's 'tp'
    # axis (models/tp_sharding.py). tp > 1 shards the qkv / gate / up
    # projections and the KV cache's head axis across tp devices and
    # runs every dispatch family SPMD, with greedy output bit-identical
    # to tp=1 (output-dim-only sharding — no contraction is ever
    # partitioned, so per-element reduction order matches a single
    # chip). Requires a mesh whose 'tp' axis is exactly this size
    # (servers/mesh_engine.build_tp_mesh) and tp | n_kv_heads,
    # tp | n_heads, tp | d_ff. tp=1 (default) keeps every code path
    # byte-identical to the pre-mesh engine — deliberately a CONFIG
    # axis, not a global, so per-tier TP groups (Nitsum) can coexist
    # in one process later. flash/ring attention kernels are not
    # tp-threaded; engine __init__ rejects the combination.
    tp: int = 1
    # Request-lifecycle hardening (defaults keep the dispatch path
    # byte-identical): TTL applied to requests that set no
    # SamplingParams.deadline_ms of their own, a bound on the admission
    # queue (submit raises EngineOverloaded instead of queueing
    # unboundedly; 0 = unbounded), and deterministic fault injection
    # (servers/chaos.py; None also consults ChaosConfig.from_env so the
    # CHAOS=1 env gate works without plumbing a config through).
    default_deadline_ms: int = 0
    max_queue: int = 0
    chaos: Optional[ChaosConfig] = None
    # graftheal supervised fault recovery (servers/supervisor.py; False
    # also consults the HEAL=1 env gate via supervisor.build, so
    # recovery can be enabled without config plumbing). Off keeps the
    # _fail_all failure path byte-identical to the pre-heal engine:
    # a faulted wave fails every live request. On, innocent in-flight
    # requests are resurrected by replaying their committed tokens
    # through the normal admission path (bit-identical continuation via
    # per-position sampling keys), bounded by a per-request replay
    # budget; heal_watchdog_ms > 0 additionally bounds every boundary
    # device fetch so a hung wave faults instead of wedging.
    heal: bool = False
    heal_max_retries: int = 4
    heal_watchdog_ms: int = 0

    def __post_init__(self):
        def pow2(n: int) -> bool:
            return n >= 1 and (n & (n - 1)) == 0

        if self.min_chunk > self.decode_chunk:
            raise ValueError(
                f"min_chunk ({self.min_chunk}) must not exceed decode_chunk "
                f"({self.decode_chunk}) — the adaptive ladder interpolates "
                f"between them"
            )
        if not pow2(self.max_admit):
            raise ValueError(
                f"max_admit ({self.max_admit}) must be a power of two — "
                f"admission groups are padded to pow2 to bound jit variants"
            )
        for b in self.prompt_buckets:
            if not pow2(b):
                raise ValueError(
                    f"prompt_buckets entry {b} must be a power of two — "
                    f"each bucket is a compiled prefill variant"
                )
        if self.chunked_prefill:
            if not pow2(self.prefill_chunk):
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a power "
                    f"of two — each chunk length is a compiled variant"
                )
            if self.prefill_chunk % self.prefix_block:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"multiple of the KV block size prefix_block "
                    f"({self.prefix_block}) so chunk boundaries never split "
                    f"a prefix-cache block"
                )
            if self.dispatch_token_budget and (
                self.dispatch_token_budget < self.prefill_chunk
            ):
                raise ValueError(
                    f"dispatch_token_budget ({self.dispatch_token_budget}) "
                    f"must be 0 (one chunk per dispatch) or >= prefill_chunk "
                    f"({self.prefill_chunk}) — a dispatch must fit at least "
                    f"one chunk to make progress"
                )
        if self.paged_kv:
            if not pow2(self.kv_block):
                raise ValueError(
                    f"kv_block ({self.kv_block}) must be a power of two — "
                    f"block offsets are computed with pow2 div/mod"
                )
            if self.kv_block % self.prefix_block:
                raise ValueError(
                    f"kv_block ({self.kv_block}) must be a multiple of "
                    f"prefix_block ({self.prefix_block}) so trie spans never "
                    f"straddle a pool block"
                )
            if self.max_seq_len % self.kv_block:
                raise ValueError(
                    f"max_seq_len ({self.max_seq_len}) must be a multiple of "
                    f"kv_block ({self.kv_block}) — block tables are "
                    f"max_seq_len / kv_block entries wide"
                )
            if any(b % self.kv_block for b in self.prompt_buckets):
                raise ValueError(
                    f"every prompt_buckets entry ({self.prompt_buckets}) "
                    f"must be a multiple of kv_block ({self.kv_block}) — "
                    f"warm prefix widths are bucketed and must cover whole "
                    f"pool blocks"
                )
            if self.chunked_prefill and self.prefill_chunk % self.kv_block:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"multiple of kv_block ({self.kv_block}) under paged_kv "
                    f"so chunk boundaries append whole pool blocks"
                )
            if self.kv_pool_blocks and self.kv_pool_blocks < 2:
                raise ValueError(
                    f"kv_pool_blocks ({self.kv_pool_blocks}) must be >= 2 "
                    f"(1 reserved trash block + 1 usable) or 0 for the "
                    f"dense-equivalent budget"
                )
        if self.spec_decode:
            if not self.paged_kv:
                raise ValueError(
                    "spec_decode=True requires paged_kv=True — rollback "
                    "after a rejected draft is a host-side block-table "
                    "trim, which only the paged engine supports"
                )
            if not pow2(self.spec_k):
                raise ValueError(
                    f"spec_k ({self.spec_k}) must be a power of two — "
                    f"verify variants compile one rung per pow2 k, and "
                    f"the pilot walks that ladder"
                )
        if self.tp < 1:
            raise ValueError(
                f"tp ({self.tp}) must be >= 1 (1 = no tensor parallelism)"
            )
        if self.default_deadline_ms < 0:
            raise ValueError(
                f"default_deadline_ms ({self.default_deadline_ms}) must be "
                f">= 0 (0 disables the default TTL)"
            )
        if self.max_queue < 0:
            raise ValueError(
                f"max_queue ({self.max_queue}) must be >= 0 (0 leaves the "
                f"admission queue unbounded)"
            )
        if self.heal_max_retries < 1:
            raise ValueError(
                f"heal_max_retries ({self.heal_max_retries}) must be >= 1 "
                f"— a request must be allowed at least one resurrection "
                f"or heal can never recover anything"
            )
        if self.heal_watchdog_ms < 0:
            raise ValueError(
                f"heal_watchdog_ms ({self.heal_watchdog_ms}) must be >= 0 "
                f"(0 disables the boundary-fetch watchdog)"
            )


@dataclasses.dataclass
class _Request:
    rid: int
    tokens: List[int]
    params: SamplingParams
    out: "queue.Queue[Optional[dict]]"
    submitted_at: float
    first_token_at: Optional[float] = None
    n_generated: int = 0
    slot: int = -1
    # Host-side upper bound of tokens produced by dispatched-but-unread
    # chunks (admission token + decode_chunk per dispatched chunk) —
    # drives optimistic slot recycling; the device's `remaining` counter
    # guarantees the row really is frozen once the budget is spent.
    expected: int = 0
    finished: bool = False
    # Prefix-cache state: match length (None until looked up; multiple of
    # prefix_block) and the pinned trie path, held until _complete so a
    # live slot's prefix can never be evicted.
    prefix_len: Optional[int] = None
    prefix_handle: Any = None
    # Chunked-prefill state: prompt tokens whose KV is already resident in
    # the slot cache (prefix-cache hit + dispatched chunks), and whether
    # the request is still mid-prefill (holds a slot, but decode rosters
    # must skip it — no tokens exist yet and device `active` is False).
    prefill_done: int = 0
    prefilling: bool = False
    # Paged-KV state: every pool block this request's table row points
    # at — owned and zero-copy-shared alike each carry one allocator ref
    # taken at admission/growth, so release is a uniform unref sweep.
    block_ids: List[int] = dataclasses.field(default_factory=list)
    # Speculative-decoding / graftheal state: every token emitted so
    # far, in order — the drafter's history source and the heal
    # supervisor's replay source. Only populated when spec_decode or
    # heal is on; otherwise the engine never appends.
    gen_hist: List[int] = dataclasses.field(default_factory=list)
    # graftheal: how many gen_hist tokens have been folded into
    # `tokens` by resurrection replays. The drafter's history is
    # tokens + gen_hist[replayed:]; n_generated counts tokens since the
    # CURRENT admission, so replayed + n_generated is the client-
    # delivered total.
    replayed: int = 0
    # Observability: when the scheduler first dispatched work for this
    # request (queue-wait = first_dispatch_at - submitted_at) and when its
    # latest token burst was emitted (drives the ITL histogram).
    first_dispatch_at: Optional[float] = None
    last_burst_at: Optional[float] = None
    # Passes dispatched for this request (ModelConfig.gen_block: the
    # tokens in flight follow from them, slot_rules.tokens_after).
    passes: int = 0
    # TTFT from inside: the five instants received_at <= submitted_at <=
    # first_dispatch_at <= admit_ready_at <= first_token_at cut a
    # request's first-token time into executor_wait, queue_wait,
    # device_wait and first_token_held (docs/distributed-tracing.md).
    # received_at: the transport handler had parsed the request
    # (SamplingParams.received_at; == submitted_at for a direct caller).
    # admit_ready_at: the admission's first-token array reached the host
    # (stamped where it is fetched; a chunked prefill's final chunk).
    # waves_ahead: waves dispatched and not yet retired at first
    # dispatch, i.e. what the device still had to run first. A
    # resurrected request keeps its first set, like its TTFT sample.
    received_at: float = 0.0
    admit_ready_at: Optional[float] = None
    waves_ahead: int = 0
    # Lifecycle: absolute deadline (perf_counter seconds, None = no TTL)
    # and the cancel flag — set from any thread (a GIL-atomic bool
    # store), acted on by the scheduler at the next boundary reap.
    deadline: Optional[float] = None
    cancelled: bool = False
    # Tracing: the adopted caller SpanContext (parsed once at submit;
    # None when tracing is off or no traceparent arrived) and the
    # terminal outcome kind, stamped by _fail_req ("" at _complete =
    # normal completion). Lifecycle spans are emitted retroactively at
    # terminal time from the timestamps above, so the hot path never
    # carries open span objects.
    trace: Any = None
    outcome: str = ""


class _PendingWave(NamedTuple):
    """One dispatched-but-unfetched boundary: the admission groups, the
    decode-chunk device handles, the slot->request roster snapshot, the
    DISPATCH_TIMING token, and the device-state epoch the wave was
    dispatched against. Named so the failure paths (_fail_all /
    _shutdown_sweep) read fields by name — the next timing-tuple growth
    can't silently misalign failure accounting. Still iterable, so
    `_process_boundary(*pending)` is unchanged.

    `epoch` exists for graftheal: a wave dispatched before a fault's
    device-state rebuild must be DISCARDED if it surfaces afterwards —
    its roster references pre-rebuild slots, and delivering its tokens
    to a resurrected (unfinished) request would double them. Pre-heal
    this race was benign because every wrecked request was terminally
    failed; resurrection makes staleness load-bearing."""

    admits: List[Tuple[List["_Request"], Any, Any, Any]]
    chunk_handles: Any
    roster: Optional[List[Optional["_Request"]]]
    timing: Any
    epoch: int = 0


# How far _loop_async may run ahead of the device: waves dispatched and
# not yet retired. Two is one wave running and one queued behind it,
# without which the device idles for a host turn after every wave. Five
# is what the fixed bound it replaces allowed (a fetch queue of four
# plus the wave the fetcher held), so no engine is deeper than it was.
_DEPTH_MIN = 2
_DEPTH_MAX = 5
# The queued waves cover this many mean host turns: a turn twice as
# slow as the mean (an admission group to build, a collection, a
# descheduled thread) still finds the device with a wave to start.
_DEPTH_MARGIN = 2.0
# The means weigh the last ~8 samples; the depth stays at _DEPTH_MIN
# until each has as many.
_DEPTH_SAMPLES = 8


# A wave dispatched while slots are free lasts at least this many host
# turns, and is no longer than that needs (_chunk_steps). Not below
# _DEPTH_MARGIN: a wave of _CHUNK_COVER turns is a period of as many,
# so _pipeline_depth, which reads the same waves and turns, still asks
# for _DEPTH_MIN; a shorter chunk is never paid for with one more wave
# dispatched ahead of every arrival. On the chip the turn's median is
# 1.7-2.1 ms (its mean, 2.6, holds the first dispatches): 4.5 of them
# are 7.5-9.5 ms, between the two steps of 5.7 ms that it keeps
# together and the one step of 11 ms that it lets run alone, with a
# fifth or more to spare on every side (PERF.md section 6, PR 47).
_CHUNK_COVER = 4.5


def _chunk_steps(step_s: float, turn_s: float, cap: int) -> int:
    """The fewest decode steps, a power of two and at most `cap`, whose
    wave covers _CHUNK_COVER host turns. A 13.8 ms step behind a 1.9 ms
    turn: 1; a 6.1 ms step: 2; a 3.3 ms step: the cap of 4."""
    n = 1
    while n < cap and n * step_s < _CHUNK_COVER * turn_s:
        n *= 2
    return min(n, cap)


def _pipeline_depth(period_s: float, turn_s: float) -> int:
    """The smallest D in [_DEPTH_MIN, _DEPTH_MAX] whose D - 1 queued
    wave periods cover _DEPTH_MARGIN host turns. An 81 ms wave behind a
    5 ms turn: 2; a 28 ms wave behind a 100 ms round trip: 5."""
    if period_s <= 0.0:
        return _DEPTH_MAX
    need = 1 + math.ceil(_DEPTH_MARGIN * turn_s / period_s)
    return max(_DEPTH_MIN, min(_DEPTH_MAX, need))


class _RunningMean:
    """Arithmetic mean of the first _DEPTH_SAMPLES samples, then an
    exponential one of that weight."""

    def __init__(self):
        self.value = 0.0
        self.n = 0

    def add(self, x: float) -> None:
        self.n += 1
        self.value += (x - self.value) / min(self.n, _DEPTH_SAMPLES)


class _DepthEstimator:
    """What the async scheduler observes of its own pipeline, and the
    depth that follows from it (_pipeline_depth). Every method runs
    under the engine's _book.

    wave period: the interval between two consecutive waves' results
    reaching the host while a further wave was already dispatched, i.e.
    the device's time for one wave, admissions included.
    host turn: a wave's results on the host -> the dispatch that its
    retirement let through returned (fetch processing, wake-up,
    _dispatch_once), plus what a device_get costs when the device had
    finished before it was called (the transfer, not the wait).
    step: the period of a wave that carried no admission, over its
    decode steps. The low rung's length is sized from it and the turn
    (chunk_steps), once, so from the medians of the last few dozen
    samples and not from the means: the first dispatch of a variant
    (a compile, or a read of the compile cache) is a turn of seconds
    and leaves the device dry for a step as long, a warm-up is full of
    them, and a mean remembers one for eight waves."""

    def __init__(self):
        self.period = _RunningMean()
        self.turn = _RunningMean()
        self.steps: Deque[float] = collections.deque(
            maxlen=4 * _DEPTH_SAMPLES)
        self.turns: Deque[float] = collections.deque(
            maxlen=4 * _DEPTH_SAMPLES)
        self.transfer = _RunningMean()
        self.fetched_at: Optional[float] = None  # the last retired wave's
        self._paced_from: Optional[float] = None

    def depth(self) -> int:
        if min(self.period.n, self.turn.n) < _DEPTH_SAMPLES:
            return _DEPTH_MIN
        return _pipeline_depth(self.period.value, self.host_turn_s())

    def chunk_steps(self, cap: int) -> Optional[int]:
        """The low rung's length (_chunk_steps), None until the step
        and the turn have their samples."""
        if min(len(self.steps), len(self.turns)) < self.steps.maxlen:
            return None
        return _chunk_steps(self.step_s(), self.typical_turn_s(), cap)

    def step_s(self) -> float:
        return statistics.median(self.steps) if self.steps else 0.0

    def typical_turn_s(self) -> float:
        return ((statistics.median(self.turns) if self.turns else 0.0)
                + self.transfer.value)

    def host_turn_s(self) -> float:
        return self.turn.value + self.transfer.value

    def note_retire(self, fetched_at: Optional[float], get_s: Optional[float],
                    more_in_flight: bool, steps: int = 0) -> None:
        """One wave left the registry. `fetched_at`: when its results
        reached the host, None for a wave dropped unread (stale epoch,
        fault), which says nothing of the device's pace. `get_s`: what
        its device_get took if the device had already finished.
        `steps`: its decode steps if they were all it ran, else 0."""
        if fetched_at is None:
            self._paced_from = None
            return
        if self._paced_from is not None:
            self.period.add(fetched_at - self._paced_from)
            if steps:
                self.steps.append((fetched_at - self._paced_from) / steps)
        self._paced_from = fetched_at if more_in_flight else None
        self.fetched_at = fetched_at
        if get_s is not None:
            self.transfer.add(get_s)

    def note_turn(self, dispatched_at: float) -> None:
        """The scheduler waited at the bound, a retirement let it
        through and its dispatch returned at `dispatched_at`. A sample
        is clipped at the turn that already asks for _DEPTH_MAX: a
        compile of seconds counts as "deep" for a few waves, not for
        minutes."""
        if self.fetched_at is None:
            return
        turn = dispatched_at - self.fetched_at
        if self.period.n:
            turn = min(turn, (_DEPTH_MAX - 1) * self.period.value
                       / _DEPTH_MARGIN)
        self.turn.add(turn)
        self.turns.append(turn)

    def gauges(self) -> Dict[str, float]:
        return {
            "depth": self.depth(),
            "wave_period_ms": 1000.0 * self.period.value,
            "host_turn_ms": 1000.0 * self.host_turn_s(),
        }


# EngineStats' counters that a decode chunk counts on the device, in the
# order of its fifth value: the sampler's tiers (every model), what the
# attention layers read of the dense slab (every model's dense chunk;
# the paged chunk and the waves stop at the sampler's), then what
# routing did (a model that dispatches tokens to experts).
SAMPLER_COUNTERS = ("sampler_steps", "sampler_drawn_steps",
                    "sampler_masked_steps")
# ... and the K rows a step wrote beside slots x attention layers, what
# a scatter over every slot writes (equal where the scatter stands).
KV_COUNTERS = ("attn_kv_tokens_read", "attn_kv_tokens_held",
               "attn_kv_rows_written", "attn_kv_rows_slots")
# ... of a stack whose attention kinds differ (sliding_attention layers
# beside full ones), right after those four, which stay the sums over both
# kinds (transformer.decode_kv_counts): what the window layers read and
# hold (their rings), what their live rows would have read without a
# window (their positions), and what the full layers read and hold.
WINDOW_COUNTERS = ("attn_window_tokens_read", "attn_window_tokens_held",
                   "attn_window_tokens_unwindowed", "attn_full_tokens_read",
                   "attn_full_tokens_held")
MOE_COUNTERS = ("moe_sparse_layer_steps", "moe_experts_touched",
                "moe_assignments")
# ... and, after them, of a stack that holds a share of its experts or
# has Mamba-2 layers (transformer.routing_width): the assignments that
# went to experts held here, and the Mamba-2 layers run.
SHARE_COUNTERS = ("moe_assignments_held", "ssm_layer_steps")
CHUNK_COUNTERS = (SAMPLER_COUNTERS + KV_COUNTERS + MOE_COUNTERS
                  + SHARE_COUNTERS)
# ... of a model that generates by diffusion over blocks
# (ModelConfig.gen_block; 0 elsewhere), right after the sampler's: a
# decode step is a pass, and these are the (slot, pass) pairs that ran,
# those of them that committed a block, and the tokens those emitted
# (slot_rules.block_step). tokens / slot passes is what a slot's pass
# yields: gen_block / (denoise_steps + 1) less tails and cuts.
# The fourth is the rows the head scored: 0, SCORED_SLOTS x gen_block or
# slots x gen_block a pass, by how many slots held an undecided position.
DIFF_COUNTERS = ("diff_slot_passes", "diff_commit_passes", "diff_tokens_out",
                 "diff_rows_scored")


def chunk_counter_names(cfg) -> Tuple[str, ...]:
    """The names of a decode chunk's counts (_chunk_impl's fifth value)
    for this model, in their order."""
    names = SAMPLER_COUNTERS
    if cfg.gen_block:
        names += DIFF_COUNTERS
    names += KV_COUNTERS
    if cfg.n_window_layers:
        names += WINDOW_COUNTERS
    if InferenceEngine._counts_routing(cfg):
        names += (MOE_COUNTERS + SHARE_COUNTERS)[
            :transformer.routing_width(cfg)]
    return names


class EngineStats:
    def __init__(self):
        # Guards every mutable counter below. The scheduler thread, the
        # boundary fetcher and submit() all bump counters concurrently;
        # graftlint's lock-guard pass enforces the `with self.lock:`
        # discipline tree-wide via the guarded-by annotations.
        self.lock = threading.Lock()
        self.requests = 0  # graftlint: guarded-by(lock) via(stats)
        self.completed = 0  # graftlint: guarded-by(lock) via(stats)
        # Terminal outcomes other than a normal completion (every typed
        # error kind); completed counts these too.
        self.failed_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.tokens_out = 0  # graftlint: guarded-by(lock) via(stats)
        self.ttft_sum = 0.0  # graftlint: guarded-by(lock) via(stats)
        self.ttft_count = 0  # graftlint: guarded-by(lock) via(stats)
        # Scheduler observability: decode dispatches and total steps
        # dispatched — their ratio is the effective (adaptive) chunk
        # length, the knob the occupancy policy is turning.
        self.decode_dispatches = 0  # graftlint: guarded-by(lock) via(stats)
        self.decode_steps = 0  # graftlint: guarded-by(lock) via(stats)
        # The tier the sampler took in decode (_note_chunk_counts;
        # models/sampling.tier, decided on the device per step): steps
        # whose tier a chunk reported, those in which some live row
        # sampled (the batch divided and drew Gumbel noise), and those
        # of them in which a sampling row asked for top-k / top-p (the
        # batch sorted the vocabulary). steps - drawn were an argmax.
        self.sampler_steps = 0  # graftlint: guarded-by(lock) via(stats)
        self.sampler_drawn_steps = 0  # graftlint: guarded-by(lock) via(stats)
        self.sampler_masked_steps = 0  # graftlint: guarded-by(lock) via(stats)
        # What decode attention read of the dense slab
        # (transformer.decode_kv_counts, summed over decode steps): KV
        # tokens the attention layers fetched, and KV tokens the slab
        # holds for them (slots x window x attention layers). read /
        # held is the share of the slab a step touches: 1 where the
        # einsums score every slot's whole window, the live rows' share
        # where ops/decode_attention reads them alone.
        self.attn_kv_tokens_read = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_kv_tokens_held = 0  # graftlint: guarded-by(lock) via(stats)
        # K rows the decode steps wrote over all attention layers (slab
        # and rings), and slots x those layers: written / slots is 1
        # where the step scatters a row of every slot, the live slots'
        # share where ops/decode_attention writes them alone.
        self.attn_kv_rows_written = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_kv_rows_slots = 0  # graftlint: guarded-by(lock) via(stats)
        # The same by attention kind, for a stack with sliding_attention
        # layers (WINDOW_COUNTERS; 0 elsewhere): read / unwindowed on the
        # window layers is what the window saves a decode step.
        self.attn_window_tokens_read = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_window_tokens_held = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_window_tokens_unwindowed = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_full_tokens_read = 0  # graftlint: guarded-by(lock) via(stats)
        self.attn_full_tokens_held = 0  # graftlint: guarded-by(lock) via(stats)
        # Passes of a model that generates by diffusion over blocks
        # (DIFF_COUNTERS; 0 elsewhere).
        self.diff_slot_passes = 0  # graftlint: guarded-by(lock) via(stats)
        self.diff_commit_passes = 0  # graftlint: guarded-by(lock) via(stats)
        self.diff_tokens_out = 0  # graftlint: guarded-by(lock) via(stats)
        self.diff_rows_scored = 0  # graftlint: guarded-by(lock) via(stats)
        # Prompt tokens admitted, by the bucket their admission group was
        # padded to ({bucket: tokens}; the cold dense admission).
        self.attn_prefill_tokens: Dict[int, int] = {}  # graftlint: guarded-by(lock) via(stats)
        # What routing did in decode (models that dispatch tokens to
        # experts; _note_chunk_counts): sparse layers run over all decode
        # steps, distinct experts those layers read for live rows
        # (summed), (row, expert) assignments. touched / layer-steps is
        # the mean number of experts a sparse layer reads per step.
        self.moe_sparse_layer_steps = 0  # graftlint: guarded-by(lock) via(stats)
        self.moe_experts_touched = 0  # graftlint: guarded-by(lock) via(stats)
        self.moe_assignments = 0  # graftlint: guarded-by(lock) via(stats)
        # A program that holds a share of its routed experts: how many
        # of moe_assignments went to experts held here (held /
        # assignments = the share, if routing is even). And the Mamba-2
        # layers run over all decode steps.
        self.moe_assignments_held = 0  # graftlint: guarded-by(lock) via(stats)
        self.ssm_layer_steps = 0  # graftlint: guarded-by(lock) via(stats)
        # Prefix-cache observability: admissions that reused cached KV,
        # prompt tokens whose prefill was skipped, and trie nodes evicted
        # under the byte budget.
        self.prefix_hits = 0  # graftlint: guarded-by(lock) via(stats)
        self.prefix_tokens_saved = 0  # graftlint: guarded-by(lock) via(stats)
        self.prefix_evictions = 0  # graftlint: guarded-by(lock) via(stats)
        # Admission-queue observability: depth sampled at each dispatch,
        # and submit -> first-dispatch wait per request.
        self.queue_depth = 0  # graftlint: guarded-by(lock) via(stats)
        self.queue_wait_sum = 0.0  # graftlint: guarded-by(lock) via(stats)
        self.queue_wait_count = 0  # graftlint: guarded-by(lock) via(stats)
        # The other phases of a first token (seconds; _Request's five
        # instants), each booked where its closing instant is stamped,
        # beside a count that is already kept there: executor wait at
        # submit (`requests`), waves ahead at first dispatch
        # (`queue_wait_count`), device wait and first-token hold at the
        # first token (`ttft_count`).
        self.executor_wait_sum = 0.0  # graftlint: guarded-by(lock) via(stats)
        self.waves_ahead_sum = 0  # graftlint: guarded-by(lock) via(stats)
        self.device_wait_sum = 0.0  # graftlint: guarded-by(lock) via(stats)
        self.first_token_held_sum = 0.0  # graftlint: guarded-by(lock) via(stats)
        # Inter-token latency histogram (ms, per decode-chunk burst gap).
        # Fixed edges keep the lock hold O(buckets) and make prometheus
        # export trivial; quantiles read the bucket upper edge.
        self.itl_edges_ms = (2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                             500.0, 1000.0)
        self.itl_counts = [0] * (len(self.itl_edges_ms) + 1)  # graftlint: guarded-by(lock) via(stats)
        self.itl_sum_ms = 0.0  # graftlint: guarded-by(lock) via(stats)
        # Chunked-prefill observability: chunks dispatched, prompt tokens
        # they covered, and how full the per-dispatch token budget ran
        # (budget_tokens / (budget_dispatches * budget) = utilization).
        self.prefill_chunks = 0  # graftlint: guarded-by(lock) via(stats)
        self.prefill_chunk_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.budget_dispatches = 0  # graftlint: guarded-by(lock) via(stats)
        self.budget_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.budget_limit = 0  # graftlint: guarded-by(lock) via(stats)
        # Paged-KV observability: admissions whose warm prefix was shared
        # by refcount alone (no device KV traffic), copy-on-write block
        # copies, admissions stalled on pool exhaustion, streams preempted
        # to free blocks for an active decoder, and — for contrast — warm
        # admissions that DID move prefix KV through the device (dense
        # gather/seed paths; provably zero in paged mode).
        self.zero_copy_admissions = 0  # graftlint: guarded-by(lock) via(stats)
        self.cow_copies = 0  # graftlint: guarded-by(lock) via(stats)
        self.pool_stalls = 0  # graftlint: guarded-by(lock) via(stats)
        self.preemptions = 0  # graftlint: guarded-by(lock) via(stats)
        self.prefix_seed_copies = 0  # graftlint: guarded-by(lock) via(stats)
        # Set by the paged engine to the allocator's snapshot() — merged
        # into snapshot() as pool_blocks_* gauges (zeros when dense, so
        # the prometheus surface is unconditional).
        self.pool_gauges = None  # graftlint: guarded-by(lock) via(stats)
        # Lifecycle observability: requests shed before admission
        # (overload rejects, drain, queued deadline/cancel), cancels
        # honored (queued or in-flight), deadline expiries (queued or
        # in-flight), and submits bounced off the max_queue bound.
        self.shed_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.cancelled_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.deadline_expired_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.queue_rejects = 0  # graftlint: guarded-by(lock) via(stats)
        # SLO attainment: per-request deadline margin at terminal time
        # (ms of deadline left; negative = finished/expired late) and
        # goodput — completions that beat their deadline vs deadline-
        # bearing requests that did not (expiries, cancels, late
        # completions) vs requests that carried no deadline at all.
        # Same fixed-edge idiom as the ITL histogram.
        self.deadline_margin_edges_ms = (
            -1000.0, -500.0, -200.0, -100.0, -50.0, -20.0, 0.0,
            20.0, 50.0, 100.0, 200.0, 500.0, 1000.0,
        )
        self.deadline_margin_counts = [0] * (
            len(self.deadline_margin_edges_ms) + 1
        )  # graftlint: guarded-by(lock) via(stats)
        self.deadline_margin_sum_ms = 0.0  # graftlint: guarded-by(lock) via(stats)
        self.deadline_met_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.deadline_missed_total = 0  # graftlint: guarded-by(lock) via(stats)
        self.completed_no_deadline_total = 0  # graftlint: guarded-by(lock) via(stats)
        # Per-variant dispatch timing (DISPATCH_TIMING=1; empty dict —
        # and no record_variant_locked calls — otherwise). Keyed by the
        # compile-ledger variant string ("admit/64/4"); duration is the
        # boundary-level host wall time measured at the deliberate
        # device_get sync, bucketed on the same fixed-edge idiom as ITL.
        self.dispatch_edges_ms = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                                  100.0, 200.0, 500.0)
        self.variant_ms = {}  # graftlint: guarded-by(lock) via(stats)
        # Scheduler-waste observability (SCHED_LEDGER=1; all stay zero
        # — and no record_waste_locked calls — otherwise). Token counts
        # mirror the sched ledger's conservation-audited totals; the
        # histogram buckets each dispatched boundary's padding fraction
        # on the same fixed-edge idiom as ITL.
        self.sched_boundaries = 0  # graftlint: guarded-by(lock) via(stats)
        self.sched_idle_boundaries = 0  # graftlint: guarded-by(lock) via(stats)
        self.sched_useful_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.sched_bucket_pad_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.sched_group_pad_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.sched_frag_tokens = 0  # graftlint: guarded-by(lock) via(stats)
        self.waste_edges_frac = (0.01, 0.02, 0.05, 0.10, 0.20, 0.35,
                                 0.50, 0.75)
        self.waste_counts = [0] * (len(self.waste_edges_frac) + 1)  # graftlint: guarded-by(lock) via(stats)

    def record_waste_locked(self, frac: float) -> None:  # graftlint: holds(lock)
        """Caller holds self.lock. One dispatched boundary's padding
        fraction (pad cells / offered cells) from the sched ledger."""
        i = 0
        for edge in self.waste_edges_frac:
            if frac <= edge:
                break
            i += 1
        self.waste_counts[i] += 1
        self.sched_boundaries += 1

    def record_variant_locked(self, key: str, ms: float) -> None:  # graftlint: holds(lock)
        """Caller holds self.lock. One boundary duration for `key`."""
        h = self.variant_ms.get(key)
        if h is None:
            h = {"count": 0, "sum_ms": 0.0,
                 "counts": [0] * (len(self.dispatch_edges_ms) + 1)}
            self.variant_ms[key] = h
        i = 0
        for edge in self.dispatch_edges_ms:
            if ms <= edge:
                break
            i += 1
        h["counts"][i] += 1
        h["count"] += 1
        h["sum_ms"] += ms

    def record_slo_locked(self, margin_ms: Optional[float],  # graftlint: holds(lock)
                          ok: bool) -> None:
        """Caller holds self.lock. margin_ms None = the request carried
        no deadline; ok = the terminal outcome was a normal completion.
        Goodput counts a deadline-bearing request as met only when it
        completed normally with margin to spare."""
        if margin_ms is None:
            if ok:
                self.completed_no_deadline_total += 1
            return
        i = 0
        for edge in self.deadline_margin_edges_ms:
            if margin_ms <= edge:
                break
            i += 1
        self.deadline_margin_counts[i] += 1
        self.deadline_margin_sum_ms += margin_ms
        if ok and margin_ms >= 0.0:
            self.deadline_met_total += 1
        else:
            self.deadline_missed_total += 1

    def record_itl_locked(self, ms: float) -> None:  # graftlint: holds(lock)
        """Caller holds self.lock."""
        i = 0
        for edge in self.itl_edges_ms:
            if ms <= edge:
                break
            i += 1
        self.itl_counts[i] += 1
        self.itl_sum_ms += ms

    def _itl_quantile_locked(self, q: float) -> float:  # graftlint: holds(lock)
        total = sum(self.itl_counts)
        if not total:
            return 0.0
        target = q * total
        cum = 0
        for i, c in enumerate(self.itl_counts):
            cum += c
            if cum >= target:
                if i < len(self.itl_edges_ms):
                    return self.itl_edges_ms[i]
                return 2.0 * self.itl_edges_ms[-1]  # overflow bucket
        return 2.0 * self.itl_edges_ms[-1]

    def snapshot(self) -> Dict[str, float]:
        with self.lock:
            gauges = self.pool_gauges
        # Called outside the stats lock: the allocator snapshot takes its
        # own lock and must stay a leaf in the lock order.
        pool = (
            gauges() if gauges is not None
            else {"total": 0, "used": 0, "free": 0, "shared": 0}
        )
        with self.lock:
            itl_count = sum(self.itl_counts)
            return {
                "pool_blocks_total": pool["total"],
                "pool_blocks_used": pool["used"],
                "pool_blocks_free": pool["free"],
                "pool_blocks_shared": pool["shared"],
                "zero_copy_admissions": self.zero_copy_admissions,
                "cow_copies": self.cow_copies,
                "pool_stalls": self.pool_stalls,
                "preemptions": self.preemptions,
                "prefix_seed_copies": self.prefix_seed_copies,
                "requests": self.requests,
                "completed": self.completed,
                "failed_total": self.failed_total,
                "tokens_out": self.tokens_out,
                "mean_ttft_ms": (
                    1000.0 * self.ttft_sum / self.ttft_count
                    if self.ttft_count
                    else 0.0
                ),
                "decode_dispatches": self.decode_dispatches,
                "decode_steps": self.decode_steps,
                **{name: getattr(self, name)
                   for name in CHUNK_COUNTERS + WINDOW_COUNTERS
                   + DIFF_COUNTERS},
                "attn_prefill_tokens": dict(self.attn_prefill_tokens),
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "prefix_evictions": self.prefix_evictions,
                "queue_depth": self.queue_depth,
                "mean_queue_wait_ms": (
                    1000.0 * self.queue_wait_sum / self.queue_wait_count
                    if self.queue_wait_count
                    else 0.0
                ),
                # phase -> (sum, count): ms, but waves_ahead in waves.
                "ttft_phases": {
                    "executor_wait_ms": (
                        1000.0 * self.executor_wait_sum, self.requests),
                    "queue_wait_ms": (
                        1000.0 * self.queue_wait_sum,
                        self.queue_wait_count),
                    "device_wait_ms": (
                        1000.0 * self.device_wait_sum, self.ttft_count),
                    "first_token_held_ms": (
                        1000.0 * self.first_token_held_sum,
                        self.ttft_count),
                    "waves_ahead": (
                        self.waves_ahead_sum, self.queue_wait_count),
                },
                "itl_count": itl_count,
                "mean_itl_ms": (
                    self.itl_sum_ms / itl_count if itl_count else 0.0
                ),
                "itl_p50_ms": self._itl_quantile_locked(0.50),
                "itl_p95_ms": self._itl_quantile_locked(0.95),
                "itl_p99_ms": self._itl_quantile_locked(0.99),
                "prefill_chunks": self.prefill_chunks,
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "budget_utilization": (
                    self.budget_tokens
                    / (self.budget_dispatches * self.budget_limit)
                    if self.budget_dispatches and self.budget_limit
                    else 0.0
                ),
                "shed_total": self.shed_total,
                "cancelled_total": self.cancelled_total,
                "deadline_expired_total": self.deadline_expired_total,
                "queue_rejects": self.queue_rejects,
                "deadline_margin_edges_ms": list(
                    self.deadline_margin_edges_ms
                ),
                "deadline_margin_counts": list(self.deadline_margin_counts),
                "deadline_margin_sum_ms": self.deadline_margin_sum_ms,
                "deadline_met_total": self.deadline_met_total,
                "deadline_missed_total": self.deadline_missed_total,
                "completed_no_deadline_total":
                    self.completed_no_deadline_total,
                "goodput": (
                    self.deadline_met_total
                    / (self.deadline_met_total + self.deadline_missed_total)
                    if (self.deadline_met_total + self.deadline_missed_total)
                    else 1.0
                ),
                "sched_boundaries": self.sched_boundaries,
                "sched_idle_boundaries": self.sched_idle_boundaries,
                "sched_useful_tokens": self.sched_useful_tokens,
                "sched_bucket_pad_tokens": self.sched_bucket_pad_tokens,
                "sched_group_pad_tokens": self.sched_group_pad_tokens,
                "sched_frag_tokens": self.sched_frag_tokens,
                "padding_waste_frac": (
                    (self.sched_bucket_pad_tokens
                     + self.sched_group_pad_tokens)
                    / (self.sched_useful_tokens
                       + self.sched_bucket_pad_tokens
                       + self.sched_group_pad_tokens)
                    if (self.sched_useful_tokens
                        + self.sched_bucket_pad_tokens
                        + self.sched_group_pad_tokens)
                    else 0.0
                ),
                "waste_edges_frac": list(self.waste_edges_frac),
                "waste_counts": list(self.waste_counts),
                "dispatch_edges_ms": list(self.dispatch_edges_ms),
                "variant_timing": {
                    k: {"count": h["count"], "sum_ms": h["sum_ms"],
                        "counts": list(h["counts"])}
                    for k, h in self.variant_ms.items()
                },
            }


class InferenceEngine:
    """Slot-based continuous batching over a single sharded model."""

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        engine_cfg: Optional[EngineConfig] = None,
        mesh=None,
        draft: Optional[Tuple[Any, ModelConfig]] = None,
    ):
        self.cfg = cfg.validate()
        self.ecfg = engine_cfg or EngineConfig()
        self.params = params
        self.mesh = mesh
        # graftmesh: exact tensor parallelism (EngineConfig.tp > 1;
        # models/tp_sharding.py). The gate is the CONFIG field, never
        # the mesh shape — multi-process slice serving already passes a
        # Megatron-sharded mesh here with the default config and must
        # stay byte-identical. With tp > 1 the weights commit onto the
        # mesh under the exact-TP table and self._tp threads sharding
        # constraints through every jitted impl below; tp=1 leaves
        # self._tp None and every partial without the kwarg.
        self._tp = None
        self._refuse_unpatterned_paths()
        if self.ecfg.tp > 1:
            tp_sharding.validate(self.cfg, self.ecfg.tp)
            if self.cfg.attn_impl in ("flash", "ring"):
                raise ValueError(
                    f"tp={self.ecfg.tp} is not supported with "
                    f"attn_impl={self.cfg.attn_impl!r} — only the gqa "
                    f"attention family is tp-threaded"
                )
            self._tp = tp_sharding.hints(mesh, self.ecfg.tp)
            self.params = tp_sharding.shard_params(mesh, self.cfg, params)
        B = self.ecfg.max_slots

        # Prompt buckets clamped to the cache window (empty -> whole window).
        Smax = self.ecfg.max_seq_len
        self._buckets = tuple(
            b for b in self.ecfg.prompt_buckets if b <= Smax
        ) or (Smax,)
        self._refuse_gen_block_shapes()

        # Paged KV cache (opt-in, single-process only — the block
        # allocator and tables are host-side state, and multi-process
        # SPMD dispatch decisions must be identical on every host). When
        # enabled, state["cache"] holds one global block pool
        # [L, NB, Hkv, kv_block, (Dh)] instead of the per-slot slab, and
        # every dispatch site branches to a paged twin that reads/writes
        # KV through per-slot int32 block tables. paged_kv=False leaves
        # every dense code path byte-identical.
        self._paged = bool(self.ecfg.paged_kv)
        if self._paged and jax.process_count() > 1:
            logger.warning(
                "paged_kv disabled: host-side block allocator requires a "
                "single-process mesh"
            )
            self._paged = False
        self._paged_prefix = None
        if self._paged:
            from seldon_tpu.servers.block_pool import BlockAllocator

            self._kv_block = self.ecfg.kv_block
            self._nbs = Smax // self._kv_block  # block-table width
            # Default pool: the dense slab's exact token budget
            # (B * Smax tokens) plus the reserved trash block — same HBM,
            # but blocks only bind to streams as they are written.
            self._num_blocks = (
                self.ecfg.kv_pool_blocks or B * self._nbs + 1
            )
            self._allocator = BlockAllocator(self._num_blocks)
            self._table_host = np.zeros((B, self._nbs), np.int32)  # graftlint: guarded-by(_book)

        self._state = self._fresh_state()
        self._active_host = np.zeros((B,), bool)  # control-flow mirror  # graftlint: guarded-by(_book)
        # Serializes slot/free-list/active bookkeeping between the
        # scheduler thread and the boundary-fetcher thread.
        self._book = threading.Lock()
        self._async_fetch = (
            self.ecfg.async_fetch and jax.process_count() == 1
        )
        # Scheduler -> fetcher hand-off. Unbounded: the one bound on how
        # far the scheduler runs ahead is the depth of _inflight_waves
        # (_loop_async).
        self._fetch_q: "queue.Queue" = queue.Queue()
        self._fetcher: Optional[threading.Thread] = None
        self._dispatch_wreck = None  # partial boundary for error paths  # graftlint: guarded-by(_book)
        # Bumped by every device-state rebuild; waves dispatched against
        # an older epoch are discarded at fetch time (see _PendingWave).
        self._wave_epoch = 0  # graftlint: guarded-by(_book)
        # Every dispatched-but-unretired wave, registered under _book at
        # dispatch time and retired under _book by the fetcher (after
        # processing OR after an epoch-stale discard). Requests
        # optimistically recycled out of _slots live ONLY in their
        # wave's roster, and a wave is invisible to _fetch_q scavenging
        # twice per boundary: between dispatch and the (bounded,
        # lock-free) put, and between the fetcher's get and its epoch
        # check. This registry is therefore the authoritative gather
        # source for wave-fault recovery — _gather_wrecked walks it
        # instead of draining the queue, which raced the scheduler's
        # puts and stranded whole waves (epoch-discarded unread, their
        # requests in no book).
        self._inflight_waves: List[_PendingWave] = []  # graftlint: guarded-by(_book)
        # The async scheduler holds at most _depth_est.depth() waves in
        # that registry and waits on _room otherwise: cleared by the
        # scheduler and set by _wave_retire, both under _book, and set
        # by stop().
        self._depth_est = _DepthEstimator()  # graftlint: guarded-by(_book)
        self._room = threading.Event()
        # The synchronous loops keep their one undelivered wave in a
        # local and never register it: 1 while it is outstanding, so
        # _record_first_dispatch reads one depth on every path.
        self._sync_depth = 0  # graftlint: guarded-by(_book)
        # Waves dispatched so far: `wave` on the sched.dispatch span.
        self._wave_seq = 0  # graftlint: guarded-by(_book)

        # Host-side bookkeeping.
        self._slots: List[Optional[_Request]] = [None] * B  # graftlint: guarded-by(_book)
        self._free: List[int] = list(range(B))  # graftlint: guarded-by(_book)
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._waiting: Deque[_Request] = collections.deque()  # graftlint: guarded-by(_book)
        self._rid = 0  # graftlint: guarded-by(_rid_lock)
        self._rid_lock = threading.Lock()
        # rid -> live request, the cancel() routing table (pruned in
        # _complete; shares _rid_lock — both are submit-path touches).
        self._requests: Dict[int, _Request] = {}  # graftlint: guarded-by(_rid_lock)
        self.stats = EngineStats()
        if self._paged:
            self.stats.pool_gauges = self._allocator.snapshot
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Deterministic fault injection (opt-in; ChaosConfig.from_env
        # lets the CHAOS=1 gate enable it without config plumbing).
        chaos_cfg = self.ecfg.chaos or ChaosConfig.from_env()
        self._chaos: Optional[ChaosMonkey] = None
        if chaos_cfg is not None and chaos_cfg.any_enabled():
            self._chaos = ChaosMonkey(chaos_cfg)
            logger.warning("chaos fault injection enabled: %s", chaos_cfg)
        # graftheal supervised recovery (opt-in; supervisor.build also
        # consults the HEAL=1 env gate). None keeps the _fail_all
        # failure path — and every hot path — byte-identical.
        self._heal: Optional[supervisor.HealSupervisor] = \
            supervisor.build(self.ecfg)
        if self._heal is not None:
            logger.warning(
                "graftheal supervised recovery enabled: %s",
                self._heal.describe(),
            )

        # Largest power of two <= min(max_admit, max_slots).
        ma = max(1, min(self.ecfg.max_admit, B))
        self._max_admit = 1 << (ma.bit_length() - 1)
        self._chunk_counters = chunk_counter_names(self.cfg)

        # Context-parallel prefill: with attn_impl=="ring" and a mesh
        # carrying a real 'sp' axis, admissions prefill with the prompt
        # sequence sharded across the ring (long-prompt scaling;
        # transformer.prefill). Decode is untouched (T-unsharded cache).
        self._ring_mesh = (
            mesh
        ) if (
            mesh is not None
            and self.cfg.attn_impl == "ring"
            and dict(mesh.shape).get("sp", 1) > 1
        ) else None
        # Conditional tp kwarg: tp=1 partials carry no extra binding at
        # all, so their jit signatures — and traces — are byte-identical
        # to a build without graftmesh.
        tpkw = {"tp": self._tp} if self._tp is not None else {}
        self._jit_admit = jax.jit(
            _named_partial(self._admit_impl, cfg=self.cfg, mesh=mesh,
                           ring_mesh=self._ring_mesh, **tpkw),
            donate_argnums=(1,),
        )
        # Prefix KV cache (opt-in, single-process only — the trie is
        # host-side state, and multi-process SPMD dispatch decisions must
        # be identical on every host). When enabled, COLD admissions run
        # through a variant that also returns the freshly-computed
        # cache-dtype KV (for trie insertion) and WARM admissions run the
        # suffix-only path; self._jit_admit itself stays untouched, so
        # prefix_cache=False keeps today's admission path byte-identical.
        self._prefix = None
        self._jit_admit_sub = None
        self._jit_admit_prefix = None
        if self.ecfg.prefix_cache and self._paged:
            # Paged engines index BLOCK IDS, not KV copies: warm hits
            # refcount cached blocks straight into the new slot's table
            # (zero-copy); the dense PrefixIndex machinery below (gather,
            # seed, insert-with-KV) never runs, so self._prefix stays
            # None and every `_prefix is not None` dense branch stays off.
            from seldon_tpu.servers.prefix_cache import PagedPrefixIndex

            self._paged_prefix = PagedPrefixIndex(
                block=self.ecfg.prefix_block,
                kv_block=self._kv_block,
                allocator=self._allocator,
            )
        elif self.ecfg.prefix_cache:
            if jax.process_count() > 1:
                logger.warning(
                    "prefix_cache disabled: host-side KV index requires a "
                    "single-process mesh"
                )
            else:
                from seldon_tpu.servers.prefix_cache import PrefixIndex

                self._prefix = PrefixIndex(
                    block=self.ecfg.prefix_block,
                    byte_budget=self.ecfg.prefix_cache_bytes,
                )
                self._jit_admit_sub = jax.jit(
                    _named_partial(
                        self._admit_impl, cfg=self.cfg, mesh=mesh,
                        ring_mesh=self._ring_mesh, return_sub=True, **tpkw,
                    ),
                    donate_argnums=(1,),
                )
                self._jit_admit_prefix = jax.jit(
                    _named_partial(
                        self._admit_prefix_impl, cfg=self.cfg, mesh=mesh,
                        **tpkw,
                    ),
                    donate_argnums=(1,),
                )
        # Chunked prefill (opt-in): chunk lengths are bucketed like
        # prompts (`_chunk_buckets` = prompt-bucket rungs clamped to the
        # chunk, so a short final chunk compiles against a snug shape),
        # and resident-prefix widths reuse the prompt buckets. The chunk
        # kernel is one jit keyed on (G, Sc) + static prefix_width.
        self._chunked = bool(self.ecfg.chunked_prefill)
        self._prefilling: Deque[_Request] = collections.deque()  # graftlint: guarded-by(_book)
        self._jit_admit_chunk = None
        self._jit_seed_prefix = None
        self._jit_admit_chunk_paged = None
        if self._chunked:
            C = min(self.ecfg.prefill_chunk, max(self._buckets))
            self._prefill_chunk = C
            self._chunk_buckets = tuple(sorted(
                {min(b, C) for b in self._buckets} | {C}
            ))
            if self._paged:
                self._jit_admit_chunk_paged = jax.jit(
                    _named_partial(
                        self._paged_admit_chunk_impl, cfg=self.cfg,
                        mesh=mesh, **tpkw,
                    ),
                    static_argnames=("prefix_width",),
                    donate_argnums=(1,),
                )
            else:
                self._jit_admit_chunk = jax.jit(
                    _named_partial(
                        self._admit_chunk_impl, cfg=self.cfg, mesh=mesh,
                        return_sub=self._prefix is not None, **tpkw,
                    ),
                    static_argnames=("prefix_width",),
                    donate_argnums=(1,),
                )
            if self._prefix is not None:
                self._jit_seed_prefix = jax.jit(
                    self._seed_prefix_impl, donate_argnums=(0,)
                )
        # Paged dispatch twins: one-shot admission (cold AND warm — the
        # static prefix_width keys the variant, 0 = cold), the block-
        # table decode chunk ladder, and the copy-on-write block copy.
        # The block table is passed as a fresh device array per dispatch
        # (never donated); the pool itself lives inside the donated state.
        self._jit_admit_paged = None
        self._jit_chunks_paged = None
        self._jit_cow = None
        if self._paged:
            self._jit_admit_paged = jax.jit(
                _named_partial(
                    self._paged_admit_impl, cfg=self.cfg, mesh=mesh,
                    **tpkw,
                ),
                static_argnames=("prefix_width",),
                donate_argnums=(1,),
            )
            self._jit_cow = jax.jit(
                self._cow_copy_impl, donate_argnums=(0,)
            )
        # Chunk-length ladder: exactly the three rungs the policy uses
        # (min / geometric mid / top) — every rung costs a full chunk
        # compile, so no speculative intermediates.
        # adaptive_chunk=False keeps the single fixed length.
        top = max(1, self.ecfg.decode_chunk)
        if self.ecfg.adaptive_chunk and top > self.ecfg.min_chunk:
            lo = max(1, min(self.ecfg.min_chunk, top))
            mid = 1 << int(round((lo * top) ** 0.5)).bit_length() - 1
            sizes = [lo, mid, top]
        else:
            sizes = [top]
        # Rebound whole, by the scheduler under _book: a reader outside
        # the lock sees one ladder or the other.
        self._chunk_sizes = tuple(sorted(set(sizes)))
        # The low rung starts at min_chunk and is sized once, when the
        # depth estimator has its samples (_size_low_rung): a single
        # fixed length has no low rung to size.
        self._rung_sized = len(self._chunk_sizes) == 1  # graftlint: guarded-by(_book)

        def chunk_jit(impl, n):
            return jax.jit(
                _named_partial(impl, cfg=self.cfg, n_steps=n, mesh=mesh,
                               **tpkw),
                donate_argnums=(1,),
            )

        self._chunk_jit = chunk_jit
        self._jit_chunks = {
            n: chunk_jit(self._chunk_impl, n) for n in self._chunk_sizes
        }
        if self._paged:
            self._jit_chunks_paged = {
                n: chunk_jit(self._paged_chunk_impl, n)
                for n in self._chunk_sizes
            }
        # Lifecycle reaping: one masked write freezes cancelled/expired
        # rows. Dispatched ONLY when a reap actually removed a slot, so
        # engines that never see a cancel/deadline keep their dispatch
        # sequence byte-identical.
        self._jit_deactivate = jax.jit(
            self._deactivate_impl, donate_argnums=(0,)
        )
        # graftspec (opt-in): speculative decoding. Each boundary a
        # drafter proposes up to spec_k tokens per live decode slot and
        # ONE wide verify dispatch (models/spec_decode.verify_wave)
        # scores all k+1 positions against the paged pool — the decode
        # chunk ladder never dispatches; ("verify", k) rungs replace it
        # in the lattice. Verification is exact-match against the
        # target's own sequentially-keyed samples, so output streams
        # are bit-identical to spec-off at ANY temperature. Requires
        # the paged engine (validated in EngineConfig); inherits its
        # single-process restriction through self._paged. The loop runs
        # synchronously (process-before-next-dispatch) because rollback
        # must trim block-table tails before the next wave sizes its
        # block growth.
        self._spec = bool(self.ecfg.spec_decode) and self._paged
        if self.ecfg.spec_decode and not self._spec:
            logger.warning(
                "spec_decode disabled: the paged engine it rides on was "
                "disabled (multi-process mesh)"
            )
        self._jit_verify = None
        self._jit_draft = None
        self._drafter = None
        if self._spec:
            from seldon_tpu.servers import spec_decode as spec_host

            self._async_fetch = False
            # Pow2 k ladder 1..spec_k: one verify compile per rung, and
            # the pilot's spec_k knob walks rung-to-rung.
            self._spec_rungs = tuple(
                1 << i for i in range(self.ecfg.spec_k.bit_length())
            )
            self._spec_k_live = self._spec_rungs[-1]  # graftlint: guarded-by(_book)
            self._jit_verify = jax.jit(
                _named_partial(
                    self._verify_impl, cfg=self.cfg, mesh=mesh, **tpkw,
                ),
                donate_argnums=(1,),
            )
            # Draft model (optional second checkpoint): greedy k-token
            # proposal over a fixed sliding history window, one jit per
            # rung keyed ("draft", k). Without it the host-side n-gram
            # drafter proposes for free.
            self._draft_cfg = None
            self._spec_window = min(64, Smax)
            if draft is not None:
                dparams, dcfg = draft
                self._draft_cfg = dcfg.validate()
                self._jit_draft = {
                    kk: jax.jit(
                        _named_partial(
                            spec_model.draft_tokens,
                            dparams,
                            cfg=self._draft_cfg,
                            k=kk,
                        )
                    )
                    for kk in self._spec_rungs
                }
            self._drafter = spec_host.make_drafter(
                self._jit_draft, self._spec_window, self.cfg.pad_token_id
            )
            # Acceptance accounting (host, under _book): feeds gauges,
            # /debug/sched via the sled, and the pilot's spec_k rule.
            self._spec_drafted = 0  # graftlint: guarded-by(_book)
            self._spec_accepted = 0  # graftlint: guarded-by(_book)
            self._spec_waves = 0  # graftlint: guarded-by(_book)
            # In-flight wave descriptor (k, wave mask, n_wave) between
            # dispatch and _spec_post_process.
            self._spec_wave = None  # graftlint: guarded-by(_book)
        # Request-scoped tracing + flight recorder (both env-gated, both
        # zero hot-path cost when off). Lifecycle spans are emitted
        # retroactively at terminal time from _Request timestamps;
        # perf_counter values convert to wall-clock ns through this
        # init-time epoch pairing (Span timestamps are time_ns-domain).
        self._tracer = tracing.get_tracer("engine")
        self._recorder = flight_recorder.from_env()
        self._epoch_perf = time.perf_counter()
        self._epoch_ns = time.time_ns()
        # Env-gated device-profile window: jax.profiler capture over the
        # first TRACE_PROFILE_N dispatched boundaries (0 = off), so the
        # device timeline can be lined up against the recorder's wall-
        # clock boundary records (tools/profile_decode.py parse pattern).
        self._profile_n = int(os.environ.get("TRACE_PROFILE_N", "0") or 0)
        self._profile_dir = os.environ.get(
            "TRACE_PROFILE_DIR", "/tmp/seldon-tpu-profile"
        )
        self._profile_count = 0
        self._profile_active = False
        # Compile & device observatory: variant ledger + live-retrace
        # witness (COMPILE_LEDGER=1), per-variant boundary timing
        # (DISPATCH_TIMING=1), HBM byte accounting (HBM_LEDGER=1). All
        # None/False when off, and every dispatch site keeps its raw
        # un-timed jit call on the off path — same zero-overhead-off
        # contract as the recorder above.
        self._cledger = compile_ledger.from_env()
        if self._cledger is not None and self._tp is not None:
            # One lattice serves the whole TP group: SPMD partitioning
            # happens inside each jit, so variant keys — and the sealed
            # lattice — are identical to tp=1. The snapshot carries the
            # group geometry so /debug/compile readers can tell an
            # 8-way mesh seal from a single-chip one.
            self._cledger.set_mesh(self.ecfg.tp,
                                   int(self._tp.mesh.devices.size))
        self._timing_on = os.environ.get(
            "DISPATCH_TIMING", "0"
        ) in ("1", "true", "True")
        # graftroof (ROOF_LEDGER=1; None — and zero hot-path code —
        # otherwise): analytical FLOPs/bytes pricing of every dispatch
        # key joined with the measured wave timing into per-variant
        # MFU/MBU, plus the host-pre/device/host-post boundary
        # decomposition served at /debug/roof. The roofline IS the
        # timing join, so ROOF_LEDGER implies DISPATCH_TIMING (the
        # PILOT-implies-sched-ledger idiom).
        self._roof = cost_model.from_env()
        if self._roof is not None:
            self._timing_on = True
            dev = jax.devices()[0]
            self._roof.bind(
                self.cfg,
                max_slots=self.ecfg.max_slots,
                max_seq_len=self.ecfg.max_seq_len,
                kv_block=self._kv_block if self._paged else 0,
                draft_cfg=getattr(self, "_draft_cfg", None),
                platform=(getattr(dev, "device_kind", "") or dev.platform),
                tp=self.ecfg.tp if self._tp is not None else 1,
            )
        self._observe = self._cledger is not None or self._timing_on
        # Variant keys dispatched since the last boundary sync, paired
        # with the boundary wall time in _process_boundary. Written only
        # by the scheduler thread between dispatch and boundary.
        self._wave_keys: List[Tuple[Any, ...]] = []
        # Roofline decomposition taps (all dead when _roof is None):
        # dispatch-step entry stamp and the wave's accumulated jit
        # enqueue seconds. Same single-writer contract as _wave_keys
        # (scheduler thread between dispatch and boundary; warmup and
        # the pre-thread start() reset run before the scheduler exists).
        self._step_t0 = 0.0
        self._wave_enq_s = 0.0
        self._hbm = hbm_ledger.from_env()
        if self._hbm is not None:
            if self._tp is None:
                self._hbm.set_static("weights", sum(
                    int(x.nbytes)
                    for x in jax.tree_util.tree_leaves(params)
                ))
                self._hbm.gauge("kv_cache", self._hbm_kv_reserved_bytes)
                self._hbm.gauge("kv_live", self._hbm_kv_live_bytes)
                self._hbm.gauge("prefix_cache", self._hbm_prefix_bytes)
                for kind, nbytes in self.cache_bytes().items():
                    if kind == "kv_window":  # the window layers' rings
                        self._hbm.set_static(kind, nbytes)
                    elif kind != "kv":  # a fixed-size state, by its kind
                        self._hbm.set_static(kind + "_state", nbytes)
            else:
                # Per-device accounting on the mesh: weights are priced
                # from each leaf's committed shard shape (replicated
                # leaves cost a full copy per device, sharded leaves
                # their slice — the exact-TP split); the mesh-total is
                # devices x per-device resident bytes, so the ledger's
                # conservation total == sum(categories) keeps holding
                # per device AND mesh-wide. KV shards exactly on the
                # head axis, so per-device = logical // tp.
                tpn = self.ecfg.tp
                self._hbm.set_devices(tpn)
                per_dev = self._hbm_weights_device_bytes()
                self._hbm.set_static("weights", per_dev * tpn,
                                     per_device=per_dev)
                self._hbm.gauge(
                    "kv_cache", self._hbm_kv_reserved_bytes,
                    per_device_fn=lambda:
                        self._hbm_kv_reserved_bytes() // tpn)
                self._hbm.gauge(
                    "kv_live", self._hbm_kv_live_bytes,
                    per_device_fn=lambda:
                        self._hbm_kv_live_bytes() // tpn)
                self._hbm.gauge("prefix_cache", self._hbm_prefix_bytes)
        # Scheduler waste observatory (SCHED_LEDGER=1; None — and zero
        # hot-path code — otherwise): per-boundary goodput attribution,
        # queue-wait decomposition, and the conservation audit that
        # runs next to graftsan's boundary audits.
        self._sled = sched_ledger.from_env()
        # graftpilot (PILOT=1 auto / PILOT=hold pinned; None — and the
        # raw FIFO dispatch path — otherwise): bounded feedback
        # controller over dispatch_token_budget / admission group size /
        # chunk rung plus EDF deadline ordering, with the decision
        # ledger served at /debug/pilot. The sched ledger is its signal
        # source, so PILOT implies one even without SCHED_LEDGER=1.
        self._pilot = controller.from_env()
        if self._pilot is not None:
            if self._sled is None:
                self._sled = sched_ledger.SchedLedger()
            self._pilot.bind(
                chunked=self._chunked,
                prefill_chunk=self._prefill_chunk if self._chunked else 0,
                max_slots=self.ecfg.max_slots,
                max_admit=self._max_admit,
                dispatch_token_budget=self.ecfg.dispatch_token_budget,
                spec=self._spec,
                spec_rungs=self._spec_rungs if self._spec else (),
            )
        # Runtime concurrency sanitizer (GRAFTSAN=1; None — and zero
        # hot-path code — otherwise). Wraps every lock above in an
        # order-asserting proxy, so this must stay the LAST piece of
        # engine state __init__ builds.
        self._san = graftsan.instrument(self)

    def _refuse_unpatterned_paths(self) -> None:
        """A patterned stack (cfg.layer_types: conv state, or a Mamba-2
        mixer's SSM and conv state, beside KV, in layers of their own or
        in the attention's own layer) runs on the default path:
        dense slab, tp = 1. Every opt-in path moves, shares or replays
        KV by token position and knows no fixed-size state, so it would
        serve those layers a state that is stale, another request's or
        absent. Refuse each by name here, at construction, not with
        wrong tokens later."""
        if not self.cfg.patterned:
            return
        e = self.ecfg
        state = "SSM state" if self.cfg.n_mamba_layers else "conv state"
        if self.cfg.n_window_layers:
            state = ("sliding_attention layers' ring of keys and values "
                     "(the window kind of KV)")
        if self.cfg.gen_block:
            # generation by diffusion over blocks: every opt-in path
            # moves, shares, replays or verifies KV a token at a time
            state = (f"block in hand (gen_block {self.cfg.gen_block} "
                     "positions, final only at its commit pass)")
        both = f", both in each {OP_ATTN_MAMBA} layer" \
            if OP_ATTN_MAMBA in self.cfg.layer_types else ""
        asked = [
            name for name, on in (
                ("paged_kv (the block pool holds KV only, every layer's as "
                 "long as the window)", e.paged_kv),
                (f"prefix_cache (a reused prefix carries no {state})",
                 e.prefix_cache),
                (f"chunked_prefill (a chunk would have to resume the {state})",
                 e.chunked_prefill),
                (f"spec_decode (a rejected draft cannot rewind the {state})",
                 e.spec_decode),
                ("heal (replay re-admits by KV position)",
                 supervisor.build(e) is not None),
                ("tp > 1 (tp_sharding has no table for this tree)",
                 e.tp > 1),
            ) if on
        ]
        if asked:
            raise ValueError(
                f"this model has a patterned stack (layer_types: {state} "
                f"beside KV{both}), which is served on the default path "
                "only; not with " + "; ".join(asked)
            )

    def _refuse_gen_block_shapes(self) -> None:
        """A model that generates by diffusion over blocks
        (cfg.gen_block) prefills whole blocks and commits whole blocks:
        a window or a prompt bucket that cuts one is refused. (The
        opt-in paths refuse it as they refuse any patterned stack, by
        name: _refuse_unpatterned_paths.)"""
        Bk = self.cfg.gen_block
        cut = [n for n in (self.ecfg.max_seq_len,) + self._buckets
               if Bk and n % Bk]
        if cut:
            raise ValueError(
                f"gen_block {Bk} needs max_seq_len and every prompt bucket "
                f"to be a multiple of it; {cut} are not")

    def _fresh_state(self) -> Dict[str, Any]:
        B, Smax = self.ecfg.max_slots, self.ecfg.max_seq_len
        if self._paged:
            cache = transformer.init_paged_cache(
                self.cfg, self._num_blocks, self._kv_block
            )
        else:
            cache = transformer.init_cache(self.cfg, B, Smax)
        state = slot_rules.fresh(cache, B, self.cfg.gen_block)
        if self._tp is not None:
            # Commit the state onto the mesh (KV heads on 'tp', per-slot
            # scalars replicated) so the FIRST dispatch already sees the
            # shardings every impl's constrain_state pins — one stable
            # jit cache key from wave zero.
            state = tp_sharding.shard_state(self._tp.mesh, state)
        elif self.mesh is not None and self.mesh.devices.size == 1:
            # Same reason on the one-chip mesh: an uncommitted state
            # next to mesh-committed weights keys the first dispatch
            # differently from every later one (whose state is a jit
            # output, hence committed), and the first variant an engine
            # runs would compile twice — 16 s of a live request at 8B
            # on a v5e (chip_smoke, PR 21).
            from jax.sharding import NamedSharding, PartitionSpec

            state = jax.device_put(
                state, NamedSharding(self.mesh, PartitionSpec())
            )
        return state

    # --- jitted kernels -----------------------------------------------------

    @staticmethod
    def _replicate(mesh, *arrays):
        """Pin host-visible outputs to full replication. On a
        multi-PROCESS mesh, device_get needs every shard addressable
        locally — without this GSPMD may shard the small result arrays
        across hosts. No-op cost on a single chip."""
        if mesh is None:
            return arrays
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        return tuple(
            jax.lax.with_sharding_constraint(a, rep) for a in arrays
        )

    @staticmethod
    def _admit_impl(
        params, state, toks, plens, seeds, temps, top_ks, top_ps,
        max_news, slots, *, cfg, mesh=None, ring_mesh=None,
        return_sub=False, tp=None,
    ):
        """Fused admission: prefill [G, Sb], scatter into cache slots, sample
        first tokens, arm slot state (models/slot.py: key, termination
        and arming rules). One dispatch, no host sync."""
        G, Sb = toks.shape
        sub = transformer.init_cache(cfg, G, Sb)
        if cfg.gen_block:
            # Generation by diffusion over blocks: the prompt's whole
            # blocks deposit their KV and nothing is scored; its tail is
            # the decided part of the first block in hand, and the slot
            # has no first token (slot_rules.first_block).
            whole = plens - plens % cfg.gen_block
            _, sub = transformer.prefill(params, toks, whole, sub, cfg)
            none, done = jnp.zeros((G,), jnp.int32), max_news <= 0
            new_state = slot_rules.arm(
                state, slots, cache=transformer.cache_scatter_slots(
                    cfg, state["cache"], sub, slots, Sb),
                first=none, done=done, pos=whole, temps=temps,
                top_ks=top_ks, top_ps=top_ps, seeds=seeds, max_news=max_news,
                block=slot_rules.first_block(toks, plens, cfg.gen_block))
            return (new_state,) + InferenceEngine._replicate(mesh, none, done)
        if ring_mesh is not None:
            sp = dict(ring_mesh.shape).get("sp", 1)
            if Sb % sp != 0:  # static per-bucket decision
                ring_mesh = None
        logits, sub = transformer.prefill(
            params, toks, plens, sub, cfg, ring_mesh=ring_mesh, tp=tp,
            spread=_spread(mesh))
        cache = state["cache"]
        Smax = cache["k"].shape[3]
        first, first_done = slot_rules.first_token(
            logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
        # Scatter EVERY cache array by its kind (transformer.cache_spec):
        # k/v + scales into the slots' first Sb positions, a fixed-size
        # state (a patterned stack's conv or SSM state) overwritten whole.
        new_cache = transformer.cache_scatter_slots(
            cfg, cache, sub, slots, Sb)
        new_state = slot_rules.arm(
            state, slots, cache=new_cache, first=first, done=first_done,
            pos=plens, temps=temps, top_ks=top_ks, top_ps=top_ps,
            seeds=seeds, max_news=max_news,
        )
        if tp is not None:
            new_state = tp.constrain_state(new_state)
        first, first_done = InferenceEngine._replicate(
            mesh, first, first_done
        )
        if return_sub:
            # Prefix-cache insertion path: `sub` already holds the
            # cache-dtype KV writes, slab rows [L, G, 1, Sb, Hkv*Dh] (scales
            # [L, G, Hkv, Sb]), that the host slices into trie blocks.
            return new_state, first, first_done, sub
        return new_state, first, first_done

    @staticmethod
    def _admit_prefix_impl(
        params, state, toks, plens, prefix_lens, prefix_kv, seeds, temps,
        top_ks, top_ps, max_news, slots, *, cfg, mesh=None, tp=None,
    ):
        """Fused WARM admission: suffix-only prefill attending to reused
        prefix KV, prefix + suffix scattered into the slot cache, first
        tokens sampled, slot state armed — the prefix-cache twin of
        _admit_impl.

        `toks` holds ONLY each prompt's uncached suffix [G, Sq]; `plens`
        are FULL prompt lengths (slot_rules.first_key).
        `prefix_kv` arrives in cache storage dtype, slab rows
        [L, G, 1, Pb, Hkv*Dh] (int8 scales [L, G, Hkv, Pb])
        (gathered host-side from the trie, zero-padded past each row's
        prefix_len — the padded tail is overwritten by the suffix scatter
        below, and decode's strict t < pos mask never reads past-plen
        garbage before it is rewritten)."""
        G, Sq = toks.shape
        logits, kv = transformer.prefill_with_prefix(
            params, toks, plens, prefix_kv, prefix_lens, cfg, tp=tp
        )
        cache = state["cache"]
        Smax = cache["k"].shape[3]
        first, first_done = slot_rules.first_token(
            logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
        writes = transformer.kv_writes(kv, cache, cfg)
        Pb = prefix_kv["k"].shape[3]
        # Suffix rows land at absolute positions prefix_len + i; rows past
        # the cache window drop out of the scatter (jax default OOB mode).
        spos = prefix_lens[:, None] + jnp.arange(Sq)[None, :]  # [G, Sq]
        new_cache = {}
        for key in cache:
            c = cache[key].at[:, slots, :, :Pb].set(
                prefix_kv[key].astype(cache[key].dtype)
            )
            # Advanced indices (slots, spos) broadcast to [G, Sq] and land
            # in front: update operand is writes[key] [L, G, 1, Sq, Hkv*Dh]
            # (scales [L, G, Hkv, Sq]) with G and Sq moved to the front.
            new_cache[key] = c.at[:, slots[:, None], :, spos].set(
                jnp.moveaxis(writes[key], (1, 3), (0, 1))
            )
        new_state = slot_rules.arm(
            state, slots, cache=new_cache, first=first, done=first_done,
            pos=plens, temps=temps, top_ks=top_ks, top_ps=top_ps,
            seeds=seeds, max_news=max_news,
        )
        if tp is not None:
            new_state = tp.constrain_state(new_state)
        first, first_done = InferenceEngine._replicate(
            mesh, first, first_done
        )
        return new_state, first, first_done, writes

    @staticmethod
    def _admit_chunk_impl(
        params, state, toks, plens, starts, seeds, temps, top_ks, top_ps,
        max_news, slots, finals, *, prefix_width, cfg, mesh=None,
        return_sub=False, tp=None,
    ):
        """Fused prefill CHUNK: run `toks` [G, Sc] (tokens
        [start, start+Sc) of each prompt) through prefill_with_prefix
        against the KV that chunks 0..k-1 (and any prefix-cache hit)
        already scattered into the slot cache, then scatter the fresh
        suffix KV back. Rows with finals=True are each prompt's LAST
        chunk: they sample the first token under _admit_impl's key —
        co-batched chunk traffic cannot perturb greedy outputs — and
        arm the slot. Non-final rows only deposit KV (slot_rules.arm).

        `prefix_width` (static) buckets how much resident KV the chunk
        attends to: the slice cache[:, slots, :, :W] covers every row's
        start (start <= W), and prefill_with_prefix's t < start mask
        hides the tail. pos is set to start+Sc (clamped to plen) even
        mid-prefill so the decode chunks interleaved between prefill
        chunks scatter their dead-row garbage write exactly where the
        NEXT chunk's scatter lands first — never inside KV already
        written."""
        G, Sc = toks.shape
        cache = state["cache"]
        Smax = cache["k"].shape[3]
        prefix_kv = {
            key: cache[key][:, slots, :, :prefix_width] for key in cache
        }
        logits, kv = transformer.prefill_with_prefix(
            params, toks, plens, prefix_kv, starts, cfg, tp=tp
        )
        first, first_done = slot_rules.first_token(
            logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
        new_pos = jnp.minimum(plens, starts + Sc)
        writes = transformer.kv_writes(kv, cache, cfg)
        # Chunk rows land at absolute positions start + i (same advanced-
        # indexing shape as _admit_prefix_impl's suffix scatter); padding
        # rows duplicate a real row's slot + data, so duplicate writes
        # are well-defined.
        spos = starts[:, None] + jnp.arange(Sc)[None, :]  # [G, Sc]
        new_cache = {
            key: cache[key].at[:, slots[:, None], :, spos].set(
                jnp.moveaxis(writes[key], (1, 3), (0, 1))
            )
            for key in cache
        }
        new_state = slot_rules.arm(
            state, slots, cache=new_cache, first=first, done=first_done,
            pos=new_pos, finals=finals, temps=temps, top_ks=top_ks,
            top_ps=top_ps, seeds=seeds, max_news=max_news,
        )
        if tp is not None:
            new_state = tp.constrain_state(new_state)
        first, first_done = InferenceEngine._replicate(
            mesh, first, first_done
        )
        if return_sub:
            return new_state, first, first_done, writes
        return new_state, first, first_done

    @staticmethod
    def _seed_prefix_impl(state, prefix_kv, slot):
        """Chunked-prefill warm start: scatter a prefix-cache hit's
        trie-gathered KV [L, 1, W, Hkv*Dh] (scales [L, Hkv, W]) into one
        slot's cache rows [0, W), so every chunk reads resident KV
        uniformly whether it came from the trie or from earlier chunks."""
        cache = state["cache"]
        W = prefix_kv["k"].shape[2]
        new_cache = {
            key: cache[key].at[:, slot, :, :W].set(
                prefix_kv[key].astype(cache[key].dtype)
            )
            for key in cache
        }
        return {**state, "cache": new_cache}

    @staticmethod
    def _chunk_impl(params, state, *, cfg, n_steps, mesh=None, tp=None):
        """`n_steps` decode iterations over every slot in one lax.scan
        (slot_rules.decode_chunk). Returns (state, toks [K,B], valid [K,B],
        active [B], counts), toks and valid [K,B,Bk] where an iteration is
        a pass over a block of Bk positions a slot (cfg.gen_block: its
        four counters come right after the sampler's); counts int32 over
        the chunk, in
        CHUNK_COUNTERS' order: steps, steps that drew, steps that masked;
        KV tokens the attention layers read and KV tokens the slab holds
        for them, K rows they wrote and slots x layers; a routed model
        adds sparse-layer steps, distinct experts read (summed over
        those), assignments; one that holds a share of its
        experts or has Mamba-2 layers adds the assignments held here
        and the Mamba-2 layers run (transformer.routing_width)."""
        Smax = state["cache"]["k"].shape[3]
        # The model is told which rows hold a request: attention reads
        # no other row's KV where it can tell them apart, and a model
        # that dispatches tokens to experts routes the others nowhere;
        # what routing did rides out with the tokens.
        routed = InferenceEngine._counts_routing(cfg)
        # over a mesh of several devices the compiler partitions the
        # program (or tp does, exactly): no kernel reads the slab there
        spread = tp is not None or _spread(mesh)

        def step_model(carry):
            live, pos, cache = carry["active"], carry["pos"], carry["cache"]
            logits, cache_, *routing = transformer.decode_step(
                params, carry["last_tok"], pos, cache, cfg, tp=tp, live=live,
                return_routing=routed, spread=spread)
            kv = transformer.decode_kv_counts(cfg, cache, live, pos, spread)
            return logits, cache_, jnp.concatenate([kv, *routing])

        def pass_model(carry):
            """step_model for cfg.gen_block: the block in hand of every
            slot; the slots whose block is decided commit its KV. The
            head is slot_rules.block_step's to call, over the slots
            that will read their scores."""
            live, pos, cache = carry["active"], carry["pos"], carry["cache"]
            commit = slot_rules.committing(carry)
            hidden, cache_, routing = transformer.decode_block(
                params, carry["blk_tok"], carry["blk_known"], pos, cache,
                cfg, live, commit, spread)
            kv = transformer.block_kv_counts(cfg, cache, live, pos, commit,
                                             spread)
            return hidden, cache_, jnp.concatenate([kv, routing])

        state, toks, valid, counts = slot_rules.decode_chunk(
            pass_model if cfg.gen_block else step_model, state, n_steps,
            Smax, cfg,
            head=functools.partial(transformer.block_logits, params, cfg=cfg))
        if tp is not None:
            state = tp.constrain_state(state)
        toks, valid, active, counts = InferenceEngine._replicate(
            mesh, toks, valid, state["active"], counts
        )
        return state, toks, valid, active, counts

    @staticmethod
    def _counts_routing(cfg) -> bool:
        """Decode chunks of this model count what routing did, after
        the sampler's tiers in their fifth value (a patterned stack
        with sparse layers, or with Mamba-2 mixers whose layer steps
        ride in the same counters; a homogeneous stack with experts,
        whose counters grow where its sparse block runs by dispatch and
        stay 0 where it keeps moe_block: transformer.decode_step)."""
        if not cfg.patterned:
            return bool(cfg.n_experts)
        return bool(cfg.n_sparse_layers or cfg.n_mamba_layers)

    # --- paged-KV kernels ---------------------------------------------------

    @staticmethod
    def _paged_admit_impl(
        params, state, table, toks, plens, prefix_lens, seeds, temps,
        top_ks, top_ps, max_news, slots, *, prefix_width, cfg, mesh=None,
        tp=None,
    ):
        """Paged fused admission — ONE kernel covers cold and warm.

        prefix_width == 0 (cold): full-prompt prefill into a scratch
        cache, exactly _admit_impl's math, then the writes scatter into
        the pool THROUGH the group's block tables instead of contiguous
        slot rows. prefix_width > 0 (warm): the reused prefix is a pure
        GATHER of the table's first prefix_width/kv_block blocks — the
        blocks a zero-copy admission just refcounted from the trie — fed
        to the same prefill_with_prefix as the dense warm path, so greedy
        outputs stay bit-identical while the admission moves no prefix
        KV at all. Suffix positions past a row's allocated blocks route
        to the trash block (paged_scatter_tokens), mirroring the dense
        path's dropped OOB scatter rows."""
        G, Sb = toks.shape
        pool = state["cache"]
        block = pool["k"].shape[3]
        Smax = table.shape[1] * block
        if prefix_width:
            prefix_kv = transformer.paged_prefix_view(
                pool, table, prefix_width // block
            )
            logits, kv = transformer.prefill_with_prefix(
                params, toks, plens, prefix_kv, prefix_lens, cfg, tp=tp
            )
            writes = transformer.kv_writes(kv, pool, cfg)
            spos = prefix_lens[:, None] + jnp.arange(Sb)[None, :]
        else:
            sub = transformer.init_cache(cfg, G, Sb)
            logits, writes = transformer.prefill(
                params, toks, plens, sub, cfg, tp=tp,
                spread=_spread(mesh))
            # A cold prefill fills slab rows; the pool is by head.
            writes = transformer.kv_by_head(writes, cfg)
            spos = jnp.broadcast_to(jnp.arange(Sb)[None, :], (G, Sb))
        first, first_done = slot_rules.first_token(
            logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
        new_pool = transformer.paged_scatter_tokens(pool, writes, table,
                                                    spos)
        new_state = slot_rules.arm(
            state, slots, cache=new_pool, first=first, done=first_done,
            pos=plens, temps=temps, top_ks=top_ks, top_ps=top_ps,
            seeds=seeds, max_news=max_news,
        )
        if tp is not None:
            new_state = tp.constrain_state(new_state)
        first, first_done = InferenceEngine._replicate(
            mesh, first, first_done
        )
        return new_state, first, first_done

    @staticmethod
    def _paged_admit_chunk_impl(
        params, state, table, toks, plens, starts, seeds, temps, top_ks,
        top_ps, max_news, slots, finals, *, prefix_width, cfg, mesh=None,
        tp=None,
    ):
        """Paged twin of _admit_chunk_impl: the resident KV of chunks
        0..k-1 (and any zero-copy warm prefix) is a block-table GATHER of
        each row's first prefix_width/kv_block blocks instead of a slab
        slice, and the fresh chunk KV scatters back through the table.
        Attention math is identical and the slot's rules are shared
        (models/slot.py), so greedy outputs match the dense chunked path
        bit-for-bit. No writes are returned — paged trie insertion is
        host-side block bookkeeping, not device KV."""
        G, Sc = toks.shape
        pool = state["cache"]
        block = pool["k"].shape[3]
        Smax = table.shape[1] * block
        prefix_kv = transformer.paged_prefix_view(
            pool, table, prefix_width // block
        )
        logits, kv = transformer.prefill_with_prefix(
            params, toks, plens, prefix_kv, starts, cfg, tp=tp
        )
        first, first_done = slot_rules.first_token(
            logits, seeds, plens, temps, top_ks, top_ps, max_news, Smax, cfg)
        new_pos = jnp.minimum(plens, starts + Sc)
        writes = transformer.kv_writes(kv, pool, cfg)
        spos = starts[:, None] + jnp.arange(Sc)[None, :]
        new_pool = transformer.paged_scatter_tokens(pool, writes, table,
                                                    spos)
        new_state = slot_rules.arm(
            state, slots, cache=new_pool, first=first, done=first_done,
            pos=new_pos, finals=finals, temps=temps, top_ks=top_ks,
            top_ps=top_ps, seeds=seeds, max_news=max_news,
        )
        if tp is not None:
            new_state = tp.constrain_state(new_state)
        first, first_done = InferenceEngine._replicate(
            mesh, first, first_done
        )
        return new_state, first, first_done

    @staticmethod
    def _paged_chunk_impl(params, state, table, *, cfg, n_steps, mesh=None,
                          tp=None):
        """Paged twin of _chunk_impl: `n_steps` decode iterations reading
        K/V through the block tables (transformer.paged_decode_step);
        the same slot_rules.decode_chunk, so greedy tokens match the dense
        chunk bit-for-bit. Inactive rows' garbage writes route through
        table entry 0 (trash) once the host zeroes a freed row — the
        paged analogue of the dense path's frozen-position scribble."""
        block = state["cache"]["k"].shape[3]
        Smax = table.shape[1] * block

        def step_model(carry):
            return transformer.paged_decode_step(
                params, carry["last_tok"], carry["pos"], carry["cache"],
                table, cfg, tp=tp,
            )

        state, toks, valid, counts = slot_rules.decode_chunk(
            step_model, state, n_steps, Smax, cfg)
        if tp is not None:
            state = tp.constrain_state(state)
        toks, valid, active, counts = InferenceEngine._replicate(
            mesh, toks, valid, state["active"], counts
        )
        return state, toks, valid, active, counts

    @staticmethod
    def _deactivate_impl(state, keep):
        """Freeze rows where keep=False (cancel/deadline reap): dropping
        `active` and zeroing `remaining` makes the row indistinguishable
        from one that just hit EOS — the decode chunk's masking already
        handles frozen pos, clamped sampler knobs, and (paged) trash-
        routed garbage writes, so no new device invariants appear."""
        return {
            **state,
            "active": state["active"] & keep,
            "remaining": jnp.where(keep, state["remaining"], 0),
        }

    @staticmethod
    def _cow_copy_impl(state, src, dst):
        """Copy-on-write block copy: duplicate pool block `src` into
        `dst` (every cache array — k/v and int8 scales). src/dst are
        traced scalars, so all CoW copies share one compile. Dispatched
        BEFORE the warm admission that writes into `dst`, and `src` is
        pinned by the request's trie handle, so device ordering makes
        the copy race-free."""
        pool = state["cache"]
        new_pool = {
            key: pool[key].at[:, dst].set(pool[key][:, src])
            for key in pool
        }
        return {**state, "cache": new_pool}

    @staticmethod
    def _verify_impl(params, state, table, drafts, wave, *, cfg,
                     mesh=None, tp=None):
        """graftspec: ONE wide verify dispatch replacing up to k + 1
        sequential decode steps (models/spec_decode.verify_wave). The
        k rung is carried by the drafts width — one compile per rung,
        keyed ("verify", k) in the lattice. Returns the decode chunk's
        exact contract (toks/valid are [k+1, B] True-prefix columns),
        so _process_chunk consumes a wave unchanged."""
        state, toks, valid, counts = spec_model.verify_wave(
            params, state, table, drafts, wave, cfg, tp=tp,
        )
        if tp is not None:
            state = tp.constrain_state(state)
        toks, valid, active, counts = InferenceEngine._replicate(
            mesh, toks, valid, state["active"], counts
        )
        return state, toks, valid, active, counts

    # --- public API ---------------------------------------------------------

    def submit(
        self, tokens: Sequence[int], params: Optional[SamplingParams] = None
    ) -> "queue.Queue[Optional[dict]]":
        """Enqueue a request. Returns a queue yielding
        {"tokens": [int, ...], "ttft_ms": float?, "timings": dict?} dicts
        (one per scheduler boundary — tokens arrive in decode-chunk
        bursts; the first carries ttft_ms and its phases, _timings), then
        None at end."""
        params = params or SamplingParams()
        if len(tokens) == 0:
            raise ValueError("empty prompt")
        max_prompt = max(self._buckets)
        if len(tokens) > max_prompt:
            raise ValueError(
                f"prompt length {len(tokens)} exceeds max bucket {max_prompt}"
            )
        if len(tokens) + params.max_new_tokens > self.ecfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(tokens)} + max_new_tokens "
                f"{params.max_new_tokens} exceeds max_seq_len "
                f"{self.ecfg.max_seq_len}; the decode would be truncated "
                f"mid-stream — lower max_new_tokens or shorten the prompt"
            )
        if self._paged:
            need = -(-len(tokens) // self._kv_block)
            if need > self._num_blocks - 1:
                raise ValueError(
                    f"prompt needs {need} kv blocks but the pool holds "
                    f"{self._num_blocks - 1}; it can never be admitted — "
                    f"raise kv_pool_blocks or shorten the prompt"
                )
        if self._draining.is_set() or self._stop.is_set():
            raise EngineDraining(
                "engine is draining; retry against another replica"
            )
        if self.ecfg.max_queue:
            # _book makes the depth a coherent snapshot: _waiting is the
            # scheduler's queue and mutates under the bookkeeping lock.
            with self._book:
                depth = self._pending.qsize() + len(self._waiting)
        if self.ecfg.max_queue and depth >= self.ecfg.max_queue:
            with self.stats.lock:
                self.stats.queue_rejects += 1
                self.stats.shed_total += 1
            raise EngineOverloaded(
                f"admission queue full ({self.ecfg.max_queue} requests); "
                f"retry with backoff"
            )
        now = time.perf_counter()
        out_q = (
            queue.Queue() if self._san is None
            else graftsan.TerminalQueue(self._san)
        )
        req = _Request(0, list(tokens), params, out_q, now)
        received = params.received_at
        req.received_at = (
            now if received is None or received > now else received
        )
        ttl_ms = params.deadline_ms or self.ecfg.default_deadline_ms
        if ttl_ms:
            req.deadline = now + ttl_ms / 1000.0
        with self._rid_lock:
            self._rid += 1
            req.rid = self._rid
            self._requests[req.rid] = req
        # Transports read the rid off the returned queue to cancel() a
        # request whose client vanished mid-stream.
        req.out.rid = req.rid
        if self._tracer.enabled and params.traceparent:
            req.trace = tracing.SpanContext.from_traceparent(
                params.traceparent
            )
        if self._recorder is not None:
            self._recorder.record(
                "submit", req.rid,
                {"prompt_tokens": len(req.tokens), "deadline_ms": ttl_ms},
            )
        with self.stats.lock:
            self.stats.requests += 1
            self.stats.executor_wait_sum += now - req.received_at
        self._pending.put(req)
        return req.out

    def generate_blocking(
        self, tokens: Sequence[int], params: Optional[SamplingParams] = None
    ) -> Dict[str, Any]:
        """Submit and collect the full completion. Raises RuntimeError if the
        engine failed the request (bad params, decode error)."""
        out = self.submit(tokens, params)
        toks: List[int] = []
        ttft_ms = None
        timings = None
        error = None
        while True:
            item = out.get()
            if item is None:
                break
            if "error" in item:
                error = item
                continue
            toks.extend(item["tokens"])
            if ttft_ms is None:
                ttft_ms = item.get("ttft_ms")
                timings = item.get("timings")
        if error is not None:
            exc = RuntimeError(f"generation failed: {error['error']}")
            # Typed-outcome surface for transports: lifecycle kind plus
            # whether a retry elsewhere could succeed.
            exc.kind = error.get("kind", "internal")
            exc.retriable = bool(error.get("retriable", False))
            exc.http_status = KIND_HTTP_STATUS.get(exc.kind, 500)
            raise exc
        return {"token_ids": toks, "ttft_ms": ttft_ms, "timings": timings}

    def cancel(self, rid: int) -> bool:
        """Flag a request for cancellation; the scheduler reaps it at the
        next boundary (queued -> shed, in-flight -> device row frozen and
        slot/blocks/trie refs freed). Returns False for unknown or
        already-finished rids — cancel is then a harmless no-op, which is
        exactly what a disconnect race wants. Thread-safe."""
        with self._rid_lock:
            req = self._requests.get(rid)
        if req is None or req.finished:
            return False
        req.cancelled = True
        return True

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def debug_timeline(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder snapshot (oldest-first records + epoch info),
        or None when FLIGHT_RECORDER is off — the /debug/timeline
        payload, and tools/trace_view.py's input."""
        if self._recorder is None:
            return None
        return self._recorder.snapshot()

    def debug_compile(self) -> Optional[Dict[str, Any]]:
        """Compile-ledger snapshot (variant lattice, warmup coverage,
        live-retrace witnesses, cumulative compile seconds), or None
        when COMPILE_LEDGER is off — the /debug/compile payload."""
        if self._cledger is None:
            return None
        return self._cledger.snapshot()

    def debug_hbm(self) -> Optional[Dict[str, Any]]:
        """HBM-ledger snapshot (per-category bytes + high-watermarks),
        or None when HBM_LEDGER is off — the /debug/hbm payload."""
        if self._hbm is None:
            return None
        return self._hbm.snapshot()

    def debug_sched(self) -> Optional[Dict[str, Any]]:
        """Sched-ledger snapshot (per-boundary waste attribution,
        goodput-gap decomposition, queue-wait components, conservation
        audit), or None when SCHED_LEDGER is off — the /debug/sched
        payload."""
        if self._sled is None:
            return None
        return self._sled.snapshot()

    def debug_pilot(self) -> Optional[Dict[str, Any]]:
        """Pilot-controller snapshot (live knobs, envelope, EDF
        counters, decision ledger with counterfactual effects), or None
        when PILOT is off — the /debug/pilot payload. Unlike the other
        ledgers the controller's state is guarded-by(_book) (it IS
        scheduler state), so the snapshot takes the lock: cold path,
        bounded ledger, legal from the HTTP thread."""
        if self._pilot is None:
            return None
        with self._book:
            return self._pilot.snapshot()

    def debug_roof(self) -> Optional[Dict[str, Any]]:
        """Roofline snapshot (per-variant MFU/MBU against the platform
        peaks, host-pre/device/host-post boundary decomposition,
        conservation audit), or None when ROOF_LEDGER is off — the
        /debug/roof payload. Lock-free like the sched ledger: the
        window may tear, a record never does."""
        if self._roof is None:
            return None
        return self._roof.snapshot()

    def roof_predict_ms(self, prompt_len: int,
                        max_new: int) -> Optional[float]:
        """Cost-model roofline estimate for one request at this
        engine's geometry (bench/tier-routing surface), or None when
        ROOF_LEDGER is off."""
        if self._roof is None:
            return None
        return self._roof.predict_request_ms(prompt_len, max_new)

    def _hbm_weights_device_bytes(self) -> int:
        """Per-device resident weight bytes under the committed
        shardings: each leaf costs its shard shape (full shape when
        replicated — the exact-TP scheme keeps wo / w_down / embeddings
        whole on every chip). Shape metadata only — no sync."""
        total = 0
        for x in jax.tree_util.tree_leaves(self.params):
            shp = x.shape
            sh = getattr(x, "sharding", None)
            if sh is not None:
                shp = sh.shard_shape(x.shape)
            total += int(np.prod(shp, dtype=np.int64)) * x.dtype.itemsize
        return total

    def cache_bytes(self) -> Dict[str, int]:
        """Bytes of the slot cache by kind: {"kv": ...} and, for a
        patterned stack, {"conv": ...}, {"ssm": ..., "ssm_conv": ...} or
        {"kv_window": ...} (the sliding_attention layers' rings)
        (transformer.cache_spec's kinds; the paged pool is all KV).
        Shape metadata — no sync."""
        if self._paged:
            return {"kv": sum(
                int(x.nbytes)
                for x in jax.tree_util.tree_leaves(self._state["cache"])
            )}
        return transformer.cache_bytes(
            self.cfg, self.ecfg.max_slots, self.ecfg.max_seq_len)

    def _hbm_kv_reserved_bytes(self) -> int:
        """Static KV reservation: the KV arrays of the cache (dense slot
        slab or paged block pool); a patterned stack's fixed-size state
        is categories of its own ("conv_state", "ssm_state",
        "ssm_conv_state")."""
        return self.cache_bytes()["kv"]

    def _hbm_kv_live_bytes(self) -> int:
        """Bytes of the reservation actually holding request state:
        used blocks (paged) or occupied slots (dense), prorated over
        the reservation. Snapshot-path only — allocator/_book locks are
        taken cold here, never from the scheduler."""
        total = self._hbm_kv_reserved_bytes()
        if self._paged:
            snap = self._allocator.snapshot()
            return total * snap["used"] // max(1, snap["total"])
        return total * self.slots_busy() // max(1, self.ecfg.max_slots)

    def _hbm_prefix_bytes(self) -> int:
        """Dense prefix-trie KV bytes (its KV copies live outside the
        slot slab). Paged prefix shares pool blocks already counted in
        kv_live, so it reports 0 rather than double-count."""
        if self._prefix is None:
            return 0
        return int(self._prefix.snapshot().get("bytes", 0))

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful drain: stop admitting (submit raises EngineDraining),
        shed everything still queued with a retriable error, and wait up
        to `timeout` seconds for in-flight requests to finish. Returns
        True once the engine is quiescent. The scheduler keeps running —
        call stop() afterwards to halt the threads (stop() drains any
        leftovers itself)."""
        if self._recorder is not None and not self._draining.is_set():
            self._recorder.record("drain", -1, {"timeout_s": timeout})
        self._draining.set()
        if self._thread is None or not self._thread.is_alive():
            # No scheduler to shed queued work on our behalf.
            with self._book:
                self._shed_queued_locked()
        deadline = time.perf_counter() + max(0.0, timeout)
        while time.perf_counter() < deadline:
            with self._book:
                idle = (
                    all(r is None for r in self._slots)
                    and not self._waiting
                    and not self._prefilling
                    and self._pending.empty()
                    and (self._heal is None or self._heal.pen_empty())
                )
            if idle and self._fetch_q.empty():
                return True
            time.sleep(0.005)
        return False

    def debug_lifecycle_check(self) -> Dict[str, Any]:
        """Leak audit for tests/soaks: with no queued or in-flight work,
        every entry in the returned dict is a leak — a slot still held, a
        free-list hole, an armed active row, a dangling registry entry,
        pool blocks that never came back, or trie nodes pinned by dead
        handles. Unpinned trie RETENTION is flushed first (it is cache,
        not a leak). Empty dict == clean."""
        leaks: Dict[str, Any] = {}
        with self._book:
            held = [r.rid for r in self._slots if r is not None]
            if held:
                leaks["slots"] = held
            if len(self._free) + len(held) != self.ecfg.max_slots:
                leaks["free_list"] = len(self._free)
            if self._active_host.any():
                leaks["active_host"] = int(self._active_host.sum())
            if self._waiting or not self._pending.empty():
                leaks["queued"] = len(self._waiting) + self._pending.qsize()
            if self._prefilling:
                leaks["prefilling"] = [r.rid for r in self._prefilling]
            with self._rid_lock:
                if self._requests:
                    leaks["registry"] = sorted(self._requests)
            if self._heal is not None and not self._heal.pen_empty():
                leaks["heal_pen"] = sorted(
                    r.rid for r in self._heal.pen_scan()
                )
            if self._paged:
                if self._paged_prefix is not None:
                    self._paged_prefix.flush()
                    if self._paged_prefix.n_nodes:
                        leaks["trie_pins"] = self._paged_prefix.n_nodes
                snap = self._allocator.snapshot()
                if snap["used"]:
                    leaks["pool_blocks"] = snap
            elif self._prefix is not None:
                self._prefix.flush()
                if self._prefix.n_nodes:
                    leaks["trie_pins"] = self._prefix.n_nodes
        return leaks

    def chaos_counts(self) -> Dict[str, int]:
        """Injected-fault counters (all zero when chaos is disabled)."""
        return self._chaos.snapshot() if self._chaos is not None else {
            "dispatch_faults": 0, "alloc_faults": 0,
            "slow_boundaries": 0, "disconnects": 0,
            "nan_injects": 0, "hangs": 0, "sticky_faults": 0,
        }

    def debug_health(self) -> Optional[Dict[str, Any]]:
        """graftheal supervisor snapshot for the /debug/health endpoint
        (None when HEAL is off — the raw failure path is in effect)."""
        return self._heal.snapshot() if self._heal is not None else None

    @property
    def max_admit(self) -> int:
        """Largest admission group (a power of two) one dispatch forms."""
        return self._max_admit

    @property
    def chunk_sizes(self) -> Tuple[int, ...]:
        """Decode-chunk lengths (steps per dispatch) this engine compiles."""
        return self._chunk_sizes

    def slots_busy(self) -> int:
        """Occupied-slot count, read under the bookkeeping lock. The one
        sanctioned way for metrics exporters to observe slot occupancy."""
        with self._book:
            return sum(1 for r in self._slots if r is not None)

    def pipeline_gauges(self) -> Dict[str, float]:
        """The async scheduler's pipeline depth in force, the two
        running means it follows from (_DepthEstimator) and the steps
        of a chunk dispatched while slots are free (the low rung,
        _size_low_rung), read under the bookkeeping lock. A synchronous
        loop is one deep, measures neither and keeps min_chunk."""
        with self._book:
            g = self._depth_est.gauges()
            g["chunk_steps"] = self._chunk_sizes[0]
        if not self._async_fetch:
            g["depth"] = 1
        return g

    def live_requests(self) -> List["_Request"]:
        """Snapshot of the requests currently holding slots, taken under
        the bookkeeping lock. The list is a copy; the _Request objects are
        live, so only probe/diagnostic readers should use this."""
        with self._book:
            return [r for r in self._slots if r is not None]

    def table_host_snapshot(self) -> np.ndarray:
        """Copy of the host-side block table under the bookkeeping lock,
        for probes that replay the decode kernel outside the engine."""
        with self._book:
            return self._table_host.copy()

    def start(self):
        if self._thread is None:
            self._stop.clear()  # allow stop() -> start() restart
            self._draining.clear()
            # Warmup dispatches never meet a boundary; drop their keys so
            # the first live wave's timing isn't charged to them.
            self._wave_keys = []
            self._wave_enq_s = 0.0
            if self._async_fetch:
                self._fetcher = threading.Thread(
                    target=self._fetch_loop, daemon=True
                )
                self._fetcher.start()
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def stop(self):
        self._draining.set()
        self._stop.set()
        self._room.set()  # a scheduler waiting at the depth bound
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if self._fetcher is not None:
            # Sentinel AFTER the last real item.
            self._fetch_q.put(None)
            self._fetcher.join(timeout=30)
            self._fetcher = None
        # No waiter may be left hanging: everything still queued or in
        # flight gets a retriable shutdown error + None sentinel.
        self._shutdown_sweep()

    def _shed_queued_locked(self) -> None:  # graftlint: holds(_book)
        """Fail every queued (not yet admitted) request with a retriable
        draining error. Caller holds _book or the scheduler is stopped."""
        self._drain_pending()
        while self._waiting:
            req = self._waiting.popleft()
            with self.stats.lock:
                self.stats.shed_total += 1
            self._fail_req(
                req, "engine draining: request was not admitted",
                kind="draining", retriable=True,
            )

    def _shutdown_sweep(self) -> None:
        """After the scheduler threads exit: fail everything that never
        reached a terminal state — queued requests, live slots, mid-
        prefill requests, and requests alive only inside un-fetched
        boundary rosters (optimistic recycling moves them out of _slots
        before their results are read). Idempotent via _fail_req."""
        # The scheduler threads are already joined, so _book is
        # uncontended here — taking it keeps the holds(_book)
        # protocol of _drain_pending/_fail_req honest.
        with self._book:
            live: Dict[int, _Request] = {}
            while True:
                try:
                    item = self._fetch_q.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                for group, _, _, _ in item.admits:
                    for req in group:
                        live[req.rid] = req
                for req in item.roster or []:
                    if req is not None:
                        live[req.rid] = req
            for req in self._slots:
                if req is not None:
                    live[req.rid] = req
            for req in self._prefilling:
                live[req.rid] = req
            if self._heal is not None:
                # Penned resurrectees are in neither _slots nor _waiting.
                for req in self._heal.pen_take(0.0, flush=True):
                    live.setdefault(req.rid, req)
            self._drain_pending()
            while self._waiting:
                req = self._waiting.popleft()
                live[req.rid] = req
            # The registry is authoritative for any straggler the scans above
            # missed (e.g. recycled out of _slots with its boundary already
            # fetched but the request failed mid-processing).
            with self._rid_lock:
                for rid, req in list(self._requests.items()):
                    live.setdefault(rid, req)
            n_swept = 0
            for req in live.values():
                if req is not None and not req.finished:
                    n_swept += 1
                    with self.stats.lock:
                        self.stats.shed_total += 1
                    self._fail_req(
                        req, "engine stopped before the request completed",
                        kind="shutdown", retriable=True,
                    )
            self._prefilling.clear()
            if n_swept:
                logger.warning("shutdown swept %d unfinished requests", n_swept)

    # --- static shape lattice -----------------------------------------------

    def lattice_spec(self) -> shape_lattice.LatticeSpec:
        """The shape-relevant slice of this engine's config, as consumed
        by servers/shape_lattice.py — the single source of truth for
        which static-shape keys exist (warmup iterates it, graftlint's
        certifier cross-checks it, compile_audit --static-xcheck asserts
        runtime dispatches stay inside it)."""
        chunked = self._chunked
        return shape_lattice.LatticeSpec(
            buckets=self._buckets,
            max_seq_len=self.ecfg.max_seq_len,
            max_slots=self.ecfg.max_slots,
            max_admit=self._max_admit,
            decode_rungs=self._chunk_sizes,
            paged=self._paged,
            chunked=chunked,
            prefix=(self._prefix is not None
                    or self._paged_prefix is not None),
            prefix_block=self.ecfg.prefix_block,
            chunk_buckets=self._chunk_buckets if chunked else (),
            prefill_chunk=self._prefill_chunk if chunked else 0,
            token_budget=(
                self.ecfg.dispatch_token_budget or self._prefill_chunk
            ) if chunked else 0,
            spec=self._spec,
            spec_rungs=self._spec_rungs if self._spec else (),
            spec_draft=self._jit_draft is not None,
        )

    def static_lattice(self) -> List[str]:
        """Canonical key strings of every variant live scheduling can
        dispatch — the /debug/compile "declared" set, exported so audits
        can compare against the runtime lattice without a ledger."""
        keys = shape_lattice.dispatch_keys(self.lattice_spec())
        return [compile_ledger.key_str(k)
                for k in shape_lattice.warmup_order(keys)]

    def warmup(self) -> None:
        """Pre-compile the full static shape lattice, so live traffic
        never eats a compile. The key set comes from lattice_spec() —
        the same closed form graftlint certifies against the scheduler
        arithmetic — so warmup covers exactly what live scheduling can
        dispatch: every reachable key (no live retraces, including the
        top-bucket == max_seq_len widths the old per-mode loops skipped)
        and no unreachable ones (no wasted prefill compiles). Not
        thread-safe against the scheduler: call before start() (or while
        no requests are in flight)."""
        keys = shape_lattice.warmup_order(
            shape_lattice.dispatch_keys(self.lattice_spec())
        )
        if self._cledger is not None:
            # Declare ahead of dispatching: a warmup crash mid-lattice
            # still leaves /debug/compile showing the full intended set.
            for key in keys:
                self._cledger.declare(key)
        for key in keys:
            self._warm_key(key)
        jax.block_until_ready(self._state["last_tok"])  # graftlint: allow(hot-sync) warmup runs before start(); the sync IS the point
        if self._cledger is not None:
            self._cledger.warmup_done()
        logger.info(
            "engine warmed: %d lattice variants across %d families",
            len(keys), len({k[0] for k in keys}),
        )

    def _warm_key(self, key: Tuple[Any, ...]) -> None:
        """Compile ONE lattice key: build zero-filled arrays of the
        key's static shapes and dispatch the matching jit entry point.
        max_new=1 everywhere -> rows are first_done; no slot state
        leaks. Traced scalars (plens/pref/starts) are clamped into the
        cache window — for top-bucket keys the bucket equals
        max_seq_len, so the nominal width+1 would index past it; the
        clamp only changes traced VALUES, never the static key."""
        kind = key[0]
        Smax = self.ecfg.max_seq_len
        if self._observe:
            t0 = time.perf_counter()
        if kind == "decode":
            # _dispatch_decode_chunk notes its own dispatch key.
            self._state = self._dispatch_decode_chunk(key[1])[0]  # graftlint: allow(holds-site) warmup runs before start(); no scheduler thread exists yet
            return
        if kind == "cow" and self._paged:
            # _cow notes its own dispatch key (traced src/dst scalars).
            self._cow(0, 0)
            return
        if kind == "deactivate":
            # All-True keep mask: identity freeze, so the first real
            # cancel/deadline reap never eats a compile mid-traffic.
            self._state = self._jit_deactivate(
                self._state, jnp.ones((self.ecfg.max_slots,), jnp.bool_)
            )
        elif kind == "admit" and not self._paged:
            _, Sb, G = key
            admit = self._jit_admit_sub if self._prefix is not None \
                else self._jit_admit
            out = admit(
                self.params,
                self._state,
                jnp.zeros((G, Sb), jnp.int32),
                jnp.ones((G,), jnp.int32),
                jnp.zeros((G,), jnp.uint32),
                jnp.ones((G,), jnp.float32),
                jnp.zeros((G,), jnp.int32),
                jnp.ones((G,), jnp.float32),
                # max_new: a gen_block row has no first token to end on
                jnp.full((G,), 0 if self.cfg.gen_block else 1, jnp.int32),
                jnp.arange(G, dtype=jnp.int32),
            )
            self._state = out[0]
        elif kind == "admit-prefix" and self._prefix is not None:
            # Warm (prefix-hit) variant: zero prefix KV keeps it a pure
            # compile.
            _, Pb, Sb, G = key
            pkv = transformer.init_cache(self.cfg, G, Pb)
            pref = min(Pb, Smax - 1)
            self._state, _, _, _ = self._jit_admit_prefix(
                self.params,
                self._state,
                jnp.zeros((G, Sb), jnp.int32),
                jnp.full((G,), pref + 1, jnp.int32),
                jnp.full((G,), pref, jnp.int32),
                pkv,
                jnp.zeros((G,), jnp.uint32),
                jnp.ones((G,), jnp.float32),
                jnp.zeros((G,), jnp.int32),
                jnp.ones((G,), jnp.float32),
                jnp.ones((G,), jnp.int32),
                jnp.arange(G, dtype=jnp.int32),
            )
        elif kind == "admit-paged" and self._paged:
            # One paged admission kernel covers cold and warm; warm rows
            # just gather through an all-trash table (pure compile).
            _, Sb, G, W = key
            pref = min(W, Smax - 1)
            self._state, _, _ = self._jit_admit_paged(
                self.params,
                self._state,
                jnp.zeros((G, self._nbs), jnp.int32),
                jnp.zeros((G, Sb), jnp.int32),
                jnp.full((G,), pref + 1, jnp.int32),
                jnp.full((G,), pref, jnp.int32),
                jnp.zeros((G,), jnp.uint32),
                jnp.ones((G,), jnp.float32),
                jnp.zeros((G,), jnp.int32),
                jnp.ones((G,), jnp.float32),
                jnp.ones((G,), jnp.int32),
                jnp.arange(G, dtype=jnp.int32),
                prefix_width=W,
            )
        elif kind == "chunk" and self._chunked:
            _, Sc, G, W = key
            start = min(W, Smax - Sc)
            args = (
                jnp.zeros((G, Sc), jnp.int32),
                jnp.full((G,), start + Sc, jnp.int32),
                jnp.full((G,), start, jnp.int32),
                jnp.zeros((G,), jnp.uint32),
                jnp.ones((G,), jnp.float32),
                jnp.zeros((G,), jnp.int32),
                jnp.ones((G,), jnp.float32),
                jnp.ones((G,), jnp.int32),
                jnp.arange(G, dtype=jnp.int32),
                jnp.ones((G,), jnp.bool_),
            )
            if self._paged:
                # All-trash tables keep the compile a no-op write.
                out = self._jit_admit_chunk_paged(
                    self.params,
                    self._state,
                    jnp.zeros((G, self._nbs), jnp.int32),
                    *args,
                    prefix_width=W,
                )
            else:
                out = self._jit_admit_chunk(
                    self.params, self._state, *args, prefix_width=W,
                )
            self._state = out[0]
        elif kind == "seed-prefix" and self._jit_seed_prefix is not None:
            W = key[1]
            pkv_full = transformer.init_cache(self.cfg, 1, W)
            pkv = {k: pkv_full[k][:, 0] for k in pkv_full}
            self._state = self._jit_seed_prefix(
                self._state, pkv, jnp.int32(0)
            )
        elif kind == "verify" and self._spec:
            # The wide spec wave at rung k: all-trash tables and an
            # all-False wave mask (every scatter routes past the table,
            # every acceptance chain is run=False) keep the compile a
            # pure no-op over real state.
            _, kk = key
            B = self.ecfg.max_slots
            self._state = self._jit_verify(
                self.params,
                self._state,
                jnp.zeros((B, self._nbs), jnp.int32),
                jnp.zeros((B, kk), jnp.int32),
                jnp.zeros((B,), jnp.bool_),
            )[0]
        elif kind == "draft" and self._jit_draft is not None:
            # Draft-model proposal at rung k over its scratch cache —
            # stateless by design, so the warm call touches no engine
            # state at all.
            _, kk = key
            B = self.ecfg.max_slots
            self._jit_draft[kk](
                jnp.zeros((B, self._spec_window), jnp.int32),
                jnp.ones((B,), jnp.int32),
            )
        else:
            raise ValueError(
                f"lattice key {key!r} has no warm recipe for this "
                f"config — shape_lattice.dispatch_keys and _warm_key "
                f"have drifted"
            )
        if self._observe:
            self._note_dispatch(key, -1, time.perf_counter() - t0)  # graftlint: allow(shape-lattice) key IS a lattice key — _warm_key iterates dispatch_keys()

    # --- compile/device observatory taps ------------------------------------

    def _note_dispatch(self, key: Tuple[Any, ...], rid: int,
                       seconds: float) -> None:
        """Observatory tap behind every jit dispatch. Callers are the
        warmup caller or the scheduler thread (same single-writer set as
        the ledger requires); hot sites guard the surrounding
        perf_counter pair on self._observe so the off path stays raw."""
        if self._cledger is not None:
            witness = self._cledger.dispatch(key, rid, seconds)
            if witness is not None:
                logger.warning(
                    "live retrace: variant %s compiled in %.1f ms on the "
                    "serving path (rid=%d)",
                    witness["key"], witness["compile_ms"], rid,
                )
                if self._recorder is not None:
                    self._recorder.record("retrace", rid, witness)
        if self._timing_on:
            self._wave_keys.append(key)
            if self._roof is not None:
                # Enqueue seconds feed the roofline's device component;
                # the host-pre residue is step span minus this.
                self._wave_enq_s += seconds

    def _cow(self, src: int, dst: int, rid: int = -1) -> None:
        """Copy-on-write block copy through the one shared jit variant
        (src/dst are traced scalars). Every call site — warmup and
        live — funnels through here so the ledger sees one "cow" key."""
        if not self._observe:
            self._state = self._jit_cow(
                self._state, jnp.int32(src), jnp.int32(dst)
            )
            return
        t0 = time.perf_counter()
        self._state = self._jit_cow(
            self._state, jnp.int32(src), jnp.int32(dst)
        )
        self._note_dispatch(("cow",), rid, time.perf_counter() - t0)

    # --- scheduler loop -----------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self.ecfg.max_seq_len

    def _admit_key(self, req: _Request) -> Tuple[int, int]:
        """(suffix bucket, prefix bucket) for grouping admissions. Cold
        requests (no prefix cache / no match) key as (full bucket, 0) —
        the pre-prefix grouping exactly. The trie lookup runs once per
        request and pins the matched path; the match is capped at
        plen - 1 so at least one suffix token remains to produce the
        next-token logits. Paged engines use the block-id trie — same
        lookup discipline, but a hit later shares blocks instead of
        gathering KV."""
        index = self._prefix if self._prefix is not None \
            else self._paged_prefix
        if index is None:
            return self._bucket(len(req.tokens)), 0
        if req.prefix_len is None:
            handle = index.lookup(
                req.tokens, max_len=len(req.tokens) - 1
            )
            req.prefix_handle = handle
            req.prefix_len = handle.match_len
            if handle.match_len:
                with self.stats.lock:
                    self.stats.prefix_hits += 1
                    self.stats.prefix_tokens_saved += handle.match_len
            if self._recorder is not None:
                self._recorder.record(
                    "trie-hit" if handle.match_len else "trie-miss",
                    req.rid,
                    {"matched_tokens": handle.match_len,
                     "prompt_tokens": len(req.tokens)},
                )
        if req.prefix_len:
            return (
                self._bucket(len(req.tokens) - req.prefix_len),
                self._bucket(req.prefix_len),
            )
        return self._bucket(len(req.tokens)), 0

    def _drain_pending(self) -> None:  # graftlint: holds(_book)
        while True:
            try:
                self._waiting.append(self._pending.get_nowait())
            except queue.Empty:
                break
        if self._pilot is not None:
            # EDF ordering (stable; no-deadline requests age via a
            # virtual deadline). An already-ordered queue — including
            # every all-FIFO workload — comes back as the same object.
            self._waiting = self._pilot.order_queue(self._waiting)
        with self.stats.lock:
            self.stats.queue_depth = len(self._waiting)

    def _admit_cap(self) -> int:  # graftlint: holds(_book)
        """Admission group-size cap: the pilot's live (power-of-two,
        clamped) value when flying, the static config cap otherwise."""
        if self._pilot is not None:
            return self._pilot.admit_cap()
        return self._max_admit

    def _shed_expired_head(self) -> bool:  # graftlint: holds(_book)
        """EDF pop-time margin re-check (pilot callers only): if the
        head of the admission queue already missed its deadline, fail
        it here — before it claims a slot, pool blocks, or budget a
        viable request could use — and return True so the caller
        re-examines the new head. The boundary-cadence reap still
        sheds mid-queue expiries; this closes the pop-time race where
        a request expires between the reap and its own admission."""
        req = self._waiting[0]
        now = time.perf_counter()
        if req.deadline is None or now < req.deadline:
            return False
        self._waiting.popleft()
        with self.stats.lock:
            self.stats.deadline_expired_total += 1
            self.stats.shed_total += 1
        self._fail_req(
            req,
            f"deadline exceeded after "
            f"{1000.0 * (now - req.submitted_at):.0f} ms in queue",
            kind="deadline",
        )
        self._pilot.note_expired_pop()
        return True

    def _pilot_signals(self) -> Dict[str, float]:  # graftlint: holds(_book)
        """Cumulative signal sample for the pilot's decision windows:
        sched-ledger counters (PILOT implies the ledger, so _sled is
        never None here), the stats SLO mirror, and instantaneous
        queue/slot levels. Keys are the controller's frozen
        signal_snapshot schema (controller.py docstring)."""
        sled = self._sled.snapshot()
        with self.stats.lock:
            budget_dispatches = self.stats.budget_dispatches
            expired = self.stats.deadline_expired_total
            met = self.stats.deadline_met_total
            missed = self.stats.deadline_missed_total
        finished = met + missed
        return {
            "boundaries": sled["dispatch_boundaries"],
            "dispatch_cells": sled["dispatch_cells"],
            "useful_tokens": sled["useful_tokens"],
            "frag_tokens": sled["frag_tokens"],
            "budget_dispatches": budget_dispatches,
            "budget_starved_passes": sled["budget_starved_passes"],
            "budget_offered_tokens": sled["budget_offered_tokens"],
            "budget_used_tokens": sled["budget_used_tokens"],
            "pool_stall_events": sled["pool_stall_events"],
            "preemptions": sled["preemptions"],
            "deadline_expired": expired,
            "goodput": met / finished if finished else 1.0,
            "queue_depth": len(self._waiting),
            "free_slots": len(self._free),
            "spec_drafted": sled["spec"]["drafted_tokens"],
            "spec_accepted": sled["spec"]["accepted_tokens"],
            "roof_backlog_ms": self._roof_backlog_ms(),
            "heal_pressure": (
                self._heal.pressure() if self._heal is not None else 0.0
            ),
        }

    def _roof_backlog_ms(self) -> float:  # graftlint: holds(_book)
        """Predicted roofline cost (ms) of everything still queued —
        the cost-model level the tier router consumes. 0.0 when the
        roof ledger is down (the signal key stays schema-stable)."""
        if self._roof is None:
            return 0.0
        total = 0.0
        for req in self._waiting:
            total += self._roof.predict_request_ms(
                len(req.tokens), req.params.max_new_tokens
            )
        return round(total, 3)

    def _pilot_tick(self) -> None:  # graftlint: holds(_book)
        """One pilot boundary: advance the control loop and mirror any
        new decisions into the flight recorder (the Perfetto decision
        lane in tools/trace_view.py)."""
        decisions = self._pilot.on_boundary(self._pilot_signals)
        if self._recorder is not None:
            for d in decisions:
                self._recorder.record(
                    "pilot", -1,
                    {"knob": d["knob"], "old": d["old"], "new": d["new"],
                     "rationale": d["rationale"],
                     "budget": self._pilot.dispatch_budget(),
                     "max_admit": self._pilot.admit_cap(),
                     "chunk_bias": self._pilot.chunk_bias()},
                )

    def _record_first_dispatch(self, group: List[_Request]) -> None:  # graftlint: holds(_book)
        """Queue-wait accounting: submit -> first dispatch, once per
        request (chunked prefills dispatch the same request many times),
        and how many dispatched waves the device still has ahead of it."""
        now = time.perf_counter()
        wait = 0.0
        n = 0
        ahead = len(self._inflight_waves) + self._sync_depth
        for req in group:
            if req.first_dispatch_at is None:
                req.first_dispatch_at = now
                req.waves_ahead = ahead
                wait += now - req.submitted_at
                n += 1
                if self._sled is not None:
                    self._sled.note_first_dispatch(
                        req.rid, req.submitted_at, now,
                        predicted_ms=(
                            self._roof.predict_request_ms(
                                len(req.tokens),
                                req.params.max_new_tokens,
                            ) if self._roof is not None else 0.0
                        ),
                    )
                if self._recorder is not None:
                    self._recorder.record(
                        "admit", req.rid,
                        {"queue_wait_ms":
                            round(1000.0 * (now - req.submitted_at), 3),
                         "prompt_tokens": len(req.tokens),
                         "prefix_tokens": req.prefix_len or 0},
                    )
        if n:
            with self.stats.lock:
                self.stats.queue_wait_sum += wait
                self.stats.queue_wait_count += n
                self.stats.waves_ahead_sum += ahead * n

    def _dispatch_admits(self) -> List[Tuple[List[_Request], Any, Any, Any]]:  # graftlint: holds(_book)
        """Admit FIFO prefix runs of same-bucket waiting requests as batched
        groups. Dispatches device work only — returns un-synced handles."""
        self._drain_pending()
        admits: List[Tuple[List[_Request], Any, Any, Any]] = []
        last_key: Optional[Tuple[int, int]] = None
        while self._free and self._waiting:
            if self._pilot is not None and self._shed_expired_head():
                continue  # expired head must not displace a viable one
            key = self._admit_key(self._waiting[0])
            # ... and bounded by its tokens (the lattice's own rule)
            max_g = min(self._admit_cap(), len(self._free),
                        shape_lattice.admit_cap(
                            self._max_admit, self.ecfg.max_slots, key[0]))
            group: List[_Request] = []
            reserved = 0
            shed = False
            while (
                len(group) < max_g
                and self._waiting
                and self._admit_key(self._waiting[0]) == key
            ):
                if self._pilot is not None and self._shed_expired_head():
                    shed = True
                    continue  # loop condition re-keys on the new head
                if self._paged:
                    # Pool gate BEFORE the pop: the whole group's owned
                    # blocks must fit (after trie eviction), so dispatch-
                    # time allocation can never fail mid-group. A head
                    # request that cannot fit stays queued — admission
                    # blocks on pool exhaustion, it does not preempt.
                    need = self._owned_need(self._waiting[0])
                    if not self._pool_reserve(reserved + need):
                        break
                    reserved += need
                group.append(self._waiting.popleft())
            if not group:
                if shed:
                    continue  # head expired mid-fill, not a pool stall
                if not self._waiting:
                    break
                with self.stats.lock:
                    self.stats.pool_stalls += 1
                if self._recorder is not None:
                    self._recorder.record(
                        "pool-stall", self._waiting[0].rid,
                        {"waiting": len(self._waiting)},
                    )
                if self._sled is not None:
                    self._sled.note_pool_stall(self._waiting[0].rid)
                break
            try:
                with jax.profiler.TraceAnnotation(
                    "sched.admit", bucket=key[0], group=len(group)
                ):
                    admits.append(self._dispatch_admit_group(group, *key))
                last_key = key
            except Exception as e:  # bad batch must not kill the loop
                logger.exception(
                    "admission failed for requests %s",
                    [r.rid for r in group],
                )
                if not self._heal_requeue_group(group, str(e)):
                    for req in group:
                        slot = req.slot
                        if slot >= 0 and self._slots[slot] is not req \
                                and slot not in self._free:
                            # Popped but never registered.
                            self._free.append(slot)
                        self._fail_req(req, str(e), kind="internal")
        # Bucket-mismatch wait attribution: the engine filled up and the
        # head-of-line request buckets differently from the last group
        # admitted — it waits behind the lattice shape, not raw capacity.
        if (self._sled is not None and last_key is not None
                and self._waiting and not self._free):
            head = self._waiting[0]
            if self._bucket(
                len(head.tokens) - (head.prefix_len or 0)
            ) != last_key[0]:
                self._sled.note_bucket_defer(head.rid)
        return admits

    def _dispatch_admit_group(  # graftlint: holds(_book)
        self, group: List[_Request], Sb: int, Pb: int = 0
    ) -> Tuple[List[_Request], Any, Any, Any]:
        """Build host arrays for `group`, dispatch the fused admission.

        G is padded up to a power of two by replicating the last request
        (identical slot + data, so the duplicate scatter writes are
        harmless), bounding compile variants to log2(max_admit)+1 per
        bucket. Pb > 0 is a prefix-cache WARM group: `Sb` buckets the
        uncached suffix, `Pb` the reused prefix, and the token array
        carries only suffixes (so the jit variant is keyed on
        (Pb, Sb, G) — one compile per prefix bucket, mirroring the
        prompt-bucket discipline)."""
        self._chaos_dispatch("admit", [r.rid for r in group])
        G = len(group)
        Gp = 1
        while Gp < G:
            Gp *= 2
        if self._sled is not None:
            # Waste attribution for this group's static shape: every one
            # of the Gp*Sb offered token-slots is useful suffix, bucket
            # rounding, or pow2 group replication — exactly (the
            # conservation audit holds this to the cell).
            useful = sum(
                len(r.tokens) - (r.prefix_len if Pb else 0) for r in group
            )
            bpad = G * Sb - useful
            gpad = (Gp - G) * Sb
            fam = (
                ("admit-paged", Sb, Gp, Pb) if self._paged
                else ("admit-prefix", Pb, Sb, Gp) if Pb
                else ("admit", Sb, Gp)
            )
            self._sled.note_group(fam, Gp * Sb, useful, bpad, gpad)
            with self.stats.lock:
                self.stats.sched_useful_tokens += useful
                self.stats.sched_bucket_pad_tokens += bpad
                self.stats.sched_group_pad_tokens += gpad
        self._record_first_dispatch(group)
        if not (Pb or self._paged):
            with self.stats.lock:
                self.stats.attn_prefill_tokens[Sb] = \
                    self.stats.attn_prefill_tokens.get(Sb, 0) \
                    + sum(len(r.tokens) for r in group)
        for req in group:
            req.slot = self._free.pop()
            # the admission samples the first token; under gen_block it
            # deposits KV and the first commit pass emits the first
            req.expected, req.passes = (0 if self.cfg.gen_block else 1), 0
        toks = np.full((Gp, Sb), self.cfg.pad_token_id, np.int32)
        plens = np.empty((Gp,), np.int32)
        pref_lens = np.empty((Gp,), np.int32)
        seeds = np.empty((Gp,), np.uint32)
        temps = np.empty((Gp,), np.float32)
        top_ks = np.empty((Gp,), np.int32)
        top_ps = np.empty((Gp,), np.float32)
        max_news = np.empty((Gp,), np.int32)
        slots = np.empty((Gp,), np.int32)
        for i in range(Gp):
            req = group[min(i, G - 1)]
            sp = req.params
            off = req.prefix_len if Pb else 0
            toks[i, : len(req.tokens) - off] = req.tokens[off:]
            plens[i] = len(req.tokens)
            pref_lens[i] = off
            seeds[i] = np.uint32(int(sp.seed) & 0xFFFFFFFF)
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
            max_news[i] = sp.max_new_tokens
            slots[i] = req.slot
        if self._paged:
            # Zero-copy admission: fill each row's block table (shared
            # refs + CoW + fresh allocs — capacity was reserved at group
            # formation), dispatch any copy-on-write block copies FIRST
            # (device ordering pins them before the admission's suffix
            # writes), then run the unified paged admission. Warm rows'
            # prefix KV is gathered from the pool through the table inside
            # the kernel — no host-side gather, no seed scatter.
            cows: List[Tuple[int, int]] = []
            for req in group:
                self._paged_admit_blocks(req, cows, cover=len(req.tokens))
            for src, dst in cows:
                self._cow(src, dst, rid=group[0].rid)
            table = jnp.asarray(self._table_host[slots])
            if self._observe:
                t0 = time.perf_counter()
            self._state, first, first_done = self._jit_admit_paged(
                self.params,
                self._state,
                table,
                jnp.asarray(toks),
                jnp.asarray(plens),
                jnp.asarray(pref_lens),
                jnp.asarray(seeds),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(max_news),
                jnp.asarray(slots),
                prefix_width=Pb,
            )
            if self._observe:
                self._note_dispatch(
                    ("admit-paged", Sb, Gp, Pb), group[0].rid,
                    time.perf_counter() - t0,
                )
            if self._hbm is not None:
                self._hbm.note_workspace(
                    int(toks.nbytes) + Gp * self.cfg.vocab_size * 4
                )
            for req in group:
                self._slots[req.slot] = req
                self._insert_paged_prompt(req, upto=len(req.tokens))
            return group, None, first, first_done
        if Pb:
            # Per-row device gather of the pinned trie path, zero-padded
            # to the prefix bucket and stacked on the batch axis (dim 1
            # of the [L, G, 1, Pb, Hkv*Dh] cache layout).
            rows = [
                self._prefix.gather(group[min(i, G - 1)].prefix_handle, Pb)
                for i in range(Gp)
            ]
            with self.stats.lock:
                # Dense warm admissions MOVE the prefix KV (device
                # gather + scatter); the paged path's zero-copy claim is
                # exactly that this counter stays 0 there.
                self.stats.prefix_seed_copies += G
            prefix_kv = {
                key: jnp.stack([r[key] for r in rows], axis=1)
                for key in rows[0]
            }
            if self._observe:
                t0 = time.perf_counter()
            self._state, first, first_done, writes = self._jit_admit_prefix(
                self.params,
                self._state,
                jnp.asarray(toks),
                jnp.asarray(plens),
                jnp.asarray(pref_lens),
                prefix_kv,
                jnp.asarray(seeds),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(max_news),
                jnp.asarray(slots),
            )
            if self._observe:
                self._note_dispatch(
                    ("admit-prefix", Pb, Sb, Gp), group[0].rid,
                    time.perf_counter() - t0,
                )
        else:
            admit = self._jit_admit_sub if self._prefix is not None \
                else self._jit_admit
            if self._observe:
                t0 = time.perf_counter()
            out = admit(
                self.params,
                self._state,
                jnp.asarray(toks),
                jnp.asarray(plens),
                jnp.asarray(seeds),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(max_news),
                jnp.asarray(slots),
            )
            if self._observe:
                self._note_dispatch(
                    ("admit", Sb, Gp), group[0].rid,
                    time.perf_counter() - t0,
                )
            if self._prefix is not None:
                self._state, first, first_done, writes = out
            else:
                self._state, first, first_done = out
                writes = None
        if self._hbm is not None:
            self._hbm.note_workspace(
                int(toks.nbytes) + Gp * self.cfg.vocab_size * 4
            )
        # Register rows now so an error path can fail them cleanly; the
        # active mirror is armed at boundary processing.
        for req in group:
            self._slots[req.slot] = req
        if self._prefix is not None:
            self._insert_prompt_kv(group, writes, warm=bool(Pb))
        # finals=None marks "every row is an armed admission" — the
        # non-chunked twin of the chunked path's per-row finals list.
        return group, None, first, first_done

    def _insert_prompt_kv(self, group: List[_Request], writes: Dict[str, Any],
                          warm: bool) -> None:
        """Insert each admitted prompt's KV into the prefix trie. `writes`
        holds cache-dtype KV [L, G(padded), 1, S, Hkv*Dh] — full prompts
        for cold groups, uncached suffixes for warm ones (warm block
        spans are rebased by the row's prefix_len; the prefix blocks
        themselves already live in the trie, pinned by the row's handle,
        so get_span is never asked for them). Insertion extends each
        handle's pin over the request's own path — a live slot keeps its
        whole prompt KV evict-proof."""
        for i, req in enumerate(group):
            off = req.prefix_len if warm else 0

            def get_span(s, e, i=i, off=off):
                return {
                    key: writes[key][:, i, :, s - off:e - off]
                    for key in writes
                }

            evicted = self._prefix.insert(
                req.tokens, get_span, handle=req.prefix_handle
            )
            if evicted:
                with self.stats.lock:
                    self.stats.prefix_evictions += evicted
                if self._recorder is not None:
                    self._recorder.record(
                        "trie-evict", req.rid, {"evicted": evicted}
                    )

    # --- paged-KV block bookkeeping ----------------------------------------

    def _pool_reserve(self, n: int) -> bool:
        """True iff n free blocks are (or can be made) available without
        touching live streams — evicts retained trie prefixes LRU-first.
        Frees can only ARRIVE between this check and the allocation
        (single scheduler thread allocates; the fetcher only releases),
        so a True answer cannot go stale."""
        if self._chaos is not None and (
            threading.current_thread() is self._thread
        ) and self._chaos.steal_alloc():
            return False  # injected exhaustion: admission stalls/preempts
        if self._allocator.free_count >= n:
            return True
        if self._paged_prefix is not None:
            evicted = self._paged_prefix.evict_for(n)
            if evicted:
                with self.stats.lock:
                    self.stats.prefix_evictions += evicted
                if self._recorder is not None:
                    self._recorder.record(
                        "trie-evict", -1, {"evicted": evicted}
                    )
        return self._allocator.free_count >= n

    def _secure_blocks(  # graftlint: holds(_book)
        self, n: int, requester: Optional[_Request] = None,
        allow_preempt: bool = True,
    ) -> Optional[List[int]]:
        """Allocate n blocks, freeing capacity as needed: retained trie
        prefixes go first (pure cache, LRU), then — decode must make
        progress — the YOUNGEST live stream is preempted (failed and
        released; its device row zombies harmlessly against the trash
        block until `remaining` runs out). Returns None only when even
        preemption cannot free enough."""
        while True:
            if self._pool_reserve(n):
                got = self._allocator.alloc_many(n)
                if got is not None:
                    return got
            if not allow_preempt:
                return None
            victim = None
            for r in self._slots:
                if r is None or r.finished or r is requester:
                    continue
                at = r.first_dispatch_at or float("inf")
                if victim is None or at > (
                    victim.first_dispatch_at or float("inf")
                ):
                    victim = r
            if victim is None:
                return None
            with self.stats.lock:
                self.stats.preemptions += 1
            if self._sled is not None:
                # Churn = prefill + decode work the victim throws away.
                self._sled.note_preempt(
                    victim.rid, len(victim.tokens) + victim.n_generated
                )
            if self._recorder is not None:
                self._recorder.record(
                    "preempt", victim.rid,
                    {"requester": requester.rid if requester else -1,
                     "need_blocks": n},
                )
            logger.warning(
                "preempting request %d: kv cache pool exhausted",
                victim.rid,
            )
            self._fail_req(
                victim, "preempted: kv cache pool exhausted",
                kind="preempted", retriable=True,
            )

    def _owned_need(self, req: _Request) -> int:
        """Blocks a one-shot admission must ALLOCATE (vs share): the
        prompt's full block count minus the zero-copy-shared fully
        matched blocks. The copy-on-write destination (partial match
        tail) counts as owned."""
        bs = self._kv_block
        total = -(-len(req.tokens) // bs)
        shared = (req.prefix_len or 0) // bs
        return total - shared

    def _paged_admit_blocks(self, req: _Request, cows: List[Tuple[int, int]],  # graftlint: holds(_book)
                            cover: int) -> None:
        """Fill req's block-table row for prompt positions [0, cover):
        fully matched kv blocks are SHARED by refcount (zero-copy), a
        partial-block match tail allocates a copy-on-write destination
        (the device copy is dispatched by the caller before the
        admission kernel), and the remainder is freshly allocated. Every
        resulting block id lands in req.block_ids with exactly one ref
        owned by this request. The caller has already reserved capacity
        via _pool_reserve/_secure_blocks."""
        bs = self._kv_block
        slot = req.slot
        total = -(-cover // bs)
        bids: List[int] = []
        m = req.prefix_len or 0
        if m and self._paged_prefix is not None:
            srcs, partial = self._paged_prefix.plan(req.prefix_handle)
            for i, sbid in enumerate(srcs):
                self._allocator.ref(sbid)
                self._table_host[slot, i] = sbid
                bids.append(sbid)
            if partial is not None:
                dst = self._allocator.alloc()
                if dst is None:
                    raise RuntimeError("kv cache pool exhausted (cow)")
                cows.append((partial, dst))
                self._table_host[slot, len(bids)] = dst
                bids.append(dst)
                with self.stats.lock:
                    self.stats.cow_copies += 1
                if self._recorder is not None:
                    self._recorder.record(
                        "cow", req.rid, {"src": partial, "dst": dst}
                    )
            with self.stats.lock:
                self.stats.zero_copy_admissions += 1
        for i in range(len(bids), total):
            bid = self._allocator.alloc()
            if bid is None:
                raise RuntimeError("kv cache pool exhausted (admit)")
            self._table_host[slot, i] = bid
            bids.append(bid)
        req.block_ids = bids

    def _release_blocks(self, req: _Request) -> None:  # graftlint: holds(_book)
        """Drop every allocator ref req's table row holds (idempotent).
        The row is zeroed so in-flight strays land in the trash block;
        actual block REUSE is ordering-safe because a new owner's
        admission scatter is dispatched after every kernel that could
        still read or scribble the block under this request."""
        if not self._paged or not req.block_ids:
            return
        slot = req.slot
        if 0 <= slot < len(self._slots) and (
            self._slots[slot] is req or self._slots[slot] is None
        ):
            self._table_host[slot, :] = 0
        for bid in req.block_ids:
            self._allocator.unref(bid)
        req.block_ids = []

    def _grow_decode_blocks(self, n: int) -> None:  # graftlint: holds(_book)
        """Before a decode chunk of n steps: extend each active slot's
        block table to cover the chunk's worst-case write positions
        (pos <= plen + expected - 1 by the recycling invariant, so this
        chunk writes at most to plen + expected + n - 2). Slots that
        cannot be grown even after trie eviction + preempting younger
        streams are failed — every active stream owns at least one
        exclusive block, so the loop always makes progress."""
        bs = self._kv_block
        for slot, req in enumerate(self._slots):
            if req is None or req.finished or req.prefilling:
                continue
            maxpos = min(
                len(req.tokens) + req.expected + n - 2,
                self.ecfg.max_seq_len - 1,
            )
            need = min(self._nbs, maxpos // bs + 1)
            have = len(req.block_ids)
            if need <= have:
                continue
            got = self._secure_blocks(need - have, requester=req)
            if got is None:
                self._fail_req(req, "kv cache pool exhausted",
                               kind="capacity", retriable=True)
                continue
            for j, bid in enumerate(got):
                self._table_host[slot, have + j] = bid
            req.block_ids.extend(got)

    def _insert_paged_prompt(self, req: _Request, upto: int) -> None:  # graftlint: holds(_book)
        """Extend the paged trie over req's prompt blocks [0, upto):
        new nodes record (and ref) the pool block the slot's table maps
        their span to — pure host bookkeeping, no device KV moves."""
        if self._paged_prefix is None:
            return
        bs, pb = self._kv_block, self.ecfg.prefix_block
        slot = req.slot

        def block_of(j: int) -> int:
            return int(self._table_host[slot, (j * pb) // bs])

        self._paged_prefix.insert(
            req.tokens[:upto], block_of, handle=req.prefix_handle
        )

    # --- chunked-prefill scheduling ----------------------------------------

    def _chunk_bucket(self, n: int) -> int:
        for b in self._chunk_buckets:
            if n <= b:
                return b
        return self._chunk_buckets[-1]

    def _admit_chunk_slot(self, req: _Request) -> None:  # graftlint: holds(_book)
        """Admit a request into a slot for chunked prefill: register it
        immediately (error paths then fail it through _slots), look up
        the prefix cache, and seed any warm hit's trie KV into the slot
        so chunk 0 starts at the first uncached block."""
        self._record_first_dispatch([req])
        req.slot = self._free.pop()
        req.prefilling = True
        self._slots[req.slot] = req
        if self._paged:
            if self._paged_prefix is not None:
                self._admit_key(req)  # trie lookup + pin; sets prefix_len
                if req.prefix_len:
                    # Warm start is pure table surgery: ref the matched
                    # blocks, CoW the partial tail — chunk 0 then starts
                    # at the first uncached token with zero device KV
                    # traffic. Later chunks allocate their blocks at
                    # dispatch (_dispatch_chunk_group).
                    cows: List[Tuple[int, int]] = []
                    self._paged_admit_blocks(
                        req, cows, cover=req.prefix_len
                    )
                    for src, dst in cows:
                        self._cow(src, dst, rid=req.rid)
                    req.prefill_done = req.prefix_len
            return
        if self._prefix is not None:
            self._admit_key(req)  # trie lookup + pin; sets prefix_len
            if req.prefix_len:
                W = self._bucket(req.prefix_len)
                pkv = self._prefix.gather(req.prefix_handle, W)
                if self._observe:
                    t0 = time.perf_counter()
                self._state = self._jit_seed_prefix(
                    self._state, pkv, jnp.int32(req.slot)
                )
                if self._observe:
                    self._note_dispatch(
                        ("seed-prefix", W), req.rid,
                        time.perf_counter() - t0,
                    )
                req.prefill_done = req.prefix_len
                with self.stats.lock:
                    self.stats.prefix_seed_copies += 1

    def _collect_chunk_work(  # graftlint: holds(_book)
        self, left: int
    ) -> List[Tuple[_Request, int, int, bool, int]]:
        """One budget pass: pop each dispatchable request at most once
        and size its next chunk. Continuing prefills go first (finish
        in-flight prompts before admitting new ones, round-robin via
        the deque); new admissions need a free slot and are gated on a
        cold-size estimate BEFORE the slot pop / trie lookup, so a
        request never ends up half-admitted outside the dispatch.
        Returns (req, Sc, prefix_width, final, chunk_len) rows."""
        C = self._prefill_chunk
        work: List[Tuple[_Request, int, int, bool, int]] = []
        while left > 0:
            if self._prefilling:
                req = self._prefilling.popleft()
                if req.finished:  # failed by an earlier error path
                    continue
            elif self._waiting and self._free:
                if self._pilot is not None and self._shed_expired_head():
                    continue  # expired head must not claim a slot
                req = self._waiting[0]
                rem = len(req.tokens)
                est = C if rem > C else self._chunk_bucket(rem)
                if est > left:
                    break
                if self._paged and not self._pool_reserve(
                    min(est, rem) // self._kv_block + 2
                ):
                    # First chunk's blocks (+ a possible CoW tail) must
                    # fit before the slot pop — admissions stall on pool
                    # exhaustion rather than half-admit.
                    with self.stats.lock:
                        self.stats.pool_stalls += 1
                    if self._recorder is not None:
                        self._recorder.record(
                            "pool-stall", req.rid,
                            {"waiting": len(self._waiting)},
                        )
                    if self._sled is not None:
                        self._sled.note_pool_stall(req.rid)
                    break
                self._waiting.popleft()
                self._admit_chunk_slot(req)
            else:
                break
            start = req.prefill_done
            rem = len(req.tokens) - start
            final = rem <= C
            Sc = self._chunk_bucket(rem) if final else C
            if Sc > left:
                # Keeps FIFO priority for the next dispatch's budget.
                self._prefilling.appendleft(req)
                break
            clen = rem if final else C
            W = 0 if start == 0 else self._bucket(start)
            work.append((req, Sc, W, final, clen))
            left -= Sc
        return work

    def _dispatch_chunk_group(  # graftlint: holds(_book)
        self, rows: List[Tuple[_Request, int, int, bool, int]]
    ) -> Tuple[List[_Request], Any, Any, Any]:
        """Build host arrays for one same-(Sc, W) run of chunk rows and
        dispatch the fused chunk kernel. G pads to a power of two by
        replicating the last row (identical slot + data — duplicate
        scatters are well-defined), mirroring _dispatch_admit_group."""
        self._chaos_dispatch("prefill-chunk", [r[0].rid for r in rows])
        group = [r[0] for r in rows]
        Sc, W = rows[0][1], rows[0][2]
        G = len(rows)
        Gp = 1
        while Gp < G:
            Gp *= 2
        if self._sled is not None:
            # Same exact cell split as _dispatch_admit_group: useful
            # chunk tokens + bucket rounding + pow2 row replication.
            useful = sum(r[4] for r in rows)
            bpad = G * Sc - useful
            gpad = (Gp - G) * Sc
            self._sled.note_group(
                ("chunk", Sc, Gp, W), Gp * Sc, useful, bpad, gpad
            )
            with self.stats.lock:
                self.stats.sched_useful_tokens += useful
                self.stats.sched_bucket_pad_tokens += bpad
                self.stats.sched_group_pad_tokens += gpad
        toks = np.full((Gp, Sc), self.cfg.pad_token_id, np.int32)
        plens = np.empty((Gp,), np.int32)
        starts = np.empty((Gp,), np.int32)
        seeds = np.empty((Gp,), np.uint32)
        temps = np.empty((Gp,), np.float32)
        top_ks = np.empty((Gp,), np.int32)
        top_ps = np.empty((Gp,), np.float32)
        max_news = np.empty((Gp,), np.int32)
        slots = np.empty((Gp,), np.int32)
        finals = np.zeros((Gp,), bool)
        for i in range(Gp):
            req, _, _, final, clen = rows[min(i, G - 1)]
            sp = req.params
            start = req.prefill_done
            toks[i, :clen] = req.tokens[start:start + clen]
            plens[i] = len(req.tokens)
            starts[i] = start
            seeds[i] = np.uint32(int(sp.seed) & 0xFFFFFFFF)
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
            max_news[i] = sp.max_new_tokens
            slots[i] = req.slot
            finals[i] = final
        if self._paged:
            # Append this chunk's pool blocks to each row's table before
            # dispatch (trie eviction, then preemption of younger
            # streams, backstop the allocation — a chunk must never
            # scatter real KV into the trash block).
            bs = self._kv_block
            for req, _, _, _, clen in rows:
                need = min(
                    self._nbs, -(-(req.prefill_done + clen) // bs)
                )
                have = len(req.block_ids)
                if need > have:
                    got = self._secure_blocks(need - have, requester=req)
                    if got is None:
                        raise RuntimeError(
                            "kv cache pool exhausted (prefill chunk)"
                        )
                    for j, bid in enumerate(got):
                        self._table_host[req.slot, have + j] = bid
                    req.block_ids.extend(got)
            if self._observe:
                t0 = time.perf_counter()
            out = self._jit_admit_chunk_paged(
                self.params,
                self._state,
                jnp.asarray(self._table_host[slots]),
                jnp.asarray(toks),
                jnp.asarray(plens),
                jnp.asarray(starts),
                jnp.asarray(seeds),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(max_news),
                jnp.asarray(slots),
                jnp.asarray(finals),
                prefix_width=W,
            )
            self._state, first, first_done = out
            writes = None
        else:
            if self._observe:
                t0 = time.perf_counter()
            out = self._jit_admit_chunk(
                self.params,
                self._state,
                jnp.asarray(toks),
                jnp.asarray(plens),
                jnp.asarray(starts),
                jnp.asarray(seeds),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.asarray(max_news),
                jnp.asarray(slots),
                jnp.asarray(finals),
                prefix_width=W,
            )
            if self._prefix is not None:
                self._state, first, first_done, writes = out
            else:
                self._state, first, first_done = out
                writes = None
        if self._observe:
            # Dense and paged chunk kernels are twins — the mode is fixed
            # per engine, so one "chunk" key family stays unambiguous.
            self._note_dispatch(
                ("chunk", Sc, Gp, W), group[0].rid,
                time.perf_counter() - t0,
            )
        if self._hbm is not None:
            self._hbm.note_workspace(
                int(toks.nbytes) + Gp * self.cfg.vocab_size * 4
            )
        finals_l = []
        for req, _, _, final, clen in rows:
            req.prefill_done += clen
            finals_l.append(final)
            if final:
                req.prefilling = False
                req.expected = 1  # the final chunk samples the first token
            else:
                self._prefilling.append(req)
            if self._paged:
                # Paged trie insertion is host bookkeeping: record the
                # blocks this chunk just filled (no device KV moves).
                self._insert_paged_prompt(req, upto=req.prefill_done)
        if writes is not None:
            self._insert_chunk_kv(rows, writes)
        return group, finals_l, first, first_done

    def _insert_chunk_kv(
        self,
        rows: List[Tuple[_Request, int, int, bool, int]],
        writes: Dict[str, Any],
    ) -> None:
        """Extend the trie with each chunk's freshly-written KV blocks.
        Blocks below the chunk's start already live in the trie (warm
        prefix + earlier chunks, pinned by the request's handle — chunk
        starts are block-aligned by the prefill_chunk % prefix_block
        validation), so get_span only ever covers [start, end)."""
        for i, (req, _, _, _, clen) in enumerate(rows):
            end = req.prefill_done  # already advanced past this chunk
            start = end - clen

            def get_span(s, e, i=i, start=start):
                return {
                    key: writes[key][:, i, :, s - start:e - start]
                    for key in writes
                }

            evicted = self._prefix.insert(
                req.tokens[:end], get_span, handle=req.prefix_handle
            )
            if evicted:
                with self.stats.lock:
                    self.stats.prefix_evictions += evicted

    def _dispatch_prefill_chunks(  # graftlint: holds(_book)
        self,
    ) -> List[Tuple[List[_Request], Any, Any, Any]]:
        """Chunked-prefill admission: pack at most dispatch_token_budget
        prefill tokens into THIS dispatch, then hand back to the decode
        chunk — instead of draining the whole queue. A request's chunks
        are sequential jit calls (chunk k+1 reads chunk k's KV from the
        slot cache), so each budget pass dispatches one chunk per
        request; repeated passes let a lone long prompt still use the
        full budget."""
        self._drain_pending()
        admits: List[Tuple[List[_Request], Any, Any, Any]] = []
        if self._pilot is not None:
            budget = self._pilot.dispatch_budget()
        else:
            budget = self.ecfg.dispatch_token_budget or self._prefill_chunk
        left = budget
        n_chunks = 0
        n_tokens = 0
        while left > 0:
            work = self._collect_chunk_work(left)
            if not work:
                break
            i = 0
            while i < len(work):
                j = i + 1
                while (
                    j < len(work)
                    and j - i < self._admit_cap()
                    and work[j][1:3] == work[i][1:3]
                ):
                    j += 1
                rows = work[i:j]
                try:
                    with jax.profiler.TraceAnnotation(
                        "sched.admit", bucket=rows[0][1], group=len(rows)
                    ):
                        admits.append(self._dispatch_chunk_group(rows))
                    for _, Sc, _, _, clen in rows:
                        left -= Sc
                        n_chunks += 1
                        n_tokens += clen
                except Exception as e:  # bad batch must not kill the loop
                    logger.exception(
                        "chunk dispatch failed for requests %s",
                        [r[0].rid for r in rows],
                    )
                    if not self._heal_requeue_group(
                        [r[0] for r in rows], str(e)
                    ):
                        for req, *_ in rows:
                            self._fail_req(req, str(e), kind="internal")
                i = j
        if n_chunks:
            with self.stats.lock:
                self.stats.prefill_chunks += n_chunks
                self.stats.prefill_chunk_tokens += n_tokens
                self.stats.budget_dispatches += 1
                self.stats.budget_tokens += budget - left
                self.stats.budget_limit = budget
            if self._sled is not None:
                # Starved = the pass ended with prefill work still
                # queued; only then does unspent budget count as
                # fragmentation (an idle-queue surplus is light load,
                # not waste) or mark budget contention for waits.
                starved = bool(
                    self._prefilling or (self._waiting and self._free)
                )
                self._sled.note_budget(budget, budget - left, starved)
                if starved and left > 0:
                    with self.stats.lock:
                        self.stats.sched_frag_tokens += left
        return admits

    # --- speculative decoding (graftspec) ----------------------------------

    def _pick_spec_k(self) -> int:  # graftlint: holds(_book)
        """Current verify rung: the top of the compiled pow2 ladder, or
        the pilot's spec_k knob when flying (the pilot's envelope is
        the ladder itself, so it never leaves compiled variants)."""
        k = self._spec_k_live
        if self._pilot is not None:
            k = self._pilot.spec_k(k)
        if k not in self._spec_rungs:
            k = self._spec_rungs[-1]
        self._spec_k_live = k
        return k

    def _collect_drafts(self, k: int):  # graftlint: holds(_book)
        """Host-side draft proposal for every armed decode row. Returns
        (drafts [B, k] int32, wave [B] bool, n_wave). Rows admitted
        THIS boundary are not yet in _active_host and sit the wave out
        (they join the next one) — per-row sequential keys make the
        emitted stream identical either way. The model drafter runs
        ONE ("draft", k) dispatch for the whole wave; the n-gram
        drafter is pure host arithmetic."""
        B = self.ecfg.max_slots
        drafts = np.zeros((B, k), np.int32)
        wave = self._active_host.copy()
        rows: List[Tuple[int, _Request]] = []
        for slot in np.flatnonzero(wave):
            req = self._slots[slot]
            if req is None or req.finished or req.prefilling:
                wave[slot] = False
                continue
            rows.append((int(slot), req))
        if not rows:
            return drafts, wave, 0
        if self._drafter.uses_model:
            # gen_hist[replayed:] — resurrection folds earlier tokens
            # into req.tokens, so the un-replayed tail IS the history.
            hists = [
                (slot, list(req.tokens) + req.gen_hist[req.replayed:])
                for slot, req in rows
            ]
            if self._observe:
                t0 = time.perf_counter()
            out = self._drafter.draft_batch(hists, k, B)
            if self._observe:
                self._note_dispatch(("draft", k), -1,
                                    time.perf_counter() - t0)
            for slot, _ in rows:
                drafts[slot] = out[slot]
        else:
            for slot, req in rows:
                drafts[slot] = self._drafter.draft(
                    req.tokens, req.gen_hist[req.replayed:], k
                )
        return drafts, wave, len(rows)

    def _dispatch_spec(self):  # graftlint: holds(_book)
        """graftspec scheduler step: admissions exactly as the bucketed
        engine, then — in place of the decode chunk — one host draft
        pass plus ONE wide ("verify", k) dispatch covering every armed
        decode row at k + 1 positions each. Every acceptance-dependent
        piece of bookkeeping (sled attribution, expected resync, block
        rollback, pilot tick) runs at process time
        (_spec_post_process): how many tokens a wave emitted is
        unknowable until its results land, which is also why the spec
        loop never pipelines (_loop_sync)."""
        admits = (
            self._dispatch_prefill_chunks() if self._chunked
            else self._dispatch_admits()
        )
        self._dispatch_wreck = _PendingWave(admits, None, None, None)
        chunk_handles = None
        roster = None
        if admits or self._active_host.any():
            roster = self._roster()
            self._dispatch_wreck = _PendingWave(admits, None, roster, None)
            if self._active_host.any():
                k = self._pick_spec_k()
                drafts, wave, n_wave = self._collect_drafts(k)
                self._spec_wave = (k, wave, n_wave)
                self._chaos_dispatch("decode", self._live_wave_rids())
                # k + 1 worst-case new positions per row; expected is
                # EXACT under spec (resynced to n_generated every
                # boundary), so growth covers pos0 .. pos0 + k and
                # nothing beyond.
                self._grow_decode_blocks(k + 1)
                if self._observe:
                    t0 = time.perf_counter()
                out = self._jit_verify(
                    self.params,
                    self._state,
                    self._table_device(),
                    jnp.asarray(drafts),
                    jnp.asarray(wave),
                )
                if self._observe:
                    self._note_dispatch(("verify", k), -1,
                                        time.perf_counter() - t0)
                self._state = out[0]
                chunk_handles = tuple(out[1:])  # toks, valid, active, counts
                with self.stats.lock:
                    self.stats.decode_dispatches += 1
                    self.stats.decode_steps += 1
                for h in chunk_handles:
                    h.copy_to_host_async()
            for _, _, f, d in admits:
                f.copy_to_host_async()
                d.copy_to_host_async()
        if admits or chunk_handles is not None:
            timing = self._make_timing() if self._timing_on else None
            self._dispatch_wreck = None
            return _PendingWave(admits, chunk_handles, roster, timing,
                                self._wave_epoch)
        self._dispatch_wreck = None
        return None

    def _spec_post_process(self, chunk_data, roster) -> None:  # graftlint: holds(_book)
        """Boundary tail under SPEC=1 (called from _process_boundary
        after _process_chunk delivered the wave's tokens): acceptance
        accounting, per-row rollback, and the observability taps the
        bucketed path runs at dispatch time.

        Acceptance convention: a row that emitted e tokens (1 <= e <=
        k + 1) accepted e - 1 drafts — the drafts that each saved a
        sequential decode step. A draft that matched but fell after a
        terminal token counts rejected: it saved nothing. Under this
        convention accepted + rejected == drafted and emitted +
        rejected == (k + 1) * wave rows hold exactly, which is what
        the sled's conservation audit re-checks every boundary.

        Rollback is pure host bookkeeping: the wave already committed
        all k + 1 positions through the block tables, but positions
        past a row's accepted prefix are dead — the next wave's
        in-layer view scatter rewrites them before any mask exposes
        them — so rejecting is: resync expected to the true
        n_generated and unref the table tail past the new position.
        Freed blocks may be re-owned immediately; the new owner's
        scatter is queued after this wave device-side."""
        wave_info, self._spec_wave = self._spec_wave, None
        emitted = accepted = rejected = drafted = 0
        k = n_wave = 0
        if chunk_data is not None and wave_info is not None:
            k, wave, n_wave = wave_info
            if n_wave:
                valid_h = chunk_data[1]
                emitted = int(valid_h.sum(axis=0)[wave].sum())
                cells = (k + 1) * n_wave
                drafted = k * n_wave
                accepted = emitted - n_wave
                rejected = cells - emitted
                self._spec_drafted += drafted
                self._spec_accepted += accepted
                self._spec_waves += 1
                if self._sled is not None:
                    self._sled.note_group(
                        ("verify", k), cells, emitted, 0, 0,
                        spec_rejected=rejected,
                    )
                    self._sled.note_spec(drafted, accepted, rejected)
                with self.stats.lock:
                    self.stats.sched_useful_tokens += emitted
            bs = self._kv_block
            for slot, req in enumerate(roster or []):
                if req is None or not wave_info[1][slot]:
                    continue
                if req.finished or self._slots[slot] is not req:
                    continue  # completed/failed rows released in full
                req.expected = req.n_generated
                pos_new = len(req.tokens) + req.n_generated - 1
                keep = min(self._nbs, pos_new // bs + 1)
                if len(req.block_ids) > keep:
                    for bid in req.block_ids[keep:]:
                        self._allocator.unref(bid)
                    self._table_host[slot, keep:len(req.block_ids)] = 0
                    del req.block_ids[keep:]
        detail = dict(
            verify_k=k, wave=n_wave, emitted=emitted, accepted=accepted,
            rejected=rejected,
        ) if n_wave else {}
        self._note_boundary(**detail)

    # --- boundary processing -----------------------------------------------

    def _process_admits(  # graftlint: holds(_book)
        self,
        admits: List[Tuple[List[_Request], Any, Any, Any]],
        admit_data: List[Tuple[np.ndarray, np.ndarray]],
        admit_ready: float,
    ) -> None:
        """`admit_ready`: when these admissions' first tokens reached
        the host (_fetch_boundary)."""
        for (group, finals, _, _), (first_h, done_h) in zip(
            admits, admit_data
        ):
            now = time.perf_counter()
            ttft_total = 0.0
            device_wait = 0.0
            n_first = 0
            # finals=None: one-shot admission, every row armed. A chunked
            # group's non-final rows deposited KV only — no token exists
            # for them yet, so they are skipped wholesale here.
            n_armed = (
                len(group) if finals is None
                else sum(1 for f in finals if f)
            )
            for i, req in enumerate(group):
                if finals is not None and not finals[i]:
                    continue
                if req.finished:  # already failed by an error path
                    continue
                slot = req.slot
                if self.cfg.gen_block:
                    # no token yet: the first commit pass brings the
                    # first, and _process_chunk stamps it
                    n_armed -= 1
                    req.admit_ready_at = admit_ready
                    if bool(done_h[i]):  # no budget at all
                        self._complete(req)
                    elif self._slots[slot] is req:
                        self._active_host[slot] = True
                    continue
                first_tok = int(first_h[i])
                req.last_burst_at = now
                req.n_generated = 1
                if self._spec or self._heal is not None:
                    req.gen_hist.append(first_tok)
                if req.first_token_at is None:
                    req.first_token_at = now
                    req.admit_ready_at = admit_ready
                    ttft_ms = 1000.0 * (now - req.submitted_at)
                    ttft_total += ttft_ms
                    device_wait += admit_ready - req.first_dispatch_at
                    n_first += 1
                    req.out.put({"tokens": [first_tok], "ttft_ms": ttft_ms,
                                 "timings": self._timings(req)})
                else:
                    # Resurrected re-admission: the client saw its first
                    # token before the fault — no second TTFT sample.
                    req.out.put({"tokens": [first_tok]})
                if self._heal is not None:
                    self._heal.note_progress(req.rid)
                if bool(done_h[i]):
                    self._complete(req)
                elif self._slots[slot] is req:
                    # Not armed when the slot was already optimistically
                    # recycled (budget spent within in-flight chunks).
                    self._active_host[slot] = True
            with self.stats.lock:
                self.stats.ttft_sum += ttft_total / 1000.0
                self.stats.ttft_count += n_first
                self.stats.device_wait_sum += device_wait
                self.stats.first_token_held_sum += \
                    n_first * (now - admit_ready)
                self.stats.tokens_out += n_armed

    @staticmethod
    def _timings(req: _Request) -> Dict[str, Optional[float]]:
        """The phases of a request's first token, from its five
        instants: what the first stream item, the /generate body and
        the access line carry. At the first token they sum to
        first_token_at - received_at (ttft_ms + executor_wait_ms)
        exactly; a phase whose closing instant the request never
        reached reads None."""
        def ms(a: Optional[float], b: Optional[float]) -> Optional[float]:
            return None if a is None or b is None else 1000.0 * (b - a)

        first, ready = req.first_dispatch_at, req.admit_ready_at
        return {
            "executor_wait_ms": ms(req.received_at, req.submitted_at),
            "queue_wait_ms": ms(req.submitted_at, first),
            "device_wait_ms": ms(first, ready),
            "first_token_held_ms": ms(ready, req.first_token_at),
            "waves_ahead": req.waves_ahead if first is not None else None,
        }

    def _note_chunk_counts(self, chunk_data) -> None:
        """What a decode chunk or a verify wave counted
        on the device (the last value of each, CHUNK_COUNTERS' order;
        came to the host in the boundary's own fetch) into the stats."""
        with self.stats.lock:
            for name, v in zip(self._chunk_counters, chunk_data[3]):
                setattr(self.stats, name, getattr(self.stats, name) + int(v))

    def _process_chunk(self, toks_h, valid_h, active_h, roster) -> None:  # graftlint: holds(_book)
        """toks_h [K, B], valid_h [K, B], active_h [B] — host arrays;
        `roster` is the slot->request snapshot taken when THIS chunk was
        dispatched (the live slot table may have moved on: optimistic
        recycling hands freed slots to new requests before old results
        are read). `valid` marks the emitted tokens: an autoregressive
        row's column is a True-prefix (rows stop and stay stopped within
        a chunk), so its first n_valid rows; under ModelConfig.gen_block
        both arrays are [K, B, Bk] and a slot holds tokens at its commit
        passes only, the first of which is its request's first token
        (`_first_burst`)."""
        blocks = toks_h.ndim == 3
        n_valid = valid_h.sum(axis=(0, 2) if blocks else 0)
        total = 0
        now = time.perf_counter()
        gaps_ms: List[float] = []
        for slot, req in enumerate(roster):
            if req is None or req.finished:
                continue
            n = int(n_valid[slot])
            if n:
                burst = (toks_h[:, slot][valid_h[:, slot]] if blocks
                         else toks_h[:n, slot]).tolist()
                if self._spec or self._heal is not None:
                    req.gen_hist.extend(burst)
                if blocks and req.first_token_at is None:
                    self._first_burst(req, burst, now)
                else:
                    req.out.put({"tokens": burst})
                req.n_generated += n
                total += n
                if self._heal is not None:
                    self._heal.note_progress(req.rid)
                if req.last_burst_at is not None:
                    # Burst-gap ITL: one sample per boundary burst — the
                    # client-visible stall a prefill interloper causes.
                    gaps_ms.append(1000.0 * (now - req.last_burst_at))
                req.last_burst_at = now
            if not active_h[slot]:
                self._complete(req)
        if total or gaps_ms:
            with self.stats.lock:
                self.stats.tokens_out += total
                for g in gaps_ms:
                    self.stats.record_itl_locked(g)

    def _first_burst(self, req: _Request, burst: List[int],  # graftlint: holds(_book)
                     now: float) -> None:
        """A request's first tokens where they come with a decode chunk
        (ModelConfig.gen_block: the first commit pass), stamped and
        counted as _process_admits does an admission's first token. Its
        first_token_held_ms runs from the admission's arrival on the
        host through the denoising passes to this chunk's end."""
        req.first_token_at = now
        ttft_ms = 1000.0 * (now - req.submitted_at)
        req.out.put({"tokens": burst, "ttft_ms": ttft_ms,
                     "timings": self._timings(req)})
        with self.stats.lock:
            self.stats.ttft_sum += ttft_ms / 1000.0
            self.stats.ttft_count += 1
            if req.admit_ready_at is not None:
                self.stats.device_wait_sum += \
                    req.admit_ready_at - req.first_dispatch_at
                self.stats.first_token_held_sum += now - req.admit_ready_at

    def _live_wave_rids(self) -> List[int]:  # graftlint: holds(_book)
        """The rids riding a whole-batch (decode/verify) wave —
        the sticky chaos fault's membership test."""
        return [
            r.rid for r in self._slots
            if r is not None and not r.finished
        ]

    def _chaos_dispatch(self, site: str,
                        rids: Sequence[int] = ()) -> None:
        """Dispatch-failure injection point, active ONLY on the scheduler
        thread — warmup and direct test calls share the dispatch helpers
        and must neither fault nor consume draws (the seeded fault
        sequence is defined over scheduler-loop dispatches alone).
        `rids` is the dispatched wave's membership, for the sticky
        (per-request deterministic) fault."""
        if self._san is not None and (
            threading.current_thread() is self._thread
        ):
            self._san.perturb("dispatch")
        if self._chaos is not None and (
            threading.current_thread() is self._thread
        ):
            try:
                self._chaos.on_dispatch(site, rids)
            except Exception:
                # An injected dispatch fault is about to unwind the
                # scheduler iteration — pin it to the timeline first.
                if self._recorder is not None:
                    self._recorder.record("chaos", -1, {"site": site})
                raise

    def _fail_req(self, req: _Request, msg: str,  # graftlint: holds(_book)
                  kind: str = "internal", retriable: bool = False) -> None:
        """Fail one request with a typed error item (kind in {internal,
        capacity, preempted, cancelled, deadline, draining, shutdown,
        poison}), then finalize it — slot/blocks/trie refs freed, None
        sentinel queued. Idempotent like _complete."""
        if req.finished:
            return
        req.outcome = kind
        req.out.put({"error": msg, "kind": kind, "retriable": retriable})
        self._complete(req)

    def _complete(self, req: _Request) -> None:  # graftlint: holds(_book)
        """Finish a request (idempotent) and free its slot unless the
        slot has already been recycled to a newer request."""
        if self._san is not None:
            self._san.assert_holds("_book")
        if req.finished:
            return
        req.finished = True
        if self._heal is not None:
            self._heal.note_done(req.rid)
        now = time.perf_counter()
        margin_ms = (
            1000.0 * (req.deadline - now) if req.deadline is not None
            else None
        )
        if self._tracer.enabled:
            self._emit_request_spans(req, now, margin_ms)
        if access_log.isEnabledFor(logging.INFO):
            self._log_access(req, now)
        if self._recorder is not None:
            self._recorder.record(
                "terminal", req.rid,
                {"outcome": req.outcome or "ok",
                 "n_generated": req.n_generated},
            )
        with self._rid_lock:
            self._requests.pop(req.rid, None)
        if req.prefix_handle is not None:
            # Unpin the trie path — the slot no longer depends on it, so
            # LRU eviction may reclaim it under budget pressure.
            index = self._prefix if self._prefix is not None \
                else self._paged_prefix
            if index is not None:
                index.release(req.prefix_handle)
            req.prefix_handle = None
        if self._paged:
            self._release_blocks(req)
        slot = req.slot
        if 0 <= slot < len(self._slots) and self._slots[slot] is req:
            self._slots[slot] = None
            self._active_host[slot] = False
            self._free.append(slot)
        with self.stats.lock:
            self.stats.completed += 1
            if req.outcome:
                self.stats.failed_total += 1
            self.stats.record_slo_locked(margin_ms, req.outcome == "")
        # The sentinel goes LAST: a client that has seen its stream end
        # reads stats and slot books that already count it.
        req.out.put(None)

    def _log_access(self, req: _Request, now: float) -> None:
        """The access line: `request {json}` on `seldon_tpu.access`,
        once per finished request whatever its outcome (same
        exactly-once gate as the spans): _timings to the microsecond,
        null for a phase the request never reached."""
        tok = req.first_token_at
        phases = self._timings(req)
        phases["decode_ms"] = None if tok is None else 1000.0 * (now - tok)
        # The engine's running device-side counters as this request
        # ended (EngineStats.sampler_* and attn_kv_*, and moe_* for a
        # model that dispatches tokens to experts): two lines'
        # difference is what the sampler, attention and routing did in
        # decode between them.
        with self.stats.lock:
            phases.update({name: getattr(self.stats, name)
                           for name in self._chunk_counters})
            if self.cfg.n_window_layers:  # long buckets: where prompts went
                phases["attn_prefill_tokens"] = {
                    str(b): n for b, n in
                    sorted(self.stats.attn_prefill_tokens.items())}
        access_log.info("request %s", json.dumps({
            "rid": req.rid,
            "outcome": req.outcome or "ok",
            "prompt_tokens": len(req.tokens) - req.replayed,
            "completion_tokens": req.replayed + req.n_generated,
            "received_unix": round(self._perf_ns(req.received_at) / 1e9, 6),
            **{k: round(v, 3) if isinstance(v, float) else v
               for k, v in phases.items()},
        }))

    def _perf_ns(self, t: float) -> int:
        """perf_counter seconds -> wall-clock ns via the init-time epoch
        pairing (Span start/end are time_ns-domain)."""
        return self._epoch_ns + int((t - self._epoch_perf) * 1e9)

    def _emit_request_spans(self, req: _Request, now: float,  # graftlint: holds(_book)
                            margin_ms: Optional[float]) -> None:
        """Retro-emit the request's lifecycle spans — `unit.executor_wait`
        and one `engine.request` root (both adopting the caller's
        traceparent when one arrived) plus queued/prefill/decode
        children, prefill split into device_wait/first_token_held — from
        the timestamps _Request already carries. Runs exactly once per
        request, gated by the `req.finished` flip in _complete, so
        terminal spans have the same exactly-once guarantee as the
        out-queue sentinel."""
        outcome = req.outcome or "ok"
        attrs: Dict[str, Any] = {
            "rid": req.rid,
            "outcome": outcome,
            "prompt_tokens": len(req.tokens),
            "completion_tokens": req.n_generated,
        }
        if req.prefix_len:
            attrs["prefix_tokens"] = req.prefix_len
        if margin_ms is not None:
            attrs["deadline_margin_ms"] = round(margin_ms, 3)
        root = self._tracer.emit_span(
            "engine.request",
            self._perf_ns(req.submitted_at),
            self._perf_ns(now),
            parent=req.trace,
            attributes=attrs,
            status="OK" if outcome == "ok" else f"ERROR: {outcome}",
        )
        # Received -> submit, the wait for a transport worker thread: a
        # sibling just before engine.request, under the caller's span
        # (with no caller it shares the request's trace as a second root).
        self._tracer.emit_span(
            "unit.executor_wait",
            self._perf_ns(req.received_at),
            self._perf_ns(req.submitted_at),
            parent=req.trace,
            context=None if req.trace is not None else tracing.SpanContext(
                trace_id=root.trace_id, span_id=secrets.token_hex(8)),
            attributes={"rid": req.rid},
        )
        first = req.first_dispatch_at
        self._tracer.emit_span(
            "engine.queued",
            self._perf_ns(req.submitted_at),
            self._perf_ns(first if first is not None else now),
            parent=root,
        )
        if first is not None:
            tok = req.first_token_at
            prefill = self._tracer.emit_span(
                "engine.prefill",
                self._perf_ns(first),
                self._perf_ns(tok if tok is not None else now),
                parent=root,
            )
            if tok is not None:
                # engine.prefill cut where the first token reached the
                # host: before, the device queue behind dispatched waves
                # plus the prefill's own run; after, the hold until the
                # wave it is delivered with has been fetched.
                ready = req.admit_ready_at
                self._tracer.emit_span(
                    "engine.device_wait",
                    self._perf_ns(first),
                    self._perf_ns(ready),
                    parent=prefill,
                    attributes={"waves_ahead": req.waves_ahead},
                )
                self._tracer.emit_span(
                    "engine.first_token_held",
                    self._perf_ns(ready),
                    self._perf_ns(tok),
                    parent=prefill,
                )
                self._tracer.emit_span(
                    "engine.decode",
                    self._perf_ns(tok),
                    self._perf_ns(now),
                    parent=root,
                    attributes={"tokens": req.n_generated},
                )

    def _wave_retire(self, item, fetched_at: Optional[float] = None,  # graftlint: holds(_book)
                     get_s: Optional[float] = None) -> None:
        """Remove one wave from the in-flight registry by identity
        (waves hold unhashable device arrays), tell the depth estimator
        (`fetched_at`, `get_s`: _DepthEstimator.note_retire) and wake a
        scheduler waiting at the depth bound. No-op for waves never
        registered (sync-mode boundaries, partial wrecks)."""
        for i, wave in enumerate(self._inflight_waves):
            if wave is item:
                del self._inflight_waves[i]
                # Only a wave that ran decode steps alone times a step
                # (chunk_handles[0] is the tokens array [steps, slots]).
                steps = (wave.chunk_handles[0].shape[0]
                         if wave.chunk_handles and not wave.admits else 0)
                self._depth_est.note_retire(
                    fetched_at, get_s, bool(self._inflight_waves), steps
                )
                self._room.set()
                return

    def _gather_wrecked(self, pendings=()) -> Dict[int, _Request]:  # graftlint: holds(_book)
        """Every request a wrecked dispatch may have owned: the live
        slot table plus the in-flight pending waves, whose admit groups
        and rosters hold requests already optimistically recycled out of
        `_slots`. Pendings are normalized through _PendingWave so a
        future timing-tuple growth can't silently misalign failure
        accounting. The in-flight wave registry is folded in because it
        is the only complete census of dispatched-but-unretired waves:
        a wave sitting in `_fetch_q`, held by the fetcher pre-epoch-
        check, or built but not yet put by the scheduler is invisible
        to everything else, and the epoch guard will discard it unread
        — a request recycled out of `_slots` into such a wave exists
        nowhere else."""
        live: Dict[int, _Request] = {}
        for req in self._slots:
            if req is not None:
                live[req.rid] = req
        for pending in (*pendings, *self._inflight_waves):
            if pending is None:
                continue
            wave = _PendingWave(*pending)
            for group, _, _, _ in wave.admits:
                for req in group:
                    live[req.rid] = req
            for req in wave.roster or []:
                if req is not None:
                    live[req.rid] = req
        return live

    def _fail_all(self, err: str, pendings=()) -> None:  # graftlint: holds(_book)
        """Fail every live request and reset device + slot state — called
        when a dispatched computation errored (donated buffers are gone)
        and the heal supervisor is off (or the engine is stopping).
        `pendings`: in-flight _PendingWave tuples — requests
        optimistically recycled out of `_slots` live only there."""
        if self._san is not None:
            self._san.assert_holds("_book")
        if self._spec:
            self._spec_wave = None  # descriptor of a wave now wrecked
        if self._recorder is not None:
            self._recorder.record("fail-all", -1, {"error": err[:200]})
        for req in self._gather_wrecked(pendings).values():
            if not req.finished:
                # Engine-wreck failures are retriable: the device state is
                # rebuilt fresh right below and the request did nothing
                # wrong.
                self._fail_req(req, err, kind="internal", retriable=True)
        self._rebuild_device_state()

    def _rebuild_device_state(self) -> None:  # graftlint: holds(_book)
        """Reset device + slot state after a wrecked dispatch: the jit
        functions donated their argument buffers, so whatever the device
        held is gone — fresh slots, fresh paged pool bookkeeping, fresh
        carried state. Every live request must already be failed
        (_fail_all) or detached for resurrection (_prepare_resurrect)
        before this runs."""
        # Invalidate every dispatched-but-unretired boundary: rosters in
        # flight reference pre-rebuild slots, and the async fetcher may
        # surface one AFTER this rebuild. _fetch_loop discards waves
        # whose epoch is stale instead of delivering their tokens twice
        # — safe because the caller gathered every registered wave's
        # requests (_gather_wrecked) before bumping the epoch here.
        self._wave_epoch += 1
        B = self.ecfg.max_slots
        self._slots = [None] * B
        self._free = list(range(B))
        self._active_host[:] = False
        self._prefilling.clear()  # mid-prefill requests failed via _slots
        if self._paged:
            # The sweep above unreffed every live request's blocks into
            # the old allocator; rebuild pool bookkeeping wholesale so it
            # matches the fresh device state (trie refs included).
            from seldon_tpu.servers.block_pool import BlockAllocator
            self._allocator = BlockAllocator(self._num_blocks)
            with self.stats.lock:
                self.stats.pool_gauges = self._allocator.snapshot
            self._table_host[:] = 0
            if self._paged_prefix is not None:
                from seldon_tpu.servers.prefix_cache import \
                    PagedPrefixIndex
                self._paged_prefix = PagedPrefixIndex(
                    block=self.ecfg.prefix_block,
                    kv_block=self._kv_block,
                    allocator=self._allocator,
                )
            # Still-waiting requests may hold handles into the old trie;
            # drop them so admission re-looks-up against the new one.
            for req in self._waiting:
                req.prefix_handle = None
                req.prefix_len = None
                req.block_ids = []
            if self._san is not None:
                # Fresh allocator/trie carry fresh raw locks.
                graftsan.rewrap_pool(self, self._san)
        self._state = self._fresh_state()

    # --- graftheal: supervised fault recovery --------------------------------

    def _fail_or_heal(self, err: str, pendings=()) -> None:  # graftlint: holds(_book)
        """Route a wrecked wave: supervised recovery when the heal
        supervisor is armed and the engine is staying up, else the
        kill-everyone _fail_all sweep — the raw failure path, byte-
        identical to the pre-heal engine whenever HEAL is off."""
        if (self._heal is None or self._stop.is_set()
                or self._draining.is_set()):
            self._fail_all(err, pendings)
            return
        logger.warning("graftheal: wave faulted (%s); recovering", err)
        self._heal_recover(err, pendings)

    def _heal_recover(self, err: str, pendings=()) -> None:  # graftlint: holds(_book)
        """Supervised wave-fault recovery (the graftheal tentpole).
        Instead of failing every innocent in-flight request, classify
        the wrecked cohort through the supervisor — resurrect / pen
        (bisection hold or retry backoff) / poison (deterministically
        faults its wave; fails alone, non-retriable) / exhausted
        (resurrection budget spent) — fail only the convicted, rewrite
        the innocents for replay, then rebuild device state and re-queue
        them at the FRONT of the admission queue in ascending-rid order
        so replays stay ahead of fresh traffic. Deterministic
        per-position sampling keys (models/slot.py) make
        each replayed continuation bit-identical to its unfaulted run,
        greedy and sampled alike."""
        heal = self._heal
        if self._san is not None:
            self._san.assert_holds("_book")
        if self._spec:
            self._spec_wave = None  # descriptor of a wave now wrecked
        now = time.perf_counter()
        live = self._gather_wrecked(pendings)
        # A stale wave still in the in-flight registry at a SECOND
        # fault references requests an earlier
        # recovery already resurrected into _waiting or penned. Those
        # are safely parked, not wrecked: re-convicting them would
        # charge a fault they didn't take, and re-resurrecting would
        # duplicate them in the admission queue.
        parked = {r.rid for r in self._waiting}
        parked.update(r.rid for r in heal.pen_scan())
        verdicts = heal.plan_recovery(
            [rid for rid, r in live.items()
             if not r.finished and rid not in parked],
            now,
        )
        if self._recorder is not None:
            counts: Dict[str, int] = {}
            for v in verdicts.values():
                counts[v] = counts.get(v, 0) + 1
            self._recorder.record(
                "heal", -1,
                {"error": err[:200], "state": heal.state,
                 "mode": heal.mode, **counts},
            )
        # Terminal verdicts and replay rewrites run BEFORE the rebuild:
        # _fail_req unrefs blocks/trie pins into the old pool, which the
        # rebuild then discards wholesale (same ordering as _fail_all).
        queue_front: List[_Request] = []
        pen: List[_Request] = []
        for rid in sorted(verdicts):
            req = live[rid]
            if req.finished:
                continue
            v = verdicts[rid]
            if v == "poison":
                self._fail_req(
                    req,
                    f"quarantined: request deterministically faults its "
                    f"wave ({err[:160]})",
                    kind="poison", retriable=False,
                )
            elif v == "exhausted":
                self._fail_req(
                    req,
                    f"resurrection budget exhausted "
                    f"(heal_max_retries={heal.max_retries}): {err[:160]}",
                    kind="internal", retriable=False,
                )
            elif self._prepare_resurrect(req):
                (pen if v == "pen" else queue_front).append(req)
        self._rebuild_device_state()
        for req in reversed(queue_front):
            self._waiting.appendleft(req)
            heal.note_resurrected()
        for req in pen:
            heal.pen_put(req, now)

    def _prepare_resurrect(self, req: _Request) -> bool:  # graftlint: holds(_book)
        """Detach a wrecked-but-innocent request from the dead device
        state and rewrite it for replay: committed tokens fold into the
        prompt, the token budget shrinks by what the client already
        holds, and the request re-enters the normal prefill/chunked
        admission path as if freshly submitted — landing in an existing
        prefill bucket, so resurrection compiles nothing. Returns False
        when the request reached a terminal state here instead (fully
        delivered, or the folded prompt can no longer be admitted)."""
        fold = req.gen_hist[req.replayed:]
        if fold:
            req.tokens = list(req.tokens) + fold
            req.replayed += len(fold)
            remaining = req.params.max_new_tokens - len(fold)
            if remaining <= 0:
                # The client already holds every token the budget buys.
                self._complete(req)
                return False
            req.params = dataclasses.replace(
                req.params, max_new_tokens=remaining
            )
        if len(req.tokens) > max(self._buckets):
            self._fail_req(
                req,
                f"resurrection impossible: folded prompt "
                f"{len(req.tokens)} exceeds max bucket "
                f"{max(self._buckets)}",
                kind="internal", retriable=True,
            )
            return False
        if self._paged:
            need = -(-len(req.tokens) // self._kv_block)
            if need > self._num_blocks - 1:
                self._fail_req(
                    req,
                    f"resurrection impossible: folded prompt needs "
                    f"{need} kv blocks but the pool holds "
                    f"{self._num_blocks - 1}",
                    kind="internal", retriable=True,
                )
                return False
        # Detach from the wrecked device state. Paged block refs and
        # trie handles just drop — the pool is rebuilt wholesale right
        # after — but a DENSE prefix pin must be released: its trie
        # survives the rebuild, and admission re-looks the prompt up.
        if req.prefix_handle is not None and self._prefix is not None:
            self._prefix.release(req.prefix_handle)
        req.prefix_handle = None
        req.prefix_len = None
        req.block_ids = []
        req.slot = -1
        req.expected = 0
        req.n_generated = 0
        req.prefilling = False
        req.prefill_done = 0
        return True

    def _heal_tick(self) -> None:  # graftlint: holds(_book)
        """Boundary-time heal bookkeeping (scheduler thread, under
        _book): reap cancelled/expired requests parked in the pen —
        they sit in neither _slots nor _waiting, so the regular reap
        cannot see them — then release due pen entries back into the
        admission queue. Draining/stopping flushes the pen wholesale so
        shutdown never strands a parked request."""
        heal = self._heal
        now = time.perf_counter()
        for req in heal.pen_scan():
            if req.finished:
                continue
            if req.cancelled:
                with self.stats.lock:
                    self.stats.cancelled_total += 1
                self._fail_req(
                    req, f"cancelled after {req.replayed} tokens",
                    kind="cancelled",
                )
                heal.pen_drop(req.rid)
            elif req.deadline is not None and now >= req.deadline:
                with self.stats.lock:
                    self.stats.deadline_expired_total += 1
                self._fail_req(
                    req, f"deadline exceeded after {req.replayed} tokens",
                    kind="deadline",
                )
                heal.pen_drop(req.rid)
        flush = self._draining.is_set() or self._stop.is_set()
        for req in heal.pen_take(now, flush=flush):
            self._waiting.appendleft(req)
            heal.note_resurrected()

    def _heal_requeue_group(self, reqs: List[_Request],  # graftlint: holds(_book)
                            err: str) -> bool:
        """Admission-group fault path with the supervisor armed. Unlike
        a wrecked wave, a failed admission group never donated the
        carried state away, so there is no rebuild: release the group's
        slots/blocks/pins back into the LIVE pool and route each
        request through the same supervisor verdicts as any wrecked
        cohort. Returns False (caller falls back to the raw per-group
        _fail_req sweep) when healing is off or the engine is going
        down."""
        if (self._heal is None or self._stop.is_set()
                or self._draining.is_set()):
            return False
        heal = self._heal
        now = time.perf_counter()
        by_rid = {r.rid: r for r in reqs}
        verdicts = heal.plan_recovery(
            [r.rid for r in reqs if not r.finished], now
        )
        if self._recorder is not None:
            counts: Dict[str, int] = {}
            for v in verdicts.values():
                counts[v] = counts.get(v, 0) + 1
            self._recorder.record(
                "heal", -1,
                {"error": err[:200], "state": heal.state,
                 "mode": heal.mode, "site": "admit", **counts},
            )
        queue_front: List[_Request] = []
        for rid in sorted(verdicts):
            req = by_rid[rid]
            if req.finished:
                continue
            slot = req.slot
            if slot >= 0:
                if self._slots[slot] is req:
                    self._slots[slot] = None
                    self._active_host[slot] = False
                    self._free.append(slot)
                elif slot not in self._free:
                    self._free.append(slot)  # popped, never registered
            try:
                self._prefilling.remove(req)
            except ValueError:
                pass
            if self._paged:
                self._release_blocks(req)
            if req.prefix_handle is not None:
                index = self._prefix if self._prefix is not None \
                    else self._paged_prefix
                if index is not None:
                    index.release(req.prefix_handle)
                req.prefix_handle = None
            v = verdicts[rid]
            if v == "poison":
                self._fail_req(
                    req,
                    f"quarantined: request deterministically faults its "
                    f"wave ({err[:160]})",
                    kind="poison", retriable=False,
                )
            elif v == "exhausted":
                self._fail_req(
                    req,
                    f"resurrection budget exhausted "
                    f"(heal_max_retries={heal.max_retries}): {err[:160]}",
                    kind="internal", retriable=False,
                )
            elif self._prepare_resurrect(req):
                if v == "pen":
                    heal.pen_put(req, now)
                else:
                    queue_front.append(req)
        for req in reversed(queue_front):
            self._waiting.appendleft(req)
            heal.note_resurrected()
        return True

    def _fetch_boundary(self, admits, chunk_handles):
        """One boundary's device->host fetch wrapped in the graftheal
        guards: the chaos hang runs INSIDE the watchdog bound (an
        injected hang is observed exactly like a wedged transfer), the
        watchdog raises WatchdogError into the wreck path after
        heal_watchdog_ms, chaos token poisoning corrupts the fetched
        copies, and the NaN/garbage sentinel screens every token id
        before any reaches a client queue. Touches no engine
        bookkeeping — runs under _book on the sync path and lock-free
        on the fetcher thread.

        Still ONE logical fetch (one watchdog bound, one chaos hook, one
        sentinel screen), but the admissions' leaves come to the host
        first and `admit_ready` — third of the returned triple — is
        stamped between: the admission's program precedes the decode
        chunk on the device, so that instant is when a first token
        existed on the host, and what follows it is the wait for the
        chunk it is delivered with (_Request.admit_ready_at)."""
        def fetch():
            if self._chaos is not None:
                self._chaos.maybe_hang()
            admit_data = jax.device_get(  # graftlint: allow(hot-sync, lock-block) deliberate boundary fetch; handles were host-copied via copy_to_host_async at dispatch
                [(f, d) for _, _, f, d in admits]
            )
            admit_ready = time.perf_counter()
            chunk_data = jax.device_get(chunk_handles)  # graftlint: allow(hot-sync, lock-block) second half of the same boundary fetch
            return admit_data, chunk_data, admit_ready

        with jax.profiler.TraceAnnotation("fetch.device_get"):
            if self._heal is not None and self._heal.watchdog_ms > 0:
                admit_data, chunk_data, admit_ready = \
                    self._heal.bounded_fetch(fetch)
            else:
                admit_data, chunk_data, admit_ready = fetch()
        if self._chaos is not None and self._chaos.cfg.nan_inject:
            # device_get host copies may be read-only views; poisoning
            # needs owned arrays (chaos-only path, never hot).
            admit_data = [
                (np.array(f), np.array(d)) for f, d in admit_data
            ]
            if chunk_data is not None:
                chunk_data = tuple(np.array(a) for a in chunk_data)
            self._chaos.poison_fetch(
                [f for f, _ in admit_data]
                + ([chunk_data[0]] if chunk_data is not None else [])
            )
        if self._heal is not None:
            self._heal.check_tokens(
                admit_data, chunk_data, self.cfg.vocab_size
            )
        return admit_data, chunk_data, admit_ready

    def _process_boundary(self, admits, chunk_handles, roster,  # graftlint: holds(_book)
                          timing=None, epoch=None) -> None:
        """Fetch one boundary's device results (one parallel transfer) and
        run host bookkeeping. `timing` is the wave's (dispatch t0,
        variant keys, roof rider) triple when DISPATCH_TIMING is on,
        None otherwise. A wave from a pre-rebuild epoch is discarded
        wholesale (see _PendingWave.epoch)."""
        if epoch is not None and epoch != self._wave_epoch:
            return
        if self._chaos is not None:
            self._chaos.maybe_slow_boundary()  # graftlint: allow(lock-block) deliberate chaos fault: a slow boundary under _book is exactly the race window being tested
        roofing = self._roof is not None and timing is not None
        f0 = time.perf_counter() if roofing else 0.0
        admit_data, chunk_data, admit_ready = self._fetch_boundary(
            admits, chunk_handles
        )
        f1 = time.perf_counter() if roofing else 0.0
        with jax.profiler.TraceAnnotation("fetch.process"):
            self._process_admits(admits, admit_data, admit_ready)
            if chunk_data is not None:
                # counts first: a request this chunk ends writes its
                # access line with the chunk's own steps in the totals
                self._note_chunk_counts(chunk_data)
                self._process_chunk(*chunk_data[:3], roster)
            if self._spec:
                self._spec_post_process(chunk_data, roster)
            self._record_wave_timing(timing)
            if roofing:
                self._roof_note_boundary(timing, f0, f1)
            if self._san is not None:
                self._san.audit(self)
            if self._sled is not None:
                self._sled.audit()
            if self._heal is not None:
                self._heal.note_boundary_ok()

    def _make_timing(self):  # graftlint: holds(_book)
        """Boundary timing token built at dispatch end: (stamp, wave
        keys, roof rider). The rider — (host_pre_s, enqueue_s) relative
        to the step-entry stamp — is the decomposition half the
        roofline joins with the boundary-side stamps; it stays None
        when the roof is down so the tuple costs nothing extra."""
        now = time.perf_counter()
        rider = None
        if self._roof is not None:
            enq = self._wave_enq_s
            rider = (max(0.0, now - self._step_t0 - enq), enq)
            self._wave_enq_s = 0.0
        keys = self._wave_keys
        self._wave_keys = []
        return (now, keys, rider)

    def _roof_note_boundary(self, timing, f0: float,
                            f1: float) -> None:  # graftlint: holds(_book)
        """Roofline boundary tap: close the step decomposition (host-
        pre from the dispatch rider, device = jit enqueue + boundary
        fetch, host-post = bookkeeping after the fetch, overlap = the
        pipelined in-flight gap) against the independently measured
        span, join the wave's keys with the device time, run the
        conservation audit, and mirror one flight-recorder "roof"
        record for the trace_view host/device lanes."""
        t0, keys, rider = timing
        if rider is None:
            return
        f2 = time.perf_counter()
        host_pre_s, enq_s = rider
        fetch_s = max(0.0, f1 - f0)
        gap_s = max(0.0, f0 - t0)
        post_s = max(0.0, f2 - f1)
        device_s = enq_s + fetch_s
        # Span re-derived from the same stamps the components use, so
        # the audit's 1% tolerance is a real accumulation-drift check,
        # not a tautology over one float.
        span_s = host_pre_s + enq_s + max(0.0, f2 - t0)
        self._roof.note_step(
            1000.0 * host_pre_s, 1000.0 * device_s,
            1000.0 * post_s, 1000.0 * span_s,
        )
        if keys:
            self._roof.note_wave(keys, 1000.0 * device_s)
        self._roof.audit()
        if self._recorder is not None:
            self._recorder.record(
                "roof", -1,
                {"pre_ms": round(1000.0 * host_pre_s, 3),
                 "enq_ms": round(1000.0 * enq_s, 3),
                 "gap_ms": round(1000.0 * gap_s, 3),
                 "fetch_ms": round(1000.0 * fetch_s, 3),
                 "post_ms": round(1000.0 * post_s, 3)},
            )

    def _record_wave_timing(self, timing) -> None:  # graftlint: holds(_book)
        """Per-variant boundary timing: the wave's dispatch keys against
        the dispatch -> boundary-processed wall time, measured at the
        deliberate device_get sync. Buckets into EngineStats and mirrors
        one flight-recorder "dispatch" record per key (single-writer:
        the scheduler thread or the fetcher under _book)."""
        if timing is None:
            return
        t0, keys = timing[0], timing[1]
        if not keys:
            return
        ms = 1000.0 * (time.perf_counter() - t0)
        with self.stats.lock:
            for key in keys:
                self.stats.record_variant_locked(
                    compile_ledger.key_str(key), ms
                )
        if self._recorder is not None:
            for key in keys:
                self._recorder.record(
                    "dispatch", -1,
                    {"variant": compile_ledger.key_str(key),
                     "ms": round(ms, 3)},
                )

    def _roster(self) -> List[Optional[_Request]]:  # graftlint: holds(_book)
        """Slot -> request snapshot for THIS wave's decode chunk. Mid-
        prefill requests hold slots but have produced no tokens and are
        device-inactive — masking them out keeps _process_chunk from
        reading their columns (and completing them on active=False) and
        keeps _recycle_budget_spent from charging them decode budget.
        Without chunked prefill no slot is ever mid-prefill, so this is
        exactly list(self._slots)."""
        return [
            None if (r is not None and r.prefilling) else r
            for r in self._slots
        ]

    def _pick_chunk(self) -> int:  # graftlint: holds(_book)
        """Prefill-priority chunk policy: admissions only happen at chunk
        boundaries, so a long chunk is admission LATENCY whenever an
        arrival could actually be admitted. Long chunks are therefore
        reserved for saturation — when fewer than max_admit slots are
        free, a mid-chunk arrival would have waited for completions
        anyway, so the full decode_chunk costs nothing and amortizes the
        host round trip. With real free capacity, boundaries stay at
        the low rung so TTFT tracks the unloaded floor (one engine holds
        both the SLO and the saturated-throughput claims — the policy
        the old chunk-4-vs-64 mode switch approximated by hand). The
        low rung is min_chunk steps at most and as few as still cover
        the host turn (_size_low_rung): every term of a first token's
        wait but its own prefill is a multiple of it."""
        sizes = self._chunk_sizes
        if len(sizes) == 1:
            return sizes[0]
        n_slots = len(self._slots)
        free = sum(1 for r in self._slots if r is None)
        # Thresholds scale with the pool so tiny test engines (where
        # max_admit ~ max_slots) don't read "half empty" as saturated.
        sat = min(self._max_admit, (n_slots + 7) // 8)
        if free < sat:
            idx = len(sizes) - 1  # saturated: nothing admittable mid-chunk
        elif free < n_slots // 4:
            # Mid rung, capped below the top: with only two rungs
            # (e.g. decode_chunk=8, min_chunk=4 dedups to (4, 8)),
            # len//2 would resolve to the TOP rung and near-saturation
            # would silently lose its admission boundaries.
            idx = min(len(sizes) // 2, len(sizes) - 2)
        else:
            idx = 0
        if self._pilot is not None:
            # Deadline-pressure bias moves the occupancy pick at most
            # one rung (pilot never leaves the compiled ladder).
            idx = max(0, min(idx + self._pilot.chunk_bias(),
                             len(sizes) - 1))
        return sizes[idx]

    def _size_low_rung(self) -> None:  # graftlint: holds(_book)
        """Size the low rung, once: the fewest steps whose wave covers
        the host turn (_DepthEstimator.chunk_steps), never more than
        min_chunk, as soon as the estimator has its samples. A rung
        below min_chunk joins the ladder, and its first dispatch
        compiles it: one program, on the serving path, which is why
        the choice is not made again as rows come and go (a step at
        eight live rows is three times one at one row, and a rung that
        followed it would compile under load; under cfg.gen_block the
        rung is whole blocks' passes, slot_rules.whole_blocks), and why it is made in
        the engine's first waves or not at all: an estimator still
        short of samples after four times as many waves has seen the
        device set the pace of fewer than one in four, the host is
        what a wave waits for there, and min_chunk stays. Only
        _loop_async's waves reach the estimator, so the synchronous
        loops keep min_chunk too; a speculative wave never picks a
        chunk."""
        est = self._depth_est
        cap = self._chunk_sizes[0]
        n = est.chunk_steps(cap)
        if n is None:
            if self._wave_seq < 4 * est.steps.maxlen:
                return
            n = cap
        if self.cfg.gen_block:
            # a step is a pass and tokens come at commits: whole blocks.
            # (The rule alone sat on its line here: 2 x 4.7 ms against
            # 4.5 turns of 1.8-2.1 ms, and a run's TTFT read 59 or 86 ms
            # by which side it fell: PERF.md section 6, PR 52.)
            n = slot_rules.whole_blocks(n, cap, self.cfg)
        self._rung_sized = True
        logger.info(
            "low rung sized at wave %d: %d steps a chunk (%d step and %d "
            "turn samples: step %.2f ms, host turn %.2f ms)",
            self._wave_seq, n, len(est.steps), len(est.turns),
            1000.0 * est.step_s(), 1000.0 * est.typical_turn_s(),
        )
        if n == cap:
            return
        if self._paged:
            self._jit_chunks_paged[n] = self._chunk_jit(
                self._paged_chunk_impl, n)
        else:
            self._jit_chunks[n] = self._chunk_jit(self._chunk_impl, n)
        self._chunk_sizes = (n,) + self._chunk_sizes
        if self._cledger is not None:
            self._cledger.declare(("decode", n))

    def _recycle_budget_spent(self, roster: List[Optional[_Request]],  # graftlint: holds(_book)
                              chunk_len: int) -> None:
        """Optimistic slot recycling: `expected` is an upper bound on the
        tokens a row will have produced once every dispatched chunk
        retires, and the device-side `remaining` counter guarantees a row
        NEVER exceeds its budget — so a slot whose budget is provably
        spent can take a new request immediately, without waiting for the
        chunk's results. The next admission's cache scatter is queued
        AFTER the chunk device-side, so ordering is exact. This removes
        the end-of-wave stall where the scheduler used to sync (one full
        host round trip with an idle device) before refilling slots."""
        for slot, req in enumerate(roster):
            if req is None or req.finished:
                continue
            if self.cfg.gen_block:
                # what a row still running will have emitted: exact, or
                # less where a threshold decides faster
                req.passes += chunk_len
                req.expected = slot_rules.tokens_after(
                    req.passes, len(req.tokens) % self.cfg.gen_block,
                    self.cfg)
            else:
                req.expected += max(1, chunk_len)
            if req.expected >= req.params.max_new_tokens:
                if self._slots[slot] is req:
                    self._slots[slot] = None
                    self._active_host[slot] = False
                    self._free.append(slot)
                    if self._paged:
                        # Return the row's blocks now: the just-dispatched
                        # chunk freezes this row at its budget, and any new
                        # owner's admission scatter is queued after it —
                        # the zombie row only touches the trash block.
                        self._release_blocks(req)

    def _drain_and_fail(self, err: str, current=None) -> None:
        """Async-mode failure: fail — or, with the heal supervisor
        armed, resurrect — every request a wrecked boundary may have
        owned. In-flight waves are gathered from the registry (see
        _inflight_waves), NOT by draining _fetch_q: a queue drain here
        raced the scheduler's lock-free puts, so waves dispatched
        between the drain and the epoch bump were never gathered and
        their requests stranded when the fetcher later discarded them
        as stale. Stale waves stay queued; the fetcher retires them.
        `current` is a partial wreck (e.g. _dispatch_wreck) that never
        reached the registry. Called under NO lock; takes _book
        itself."""
        with self._book:
            self._fail_or_heal(
                err, [current] if current is not None else []
            )

    def _fetch_loop(self) -> None:
        """Boundary-fetcher thread: device_get (the wait for the device
        to finish the wave, then the transfer) runs OUTSIDE the
        bookkeeping lock, so the scheduler keeps dispatching while
        results travel; only the host-side processing serializes with
        it. Every wave it takes is retired here, exactly once, and the
        retirement is what lets the scheduler dispatch the next
        (_loop_async): a request's first token waits for the waves in
        flight ahead of its admission, at most the depth less one."""
        while True:
            item = self._fetch_q.get()
            if item is None:
                return
            admits, chunk_handles, roster, timing, epoch = item
            fetched_at = get_s = None
            try:
                with self._book:
                    if epoch != self._wave_epoch:
                        # Dispatched against pre-rebuild device state
                        # while a fault was being healed: the roster
                        # references dead slots and its requests were
                        # already gathered from the registry and
                        # resurrected — fetching or screening it could
                        # only double tokens or re-trip recovery.
                        continue
                if self._san is not None:
                    self._san.perturb("boundary")
                if self._chaos is not None:
                    self._chaos.maybe_slow_boundary()
                roofing = self._roof is not None and timing is not None
                # The chunk is the wave's last program: ready before the
                # device_get, the call costs the transfer alone.
                was_ready = chunk_handles[-1].is_ready()
                f0 = time.perf_counter()
                admit_data, chunk_data, admit_ready = \
                    self._fetch_boundary(admits, chunk_handles)
                f1 = time.perf_counter()
                with self._book, \
                        jax.profiler.TraceAnnotation("fetch.process"):
                    if epoch != self._wave_epoch:
                        continue  # rebuild raced the fetch: stale wave
                    fetched_at = f1
                    get_s = f1 - f0 if was_ready else None
                    self._process_admits(admits, admit_data, admit_ready)
                    if chunk_data is not None:
                        self._note_chunk_counts(chunk_data)
                        self._process_chunk(*chunk_data[:3], roster)
                    self._record_wave_timing(timing)
                    if roofing:
                        self._roof_note_boundary(timing, f0, f1)
                    if self._san is not None:
                        self._san.audit(self)
                    if self._sled is not None:
                        self._sled.audit()
                    if self._heal is not None:
                        self._heal.note_boundary_ok()
            except Exception as e:
                logger.exception("boundary fetch failed")
                fetched_at = None  # the device state is rebuilt: no pace
                self._drain_and_fail(str(e), current=item)
            finally:
                # Retire exactly once on every path — processed, stale-
                # dropped, or faulted (after recovery gathered it).
                with self._book:
                    self._wave_retire(item, fetched_at, get_s)

    def _loop(self) -> None:
        # Software-pipelined scheduler: chunk N+1 is dispatched BEFORE
        # chunk N's results are fetched, so the host fetch (one device
        # round trip) and queue bookkeeping overlap with device compute.
        # This is safe because per-row termination is device-side: rows
        # that finished during chunk N are already frozen (active=False
        # in the carried state) when chunk N+1 runs — the host merely
        # learns about it one boundary late (per-chunk rosters keep
        # attribution exact). Length-bounded rows free their slots at
        # DISPATCH time (_recycle_budget_spent), so the pipeline never
        # drains at wave boundaries; EOS-finished rows free one boundary
        # late (the async loop's depth less one). With async_fetch
        # (single-process), fetches run on a dedicated thread
        # (_fetch_loop) and this loop waits only for room in its
        # pipeline, never on a fetch; multi-process meshes keep the
        # synchronous variant so SPMD dispatch decisions stay
        # timing-independent.
        if self._async_fetch:
            self._loop_async()
        else:
            self._loop_sync()
        if self._profile_active:
            # Window still open at shutdown: flush what was captured.
            try:
                jax.profiler.stop_trace()
            except (RuntimeError, OSError, ValueError):
                # Best-effort flush; no request state rides on it.
                logger.exception("TRACE_PROFILE_N flush failed")
            self._profile_active = False

    def _profile_tick(self) -> None:
        """TRACE_PROFILE_N device-profile window: start a jax.profiler
        capture at the first dispatched boundary, stop it after N — the
        device timeline (tools/profile_decode.py parses the same
        trace.json.gz) lines up against the recorder's wall-clock
        "boundary" records via the profile-start/-stop markers. Called
        from the scheduler loop OUTSIDE _book: profiler start/stop does
        host I/O and must not block bookkeeping."""
        if not self._profile_active:
            try:
                jax.profiler.start_trace(self._profile_dir)
            except (RuntimeError, OSError, ValueError):
                # Best-effort start; disables the window, never a request.
                logger.exception("TRACE_PROFILE_N start failed")
                self._profile_n = 0
                return
            self._profile_active = True
            if self._recorder is not None:
                self._recorder.record(
                    "profile-start", -1, {"dir": self._profile_dir}
                )
        self._profile_count += 1
        if self._profile_count >= self._profile_n:
            self._profile_n = 0  # window done; ticks stop
            self._profile_active = False
            try:
                jax.profiler.stop_trace()
            except (RuntimeError, OSError, ValueError):
                # Best-effort stop; no request state rides on it.
                logger.exception("TRACE_PROFILE_N stop failed")
            if self._recorder is not None:
                self._recorder.record(
                    "profile-stop", -1,
                    {"dir": self._profile_dir,
                     "boundaries": self._profile_count},
                )

    def _table_device(self):  # graftlint: holds(_book)
        """The block tables as a program's argument, from a copy: the
        CPU backend aliases a numpy buffer it is handed (and any backend
        may read it after the call returns), and the next dispatch grows
        and rebinds rows of _table_host before this one's program has
        run. Without the copy a pipelined wave read the tables of the
        wave after it, whenever the host got that far ahead."""
        return jnp.asarray(self._table_host.copy())

    def _dispatch_decode_chunk(self, n: int):  # graftlint: holds(_book)
        """Dispatch one n-step decode chunk. Dense engines call the slab
        kernel unchanged; paged engines first grow each live row's block
        table to cover the chunk's worst-case positions (evicting /
        preempting on exhaustion), then pass the fresh tables alongside
        the donated state."""
        self._chaos_dispatch("decode", self._live_wave_rids())
        if self._paged:
            self._grow_decode_blocks(n)
            if not self._observe:
                return self._jit_chunks_paged[n](
                    self.params, self._state, self._table_device()
                )
            t0 = time.perf_counter()
            out = self._jit_chunks_paged[n](
                self.params, self._state, self._table_device()
            )
            self._note_dispatch(("decode", n), -1,
                                time.perf_counter() - t0)
            return out
        if not self._observe:
            return self._jit_chunks[n](self.params, self._state)
        t0 = time.perf_counter()
        out = self._jit_chunks[n](self.params, self._state)
        self._note_dispatch(("decode", n), -1, time.perf_counter() - t0)
        return out

    def _reap_lifecycle(self) -> None:  # graftlint: holds(_book)
        """Boundary-time lifecycle pass (scheduler thread, under _book):
        chaos disconnects, drain shedding, queued cancel/deadline
        shedding, then in-flight cancel/deadline finalization. Reaped
        in-flight rows are frozen device-side by ONE masked write —
        dispatched only when a reap actually happened, so engines that
        never see a cancel/deadline/drain keep their dispatch sequence
        byte-identical. A request already recycled out of _slots is
        within decode_chunk tokens of its budget and is left to retire
        naturally (its waiter already has every token it will get)."""
        if self._san is not None:
            self._san.perturb("reap")
        if self._chaos is not None:
            rids = [
                r.rid for r in self._slots
                if r is not None and not r.finished
            ]
            victim = self._chaos.pick_disconnect(rids)
            if victim is not None:
                self.cancel(victim)
        if self._draining.is_set():
            self._shed_queued_locked()
        if self._heal is not None:
            self._heal_tick()
        now = time.perf_counter()
        self._drain_pending()
        if self._waiting and any(
            r.cancelled or (r.deadline is not None and now >= r.deadline)
            for r in self._waiting
        ):
            kept: List[_Request] = []
            for req in self._waiting:
                if req.cancelled:
                    with self.stats.lock:
                        self.stats.cancelled_total += 1
                        self.stats.shed_total += 1
                    self._fail_req(req, "cancelled before admission",
                                   kind="cancelled")
                elif req.deadline is not None and now >= req.deadline:
                    with self.stats.lock:
                        self.stats.deadline_expired_total += 1
                        self.stats.shed_total += 1
                    self._fail_req(
                        req,
                        f"deadline exceeded after "
                        f"{1000.0 * (now - req.submitted_at):.0f} ms in "
                        f"queue",
                        kind="deadline",
                    )
                else:
                    kept.append(req)
            self._waiting = collections.deque(kept)
        dead: List[int] = []
        for slot, req in enumerate(self._slots):
            if req is None or req.finished:
                continue
            if req.cancelled:
                with self.stats.lock:
                    self.stats.cancelled_total += 1
                self._fail_req(
                    req, f"cancelled after {req.n_generated} tokens",
                    kind="cancelled",
                )
                dead.append(slot)
            elif req.deadline is not None and now >= req.deadline:
                with self.stats.lock:
                    self.stats.deadline_expired_total += 1
                self._fail_req(
                    req,
                    f"deadline exceeded after {req.n_generated} tokens",
                    kind="deadline",
                )
                dead.append(slot)
        if dead:
            keep = np.ones((self.ecfg.max_slots,), bool)
            keep[dead] = False
            if self._observe:
                t0 = time.perf_counter()
            self._state = self._jit_deactivate(
                self._state, jnp.asarray(keep)
            )
            if self._observe:
                self._note_dispatch(("deactivate",), -1,
                                    time.perf_counter() - t0)

    def _note_boundary(self, **detail) -> None:  # graftlint: holds(_book)
        """A wave is dispatched (under SPEC=1: processed, its acceptance
        known): close the sched ledger's boundary, tick the pilot and
        write the flight recorder's "boundary" record, `detail` plus
        what every wave reports."""
        wf = 0.0
        if self._sled is not None:
            self._sled.note_boundary()
            wf = self._sled.boundary_waste()
            with self.stats.lock:
                self.stats.record_waste_locked(wf)
        if self._pilot is not None:
            self._pilot_tick()
        if self._recorder is not None:
            detail["active"] = int(self._active_host.sum())
            if self._paged:
                detail["pool_free"] = int(self._allocator.free_count)
            if self._sled is not None:
                detail["waste_frac"] = round(wf, 4)
            self._recorder.record("boundary", -1, detail)

    def _dispatch_once(self):  # graftlint: holds(_book)
        """One scheduling step under the bookkeeping lock
        (_dispatch_wave), as a `sched.dispatch` host span on the
        profiler's clock: in a device profile it shows what the
        scheduler was doing in the gap before a program. Metadata: the
        wave's sequence number, the requests it admitted, its decode
        steps, the low rung in force (_size_low_rung) and the pipeline
        depth in force with the two means it follows from
        (_DepthEstimator)."""
        with jax.profiler.TraceAnnotation("sched.dispatch") as span:
            work = self._dispatch_wave()
            if work is not None:
                self._wave_seq += 1
                span.set_metadata(
                    wave=self._wave_seq,
                    admits=sum(len(a[0]) for a in work.admits),
                    # chunk_handles[0] is the tokens array [steps, slots]
                    chunk_steps=work.chunk_handles[0].shape[0]
                    if work.chunk_handles else 0,
                    low_rung=self._chunk_sizes[0],
                    gen_block=self.cfg.gen_block,
                    **self._depth_est.gauges(),
                )
        return work

    def _dispatch_wave(self):  # graftlint: holds(_book)
        """One scheduling step. Returns the
        (admits, chunk_handles, roster, timing) boundary or None if
        idle. On an
        exception, self._dispatch_wreck holds the partial boundary so
        the error path can fail recycled-out-of-_slots requests."""
        self._dispatch_wreck = None
        if self._roof is not None:
            self._step_t0 = time.perf_counter()
        self._reap_lifecycle()
        if self._spec:
            # graftspec: admissions as usual, then a draft pass + one
            # wide verify dispatch instead of the decode chunk.
            return self._dispatch_spec()
        admits = (
            self._dispatch_prefill_chunks() if self._chunked
            else self._dispatch_admits()
        )
        self._dispatch_wreck = _PendingWave(admits, None, None, None)
        if admits or self._active_host.any():
            roster = self._roster()
            self._dispatch_wreck = _PendingWave(admits, None, roster, None)
            if not self._rung_sized:
                self._size_low_rung()
            n = self._pick_chunk()
            out = self._dispatch_decode_chunk(n)
            self._state = out[0]
            chunk_handles = tuple(out[1:])  # toks, valid, active, counts
            with self.stats.lock:
                self.stats.decode_dispatches += 1
                self.stats.decode_steps += n
            self._recycle_budget_spent(roster, n)
            # Start the host copies NOW: the fetcher's device_get then
            # finds data already in flight, so boundary fetches overlap
            # each other instead of serializing one transfer each. Where
            # a chunk computes faster than a transfer (a small model, a
            # remote device) the fetcher paces the pipeline, and the
            # depth estimator runs it deeper (_DepthEstimator).
            for _, _, f, d in admits:
                f.copy_to_host_async()
                d.copy_to_host_async()
            for h in chunk_handles:
                h.copy_to_host_async()
            self._note_boundary(
                admits=sum(len(g) for g, _, _, _ in admits), chunk=n)
            timing = self._make_timing() if self._timing_on else None
            self._dispatch_wreck = None
            return _PendingWave(
                admits, chunk_handles, roster, timing,
                self._wave_epoch,
            )
        self._dispatch_wreck = None
        return None

    def _loop_async(self) -> None:
        # The one bound on how far this loop runs ahead of the device:
        # it dispatches only while fewer than _depth_est.depth() waves
        # are registered in _inflight_waves, and waits on _room, outside
        # _book, otherwise. Everything that retires a wave (the fetcher:
        # processed, epoch-stale or faulted) sets _room, and so does
        # stop(). A wave ahead of an admission is a whole chunk of TTFT,
        # so the depth is what the measured host turn needs and no more.
        waited = False
        while not self._stop.is_set():
            work = None
            try:
                with self._book:
                    full = (len(self._inflight_waves)
                            >= self._depth_est.depth())
                    if full:
                        self._room.clear()
                    else:
                        work = self._dispatch_once()
                    # Register the wave before releasing _book: requests
                    # recycled out of _slots this dispatch live only in
                    # its roster, and a recovery at ANY point before the
                    # fetcher retires it gathers it from this registry
                    # (see _gather_wrecked).
                    if work is not None:
                        self._inflight_waves.append(work)
                        if waited:
                            self._depth_est.note_turn(time.perf_counter())
            except Exception as e:
                logger.exception("engine dispatch failed")
                # _dispatch_once may have recycled requests out of
                # _slots before failing; they live only in its roster.
                with self._book:
                    wreck, self._dispatch_wreck = self._dispatch_wreck, None
                self._drain_and_fail(str(e), current=wreck)
                waited = False
                continue
            waited = full
            if full:
                # _stop is read again after the clear() above: a stop()
                # between the loop's test and the clear() is not lost.
                if not self._stop.is_set():
                    with jax.profiler.TraceAnnotation("sched.wait_depth"):
                        self._room.wait()
            elif work is not None:
                if self._profile_n:
                    self._profile_tick()
                # The wave stays registered until the fetcher retires it.
                self._fetch_q.put(work)
            elif self._pending.empty():
                if self._sled is not None:
                    self._sled.note_idle()
                    with self.stats.lock:
                        self.stats.sched_idle_boundaries += 1
                time.sleep(self.ecfg.idle_sleep_s)

    def _loop_sync(self) -> None:
        """The synchronous scheduler loop (no fetcher thread: a
        multi-process mesh, or async_fetch off): wave N+1 is dispatched
        before wave N's results are fetched, one boundary ahead.
        Slot/free-list/active bookkeeping runs under _book here too:
        drain(), cancel paths and debug_lifecycle_check() read the same
        state from other threads. Requests optimistically recycled out
        of _slots live only in a wave's roster — the one in flight, the
        one just dispatched, or the wreck of a dispatch that raised —
        so the error path fails all three.

        Under SPEC=1 it runs nothing ahead: the next wave depends on
        THIS wave's acceptance three ways — the drafter reads the
        emitted history, _grow_decode_blocks sizes k + 1 positions from
        the resynced expected, and rollback trims the tables the next
        dispatch snapshots. The wide verify dispatch amortizes the round
        trip the look-ahead hides: one sync per up-to-(k+1) tokens per
        row instead of one per chunk."""
        ahead = 0 if self._spec else 1
        pending: Optional[_PendingWave] = None
        while not self._stop.is_set():
            work = None
            try:
                with self._book:
                    self._sync_depth = int(pending is not None)
                    work = self._dispatch_once()
                    due = pending if ahead else work
                    if due is not None:
                        self._process_boundary(*due)
                    pending = work if ahead else None
                    idle = work is None and not self._active_host.any()
                if self._profile_n and work is not None:
                    self._profile_tick()
                # Sleep outside the lock so drain()/cancel() never wait
                # on an idle tick.
                if idle and self._pending.empty():
                    if self._sled is not None:
                        self._sled.note_idle()
                        with self.stats.lock:
                            self.stats.sched_idle_boundaries += 1
                    time.sleep(self.ecfg.idle_sleep_s)
            except Exception as e:  # fail requests, reset, keep serving
                logger.exception("engine iteration failed")
                with self._book:
                    wreck, self._dispatch_wreck = (
                        self._dispatch_wreck, None
                    )
                    self._fail_or_heal(str(e), [pending, work, wreck])
                pending = None
        # Drain the in-flight boundary so stop() doesn't strand requests.
        if pending is not None:
            try:
                with self._book:
                    self._process_boundary(*pending)
            except Exception as e:
                logger.exception("final boundary failed")
                with self._book:
                    self._fail_all(str(e), [pending])
