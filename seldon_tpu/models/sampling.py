"""Token sampling — temperature / top-k / top-p, fully jittable.

Per-request knobs arrive as arrays and sampling stays inside the jitted
decode loop (no host sync per token — the reference has no generation
path at all, SURVEY.md §5.7). One compiled sampler serves every request
config, and what it EXECUTES is decided on the device from those arrays
(`tier`): a batch in which no row samples takes the argmax and nothing
else; a batch in which some row samples also divides and draws the
Gumbel noise over [B, V]; only a batch in which a row that samples asks
for top-k / top-p pays the full-vocabulary sort, softmax and cumsum.
Within a tier the work is whole-batch (value-level jnp.where per row):
one sampled row among greedy ones costs the draw over all rows.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Host-side request knobs; converted to per-row arrays by the server."""

    # These defaults hold for a caller that builds SamplingParams or
    # sends the in-process dict. Over REST and gRPC a field the request
    # does not name is a proto3 zero today: temperature 0.0 (greedy) and
    # top_p 0.0 (keep the argmax alone) — ROADMAP queue C, item C12.
    temperature: float = 0.7
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    max_new_tokens: int = 128
    seed: int = 0
    # Request TTL in milliseconds, measured from submit. 0 = no per-
    # request deadline (EngineConfig.default_deadline_ms still applies).
    # Expired requests are shed from the queue or finalized early at the
    # next scheduler boundary (servers/engine.py request lifecycle).
    deadline_ms: int = 0
    # W3C traceparent adopting the caller's trace: engine lifecycle spans
    # parent under it so one trace id covers orchestrator -> engine ->
    # streamed tokens. "" = no incoming context (the engine roots its own
    # trace when tracing is on). Rides meta.tags["traceparent"] over the
    # proto transports, same route as deadline_ms.
    traceparent: str = ""
    # time.perf_counter() of this process when the transport handler had
    # parsed the request (runtime/wrapper.py stamps it; same route as
    # traceparent). None = the caller is the transport: the engine uses
    # its own submit time and the request's executor wait reads 0.
    received_at: Optional[float] = None


def _mask_top_k_top_p(
    scaled: jnp.ndarray,  # [B, V] temperature-scaled logits
    top_k: jnp.ndarray,  # [B] int32; 0 => off
    top_p: jnp.ndarray,  # [B] f32; 1.0 => off
) -> jnp.ndarray:
    """Apply top-k + top-p (nucleus) masks. O(V log V) per row (one sort) —
    sample_per_row skips this entirely via lax.cond unless some row that
    samples has one of them on (`tier`). For a row with both off the
    mask is the identity (up to the float cumsum's last ulps of tail
    mass), so it does not matter to such a row whether another switched
    it on."""
    B, V = scaled.shape
    # top-k: mask everything below the k-th largest logit per row.
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)

    # top-p: keep the smallest prefix of the sorted distribution whose
    # cumulative probability covers p; always keep the argmax (so top_p<=0
    # degrades to greedy rather than an all-masked row). The post-top-k
    # sorted view is the first sort with ranks >= k masked — no second
    # O(V log V) sort.
    sorted_logits = jnp.where(
        jnp.arange(V)[None, :] >= k[:, None], -jnp.inf, sorted_desc
    )
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    inside = cum - probs_sorted < jnp.maximum(top_p, 1e-9)[:, None]
    cut = jnp.where(inside, sorted_logits, jnp.inf)
    min_keep = jnp.min(cut, axis=-1, keepdims=True)
    return jnp.where(masked < min_keep, -jnp.inf, masked)


def tier(
    temperature: jnp.ndarray,  # [B] f32; 0 => greedy
    top_k: jnp.ndarray,  # [B] int32; 0 => off
    top_p: jnp.ndarray,  # [B] f32; 1.0 => off
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What this batch asks of the sampler, as two scalar booleans:
    (draws, masks). draws: some row samples (temperature > 0). masks:
    some row that samples has top-k or top-p on. A greedy row never
    needs a mask — its token is argmax(logits) whatever its top_k /
    top_p say — so the knobs of greedy rows switch nothing on. The
    predicates sample_per_row branches on; a decode chunk counts them
    per step (EngineStats.sampler_*)."""
    samples = temperature > 0
    wants_mask = samples & ((top_k > 0) | (top_p < 1.0))
    return jnp.any(samples), jnp.any(wants_mask)


def live_knobs(
    run: jnp.ndarray,  # [B] bool: the row holds a running request
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(temperature, top_k, top_p) with the rows that are not running
    read as greedy with both masks off. A program that steps over a
    whole slab passes these: a freed slot keeps its last request's
    knobs, and one stale sampled row would otherwise hold every later
    batch on the drawn (or masked) tier. Such rows' tokens are
    discarded by the caller."""
    return (jnp.where(run, temperature, 0.0), jnp.where(run, top_k, 0),
            jnp.where(run, top_p, 1.0))


@jax.named_scope("sampler")
def sample_per_row(
    logits: jnp.ndarray,  # [B, V]
    keys: jax.Array,  # [B] PRNG keys (one per row)
    temperature: jnp.ndarray,  # [B] f32; 0 => greedy
    top_k: jnp.ndarray,  # [B] int32; 0 => off
    top_p: jnp.ndarray,  # [B] f32; 1.0 => off
) -> jnp.ndarray:
    """Row-independent sampling: each row draws from its own key, so a
    request's tokens are reproducible from (seed, position) no matter
    what other requests share the batch (continuous-batching
    requirement). Two nested batch-level lax.conds on `tier`: the
    divide and the Gumbel draw run only when some row samples, the
    top-k/top-p sort only when a row that samples asks for it. An
    all-greedy batch (the decode-loop common case) is one argmax,
    whatever top_k / top_p its rows carry.

    Gumbel-argmax over inverse-CDF: argmax(logits/T + g) IS a categorical
    sample, in ONE pass over the logits — the CDF route (softmax + cumsum
    + compare) is 4+ passes over the [B, V] f32 tensor and measured ~0.5
    ms/step at [160, 32k] on v5e vs ~0.15 ms for the Gumbel ALU. Masked
    entries stay -inf through the addition, so the same argmax serves the
    top-k/top-p branch."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draws, masks = tier(temperature, top_k, top_p)

    def draw():
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = jax.lax.cond(
            masks,
            lambda s: _mask_top_k_top_p(s, top_k, top_p),
            lambda s: s,
            logits / temp,
        )
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (V,), dtype=jnp.float32)
        )(keys)
        sampled = jnp.argmax(scaled + gumbel, axis=-1)
        return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(draws, draw, lambda: greedy)


def sample(
    logits: jnp.ndarray,  # [B, V] f32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] f32; 0 => greedy
    top_k: jnp.ndarray,  # [B] int32; 0 => off
    top_p: jnp.ndarray,  # [B] f32; 1.0 => off
) -> jnp.ndarray:
    """Batch sampling from one key (whole-batch generate path)."""
    keys = jax.random.split(key, logits.shape[0])
    return sample_per_row(keys=keys, logits=logits, temperature=temperature,
                          top_k=top_k, top_p=top_p)
