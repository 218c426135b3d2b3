"""The one general traffic generator. A traffic mix is a JSON file of
parameters (benchmark/traffic/<name>.json, optionally overridden per cell
by benchmark/cells/<workload>.json); this module turns it into requests.

Every run of a cell offers the same work: the multiset of (prompt,
output) lengths is the N quantile midpoints of the file's distributions,
paired by a shuffle that never sees --seed. The seed orders the pairs,
places the arrivals and draws the token ids: two seeds are two traces of
the same work.

Where a cell's file says `"placement": "ring"` the order and the places
are part of the work too (`ring`): the window is one turn of a fixed
ring of requests, the seed says at which of them the turn begins, and
the lead-in and the tail are the ring's requests before and after. Which
request meets which is then the same for every seed. That is for a cell
in which a live row is a large part of a step, where which requests
overlap moved TPOT more from seed to seed than any change would (PERF.md
section 2)."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import random
import statistics
from typing import Dict, List, Optional, Tuple

_PAIRING_SEED = 20260927  # fixed: the pairing is part of the work, not of the run


@dataclasses.dataclass
class Request:
    idx: int
    phase: str            # "lead" | "window" | "tail" ("setup": warm-up, probes)
    prompt_len: int
    max_new: int
    due: Optional[float]  # seconds relative to the window's start; None in set-up
    prompt_ids: List[int] = dataclasses.field(default_factory=list, repr=False)


def load_traffic(bench_dir: str, traffic: str, workload: str,
                 rehearse: bool = False) -> Dict:
    """The traffic file, with the cell's own overrides on top (and the
    file's `rehearse` block on top of that for CPU rehearsals)."""
    with open(os.path.join(bench_dir, "traffic", traffic + ".json")) as f:
        spec = json.load(f)
    cell = os.path.join(bench_dir, "cells", workload + ".json")
    if os.path.exists(cell):
        with open(cell) as f:
            spec.update(json.load(f))
    if rehearse:
        spec.update(spec.get("rehearse", {}))
    return spec


def quantile_midpoints(dist: Dict, n: int) -> List[int]:
    """The n quantile midpoints ((i + 0.5) / n) of a length distribution,
    rounded to whole tokens and clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(
                dist["sigma"] * statistics.NormalDist().inv_cdf(q))
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def multiset(spec: Dict, n: int) -> List[Tuple[int, int]]:
    """n (prompt, output) pairs: quantile midpoints of each distribution,
    paired by a fixed shuffle, the output clipped so that the pair fits
    the cell's window. The same for every seed."""
    prompts = quantile_midpoints(spec["prompt_tokens"], n)
    outs = quantile_midpoints(spec["output_tokens"], n)
    random.Random(_PAIRING_SEED + n).shuffle(outs)
    window = int(spec["window_tokens"])
    return [(p, max(1, min(o, window - p - 1))) for p, o in zip(prompts, outs)]


def seeded_order(pairs: List[Tuple[int, int]], seed: int,
                 stratify: int) -> List[Tuple[int, int]]:
    """A seeded order of the multiset. The pairs, sorted by prompt
    length, are dealt into blocks of `stratify` that each hold one pair
    from every quantile; blocks and the pairs inside them are shuffled.
    Any run of consecutive requests then carries nearly the same work,
    whatever the seed. Fewer than two blocks' worth (a lead-in) is
    plainly shuffled."""
    rng = random.Random(seed)
    pairs = list(pairs)
    if len(pairs) >= 2 * stratify:
        ranked = sorted(pairs)
        n_blocks = len(ranked) // stratify
        strata = [ranked[s * n_blocks:(s + 1) * n_blocks]
                  for s in range(stratify)]
        rest = ranked[stratify * n_blocks:]
        for s in strata:
            rng.shuffle(s)
        blocks = [[s[b] for s in strata] for b in range(n_blocks)]
        for b in blocks:
            rng.shuffle(b)
        rng.shuffle(blocks)
        out = [p for b in blocks for p in b]
        for p in rest:  # what did not fill a block lands at seeded places
            out.insert(rng.randrange(len(out) + 1), p)
        return out
    rng.shuffle(pairs)
    return pairs


def arrival_times(n: int, span: float, rng: random.Random) -> List[float]:
    """n due times in [0, span), sorted: the span is cut into n equal
    slots and each holds one arrival, at a seeded uniform place inside
    it. A fixed count on a random schedule, like n sorted uniforms, but
    without their clumps: at some tens of requests a window, which clump
    meets which long request differs more from seed to seed than any
    change of the program would (PERF.md section 6), and bursts are a
    traffic mix of their own."""
    return [(i + rng.random()) * span / n for i in range(n)]


def ring(spec: Dict, n: int) -> Tuple[List[Tuple[int, int]], List[float]]:
    """The window's n requests as one turn of a ring that never sees
    --seed: the multiset in a stratified order, and each request's place
    inside its slot of 1/rate seconds."""
    rng = random.Random(_PAIRING_SEED + 7 * n)
    pairs = seeded_order(multiset(spec, n), rng.randrange(1 << 30),
                         int(spec["stratify"]))
    return pairs, [rng.random() for _ in range(n)]


def ring_loop(spec: Dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """`open_loop` for `"placement": "ring"`: slot j of the run (j < 0 the
    lead-in, j >= n the tail) holds request (j + k) % n of the ring at
    the ring's own place inside the slot, k drawn from the seed. Every
    window request has the same neighbours at the same distances for
    every k; the seed turns the ring and draws the token ids."""
    rng = random.Random(seed)
    n = int(round(float(spec["rate_rps"]) * seconds))
    slot = float(seconds) / n
    pairs, places = ring(spec, n)
    k = rng.randrange(n)
    out: List[Request] = []
    for j in range(-int(float(spec["lead_in_s"]) / slot),
                   n + int(float(spec["tail_s"]) / slot)):
        (p, o), u = pairs[(j + k) % n], places[(j + k) % n]
        phase = "lead" if j < 0 else "window" if j < n else "tail"
        out.append(Request(len(out), phase, p, o, (j + u) * slot,
                           [rng.randrange(vocab) for _ in range(p)]))
    return out


def open_loop(spec: Dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Lead-in, window and tail of a cell, sorted by due time. The window
    holds exactly round(rate * seconds) requests; the lead-in and the
    tail are the same mix at the same rate with their own fixed
    multisets (`"placement": "ring"`: the ring's neighbours, `ring_loop`).
    Only window requests are sampled."""
    if spec.get("placement", "seeded") == "ring":
        return ring_loop(spec, seed, seconds, vocab)
    if spec.get("placement", "seeded") != "seeded":
        raise ValueError(f"unknown placement {spec['placement']!r}")
    rate = float(spec["rate_rps"])
    rng = random.Random(seed)
    out: List[Request] = []
    phases = (("lead", float(spec["lead_in_s"]), -float(spec["lead_in_s"])),
              ("window", float(seconds), 0.0),
              ("tail", float(spec["tail_s"]), float(seconds)))
    for phase, span, start in phases:
        n = int(round(rate * span))
        pairs = seeded_order(multiset(spec, n), rng.randrange(1 << 30),
                             int(spec["stratify"]))
        dues = arrival_times(n, span, rng)
        for (p, o), d in zip(pairs, dues):
            out.append(Request(len(out), phase, p, o, start + d,
                               [rng.randrange(vocab) for _ in range(p)]))
    return out


def buckets_reached(spec: Dict, seconds: float, buckets: List[int]) -> List[int]:
    """Prompt buckets of the engine that any request of this cell can
    land in (lead-in, window and tail)."""
    reached = set()
    spans = (spec["lead_in_s"], seconds, spec["tail_s"])
    if spec.get("placement") == "ring":  # lead-in and tail are the window's own
        spans = (seconds,)
    for span in spans:
        for p, _ in multiset(spec, int(round(float(spec["rate_rps"]) * span))):
            reached.add(next((b for b in sorted(buckets) if p <= b), max(buckets)))
    return sorted(reached)
