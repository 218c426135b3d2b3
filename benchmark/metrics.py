"""From observations to named metrics.

End-to-end metrics are computed here, by the benchmark itself, from the
client's clock. A per-layer metric is a file of its own,
benchmark/layer_metrics/<name>.py, with UNIT, LAYER, MOVES and
read(obs) -> float | None; which cells report it is BENCHMARK.json's
business. A reader that finds nothing to read returns None and the metric
is left out of the line."""

from __future__ import annotations

import importlib.util
import math
import os
import sys
from typing import Any, Callable, Dict, List, Optional

import stats

MIN_TOKENS_FOR_TPOT = 8


class Obs(dict):
    """What a run observed; readers take what they need by attribute.
    A missing observation reads as None."""

    def __getattr__(self, k: str) -> Any:
        return self.get(k)


def ttft_ms(results) -> List[float]:
    """First token seen minus time due, per sampled request; a failed or
    refused request counts as +inf."""
    return [1000.0 * (r.first - r.due) if r.ok and r.first is not None
            else stats.INF for r in results]


def tpot_ms(results) -> List[float]:
    """(last token - first token) / (tokens - 1) per sampled request that
    delivered at least MIN_TOKENS_FOR_TPOT tokens; a failed one is +inf."""
    out = []
    for r in results:
        if not r.ok:
            out.append(stats.INF)
        elif len(r.tokens) >= MIN_TOKENS_FOR_TPOT:
            out.append(1000.0 * (r.last - r.first) / (len(r.tokens) - 1))
    return out


END_TO_END: Dict[str, Callable[[Obs], Optional[float]]] = {
    "ttft_mid80_ms": lambda o: stats.trimmed_mean(ttft_ms(o.samples), 0.1),
    "tpot_mid80_ms": lambda o: stats.trimmed_mean(tpot_ms(o.samples), 0.1),
    "setup_s": lambda o: o.setup_s,
}


def applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_reader(bench_dir: str, name: str):
    folder = os.path.join(bench_dir, "layer_metrics")
    if folder not in sys.path:
        sys.path.insert(0, folder)  # readers may share helpers (_trace.py)
    path = os.path.join(folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def end_to_end(bench: Dict, workload: str, obs: Obs) -> Dict[str, Dict]:
    out = {}
    for e in bench["end_to_end"]:
        if applies(e, workload):
            v = END_TO_END[e["name"]](obs)
            if v is not None:
                out[e["name"]] = {"value": v, "unit": e["unit"]}
    return out


def per_layer(bench: Dict, bench_dir: str, workload: str, obs: Obs) -> Dict[str, Dict]:
    out = {}
    for e in bench["per_layer"]:
        if not applies(e, workload):
            continue
        mod = load_reader(bench_dir, e["name"])
        if (mod.UNIT, mod.LAYER, mod.MOVES) != (e["unit"], e["layer"], e["moves"]):
            raise ValueError(f"layer_metrics/{e['name']}.py disagrees with "
                             f"BENCHMARK.json on unit, layer or moves")
        v = mod.read(obs)
        if v is not None and finite(v):
            out[e["name"]] = {"value": v, "unit": e["unit"]}
    return out
