"""sched.steps_per_chunk.chat: the unit's two decode counters over lead-in,
window and tail, the same quotient step.decode_ms divides a chunk by."""
import json
import os

import pytest

import metrics
from conftest import BENCH, ROOT

NAME = "sched.steps_per_chunk.chat"


def read(obs):
    return metrics.load_reader(BENCH, NAME).read(obs)


@pytest.mark.parametrize("steps,dispatches,want", [
    (4000, 1000, 4.0),   # min_chunk while slots are free: the parent, and a short step
    (3812, 3812, 1.0),   # a step that covers the host turn alone
    (2400, 1200, 2.0),
    (4300, 1000, 4.3),   # some chunks ran at a saturated rung
])
def test_steps_over_dispatches(steps, dispatches, want):
    obs = metrics.Obs(decode_steps=steps, decode_dispatches=dispatches)
    assert read(obs) == pytest.approx(want)


@pytest.mark.parametrize("obs", [
    metrics.Obs(), metrics.Obs(decode_steps=0, decode_dispatches=0)])
def test_nothing_dispatched_reads_nothing(obs):
    assert read(obs) is None


def test_step_decode_ms_still_reads_a_step_not_a_chunk():
    """The same quotient divides the chunk program's median execution."""
    import _trace
    for n in (1, 2, 4):
        obs = metrics.Obs(decode_steps=n * 500, decode_dispatches=500,
                          trace={"modules": {_trace.DECODE[0]: {"median_s": n * 0.011}}})
        assert _trace.decode_step_s(obs) == pytest.approx(0.011)


def test_benchmark_json_lists_the_metric_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [e for e in bench["per_layer"] if e["name"] == NAME]
    mod = metrics.load_reader(BENCH, NAME)
    assert entry == [{"name": NAME, "unit": mod.UNIT, "better": "lower",
                      "source": "program_counter", "layer": mod.LAYER,
                      "moves": mod.MOVES}]
    # no `workloads` key: every cell, those appended since too, reports it
    assert all(metrics.applies(entry[0], w["name"]) for w in bench["workloads"])
