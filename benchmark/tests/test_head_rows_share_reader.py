"""diff.head_rows_share.chat on the hand-made unit.log of test_access.py:
the window's growth of the engine's diff_rows_scored over that of its
passes x slots x block_length."""
import json
import os
import time

import pytest

import metrics
from test_access import BENCH, N, cell, read, request_line  # noqa: F401  (cell: fixture)

NAME = "diff.head_rows_share.chat"
SLOTS, BK = 64, 4


def a_block_model(obs):
    return metrics.Obs(obs, cfg={"assumed": {"block_length": BK}}, slots=SLOTS)


def counted(obs, passes, rows):
    """Window lines whose request i ended after (i + 1) x `passes` passes
    that scored (i + 1) x `rows` rows; a lead-in line before the window
    carries other numbers that must not be read."""
    off = time.time() - time.perf_counter()
    out = [request_line(940, obs.t0 + off - 1.0, sampler_steps=7,
                        diff_rows_scored=7 * SLOTS * BK)]
    out += [request_line(i, obs.t0 + off + 10.0 * (i + 0.5) / N,
                         sampler_steps=1000 + (i + 1) * passes,
                         diff_rows_scored=5000 + (i + 1) * rows)
            for i in range(N)]
    return out


@pytest.mark.parametrize("passes,rows,want", [
    (300, 300 * 8 * BK, 12.5),            # every pass scored the rung's 8 slots
    (300, 276 * 8 * BK, 11.5),            # 8 % of the passes scored nothing
    (300, 300 * SLOTS * BK, 100.0),       # every pass scored every slot
    (300, 0, 0.0),
])
def test_share_is_rows_scored_over_rows_of_the_passes(cell, passes, rows, want):
    obs, _, work = cell
    (work / "unit.log").write_text("\n".join(counted(obs, passes, rows)) + "\n")
    assert read(NAME, a_block_model(obs)) == pytest.approx(want)


def test_a_program_without_the_counter_reads_nothing(cell):
    """The parent's access lines carry the passes and no rows scored; nor
    does another model's observation hold a block length, nor a window
    without a pass anything to divide by."""
    obs, _, work = cell
    off = time.time() - time.perf_counter()
    (work / "unit.log").write_text("\n".join(
        request_line(i, obs.t0 + off + 10.0 * (i + 0.5) / N,
                     sampler_steps=1000 + 300 * i) for i in range(N)) + "\n")
    assert read(NAME, a_block_model(obs)) is None
    (work / "unit.log").write_text(
        "\n".join(counted(obs, 300, 300 * 8 * BK)) + "\n")
    assert read(NAME, a_block_model(obs)) == pytest.approx(12.5)
    assert read(NAME, metrics.Obs(obs, slots=SLOTS)) is None          # no block
    assert read(NAME, metrics.Obs(obs, slots=SLOTS, cfg={"assumed": {}})) is None
    assert read(NAME, metrics.Obs(a_block_model(obs), slots=None)) is None
    (work / "unit.log").write_text("\n".join(counted(obs, 0, 0)) + "\n")
    assert read(NAME, a_block_model(obs)) is None                     # no pass
    os.remove(work / "unit.log")
    assert read(NAME, a_block_model(obs)) is None


def test_benchmark_json_lists_the_metric_for_its_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == NAME]
    mod = metrics.load_reader(BENCH, NAME)
    assert entry == [{"name": NAME, "unit": mod.UNIT, "better": "lower",
                      "source": "program_counter", "layer": mod.LAYER,
                      "moves": mod.MOVES, "workloads": ["sdar.chat"]}]
