"""attn.kv_write_share.chat on the hand-made unit.log of test_access.py:
the window's difference of the engine's two KV row counters."""
import os
import time

import pytest

import metrics
from test_access import BENCH, N, cell, read, request_line  # noqa: F401  (cell: fixture)

NAME = "attn.kv_write_share.chat"


def counted(obs, slots, written):
    """Window lines whose request i ended with the counters at (i + 1) x
    (slots, written); a lead-in line before the window carries other
    numbers that must not be read."""
    off = time.time() - time.perf_counter()
    out = [request_line(940, obs.t0 + off - 1.0, attn_kv_rows_slots=7,
                        attn_kv_rows_written=7)]
    for i in range(N):
        out.append(request_line(
            i, obs.t0 + off + 10.0 * (i + 0.5) / N,
            attn_kv_rows_slots=10**9 + (i + 1) * slots,
            attn_kv_rows_written=10**7 + (i + 1) * written))
    return out


@pytest.mark.parametrize("slots,written,want", [
    (2048, 2048, 100.0),  # the scatter: a row of every slot, 32 layers x 64
    (2048, 55, 2.685546875),  # 1.72 live rows of 64 a step
    (2048, 0, 0.0),  # steps ran with no live row
])
def test_share_is_the_window_difference_of_the_counters(cell, slots, written, want):
    obs, _, work = cell
    (work / "unit.log").write_text("\n".join(counted(obs, slots, written)) + "\n")
    assert read(NAME, obs) == pytest.approx(want)


def test_a_program_without_the_counters_reads_nothing(cell):
    """The parent's access lines have no such fields; nor does an empty
    observation, a missing log, or a window in which no step ran."""
    obs, write, work = cell
    assert read(NAME, obs) is None                     # lines without the fields
    assert read(NAME, metrics.Obs()) is None
    (work / "unit.log").write_text("\n".join(counted(obs, 0, 0)) + "\n")
    assert read(NAME, obs) is None                     # no step in the window
    os.remove(work / "unit.log")
    assert read(NAME, obs) is None


def test_benchmark_json_lists_the_metric_for_every_cell():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == NAME]
    mod = metrics.load_reader(BENCH, NAME)
    assert entry == [{"name": NAME, "unit": mod.UNIT, "better": "lower",
                      "source": "program_counter", "layer": mod.LAYER,
                      "moves": mod.MOVES}]
