"""sampler.masked_step_share.chat (layer_metrics/_sampler.py) on the
hand-made unit.log of test_access.py: the window's difference of the
engine's running sampler counters."""
import os
import time

import pytest

import metrics
from test_access import BENCH, N, cell, read, request_line  # noqa: F401  (cell: fixture)

NAME = "sampler.masked_step_share.chat"


def counted(obs, per_request):
    """Window lines whose request i ended with the counters at
    (i + 1) x per_request = (steps, drawn, masked); a lead-in line before
    the window carries other numbers that must not be read."""
    off = time.time() - time.perf_counter()
    out = [request_line(940, obs.t0 + off - 1.0, sampler_steps=7,
                        sampler_drawn_steps=7, sampler_masked_steps=7)]
    for i in range(N):
        s, d, m = ((i + 1) * c for c in per_request)
        out.append(request_line(
            i, obs.t0 + off + 10.0 * (i + 0.5) / N, sampler_steps=1000 + s,
            sampler_drawn_steps=50 + d, sampler_masked_steps=5 + m))
    return out


@pytest.mark.parametrize("per_request,want", [
    ((128, 0, 0), 0.0),          # greedy traffic: every step an argmax
    ((128, 32, 0), 0.0),         # some steps drew, none masked
    ((128, 64, 16), 12.5),       # an eighth of the steps sorted
    ((128, 128, 128), 100.0),
])
def test_share_is_the_window_difference_of_the_counters(cell, per_request, want):
    obs, _, work = cell
    (work / "unit.log").write_text("\n".join(counted(obs, per_request)) + "\n")
    assert read(NAME, obs) == pytest.approx(want)
    import _access
    import _sampler
    d = _access.window_delta(obs, _sampler.FIELDS)
    assert d["sampler_steps"] == (N - 1) * per_request[0]
    assert _sampler.share(obs, "sampler_drawn_steps") == pytest.approx(
        100.0 * per_request[1] / per_request[0])


def test_a_program_without_the_counters_reads_nothing(cell):
    """The parent's access lines have no sampler fields; nor does an
    empty observation, a missing log, or a window in which no step ran."""
    obs, write, work = cell
    assert read(NAME, obs) is None                     # lines without the fields
    assert read(NAME, metrics.Obs()) is None
    (work / "unit.log").write_text("\n".join(counted(obs, (0, 0, 0))) + "\n")
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
