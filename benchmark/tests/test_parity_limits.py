"""The parity limits of the two configurations against the readings they
were set from (PR 26, on the chip at the cells' own sizes: 32 and 14
weight seeds, the engine's tokens and the int4-grid control's): every
sound reading passes, every control reading is rejected. A later change
of a limit meets these readings first."""
import json
import os

import pytest

import reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "tests", "data", "parity_readings_pr26.json")) as f:
    READINGS = json.load(f)


def _limits(config):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        return reference.limits(json.load(f)["parity"])


def _passes(lim, share_at_075, widest, over_45):
    """The criterion on a reading's numbers, which were read at epsilon
    0.75 and, for the sparse model, epsilon_all 4.5 (the first readings
    kept the widest gap only: one position beyond it at the least)."""
    assert lim["epsilon"] == 0.75 and lim["epsilon_all"] in (0.75, 4.5)
    if over_45 is None or lim["epsilon_all"] == 0.75:
        over_45 = int(widest > lim["epsilon_all"])
    return share_at_075 >= lim["min_share_within"] and over_45 <= lim["max_over_epsilon_all"]


@pytest.mark.parametrize("config,seeds", [("mixtral-8x7b", 32), ("mistral-7b-v0.3", 14)])
def test_sound_readings_pass_and_control_readings_fail(config, seeds):
    lim, rs = _limits(config), [r for r in READINGS if r["config"] == config]
    assert len({r["weights_seed"] for r in rs}) == len(rs) == seeds
    for r in rs:
        assert _passes(lim, r["share_within_0.75"], r["widest_gap"], r.get("over_4.5")), r
        assert not _passes(lim, r["control_share_within_0.75"], r["control_widest_gap"],
                           r.get("control_over_4.5")), r


def test_the_dense_limit_lies_between_the_sound_runs_and_the_control():
    lim = _limits("mistral-7b-v0.3")
    rs = [r for r in READINGS if r["config"] == "mistral-7b-v0.3"]
    sound, control = max(r["widest_gap"] for r in rs), min(r["control_widest_gap"] for r in rs)
    assert control > 3 * sound                      # a limit can hold at all
    assert lim["min_share_within"] == 1.0 and lim["max_over_epsilon_all"] == 0
    assert 1.25 * sound <= lim["epsilon"] == lim["epsilon_all"] <= control / 4


def test_the_sparse_limits_are_counts_because_the_widest_gaps_overlap():
    lim = _limits("mixtral-8x7b")
    rs = [r for r in READINGS if r["config"] == "mixtral-8x7b"]
    assert (max(r["widest_gap"] for r in rs)
            > min(r["control_widest_gap"] for r in rs))   # no limit on the widest gap can hold
    kept = [r for r in rs if "control_over_4.5" in r]     # every position's gap kept
    sound, control = max(r["over_4.5"] for r in rs), min(r["control_over_4.5"] for r in kept)
    assert len(kept) >= 16 and control >= 3 * lim["max_over_epsilon_all"]
    assert lim["max_over_epsilon_all"] == sound + 1
    lowest = min(r["share_within_0.75"] for r in rs)
    highest = max(r["control_share_within_0.75"] for r in rs)
    assert highest + 0.5 <= lim["min_share_within"] <= lowest - 1 / 48
