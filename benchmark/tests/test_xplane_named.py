"""The trace reduction on programs that carry names (a case for
test_xplane.py, kept in a file of its own: a PR that adds to the benchmark
edits no file the benchmark has)."""
import os

import pytest

import metrics
import xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_named_programs_are_read_by_name_whatever_their_run_counts():
    """The engine's programs as they are named since its jits carry their
    methods' names: the admission ran more often than the chunk here, and
    a third program is neither."""
    ops = [(f"%fusion.{i} = bf16[8]", 100 * i, 50) for i in range(6)]
    planes = [("/device:TPU:0", [
        ("XLA Ops", ops),
        ("XLA Modules", [("jit__admit_impl(7)", 0, 60),
                         ("jit__admit_impl(7)", 100, 60),
                         ("jit__chunk_impl(3)", 200, 80),
                         ("jit__deactivate_impl(9)", 300, 10),
                         ("jit__admit_impl(8)", 400, 60)]),
    ]), ("/host:CPU", [("scheduler", [("sched.dispatch", 0, 10)])])]
    tr = xplane.reduce_planes(planes)
    assert {k: v["count"] for k, v in tr["modules"].items()} == {
        "_admit_impl": 3, "_chunk_impl": 1, "_deactivate_impl": 1}
    assert not any(k.startswith("unnamed") for k in tr["modules"])
    assert tr["modules"]["_chunk_impl"]["median_s"] == pytest.approx(80e-9)
    assert {n for n, _ in tr["idle_gaps"]} == {
        "gap_before__admit_impl", "gap_before__chunk_impl",
        "gap_before__deactivate_impl"}
    # and the readers take them by name: the chunk is not "the most run"
    obs = metrics.Obs(trace=tr, decode_steps=4.0, decode_dispatches=1.0)
    assert metrics.load_reader(BENCH, "step.decode_ms").read(obs) == \
        pytest.approx(1000.0 * 80e-9 / 4)
