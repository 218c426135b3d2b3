"""The trace reduction on a small trace recorded on a TPU v5e
(tests/data/small.xplane.pb: 6 runs of a jitted 4-step scan named
_chunk_impl and 3 of a jitted _admit_impl, with host sleeps between)."""
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_of_intervals():
    assert xplane.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert xplane.union_s([(0, 100), (10, 20), (20, 30)]) == pytest.approx(100e-9)
    assert xplane.union_s([]) == 0.0


def test_recorded_tpu_trace_reduces_to_known_numbers():
    tr = xplane.reduce_planes(xplane.read_planes(DATA))
    assert tr["device_planes"] == 1
    assert tr["modules"]["_chunk_impl"]["count"] == 6
    assert tr["modules"]["_admit_impl"]["count"] == 3
    assert tr["modules"]["_chunk_impl"]["median_s"] == pytest.approx(6.556e-06)
    assert tr["busy_s"] == pytest.approx(4.8333e-05)
    assert tr["window_s"] == pytest.approx(0.008728054)
    assert 0.0 < tr["busy_s"] <= tr["window_s"]  # busy can never pass the window
    # ops are listed themselves, not the while loop that spans them
    names = [n for n, _ in tr["device_ops"]]
    assert names[0].startswith("convolution_tanh_fusion") and not any(
        n.startswith("while") for n in names)
    assert sum(s for _, s in tr["device_ops"]) <= tr["busy_s"] * 1.001
    # the longest gaps are the host's sleeps, named by what ran next
    assert tr["idle_gaps"][0][0] in ("gap_before__chunk_impl", "gap_before__admit_impl")
    assert tr["idle_gaps"][0][1] == pytest.approx(0.003757134)
    assert len(tr["device_ops"]) <= 10 and len(tr["idle_gaps"]) <= 10


def test_programs_without_a_name_are_told_apart_by_id_and_run_count():
    planes = [("/device:TPU:0", [
        ("XLA Ops", [("%fusion.1 = bf16[8]", 0, 50), ("%fusion.2 = bf16[8]", 100, 50),
                     ("%while.3 = (s32[])", 0, 400), ("%fusion.1 = bf16[8]", 300, 50)]),
        ("XLA Modules", [("jit__unknown(11)", 0, 60), ("jit__unknown(22)", 100, 60),
                         ("jit__unknown(11)", 300, 60)]),
    ]), ("/host:CPU", [("python3", [("PjitFunction(f)", 0, 10)])])]
    tr = xplane.reduce_planes(planes)
    assert tr["modules"]["unnamed_most_run"]["count"] == 2
    assert tr["modules"]["unnamed_other"]["count"] == 1
    assert tr["busy_s"] == pytest.approx(400e-9)  # the while op covers its body
    assert [n for n, _ in tr["device_ops"]] == ["fusion.1_bf16_8", "fusion.2_bf16_8"]
    assert tr["idle_gaps"][0] == ["gap_before_unnamed_most_run", pytest.approx(140e-9)]


def test_a_trace_with_no_device_plane_reads_as_nothing():
    tr = xplane.reduce_planes([("/host:CPU", [("python3", [("x", 0, 5)])])])
    assert tr["busy_s"] == 0.0 and tr["modules"] == {}
