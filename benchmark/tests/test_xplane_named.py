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


def test_an_op_the_ranking_drops_is_still_found_by_name():
    """The recorded trace (see test_xplane.py) holds eleven device ops: the
    printed ranking keeps ten, the table by program and name keeps every
    one with its seconds, so a reader that looks for a kernel does not
    depend on what outranks it."""
    tr = xplane.reduce_planes(xplane.read_planes(
        os.path.join(BENCH, "tests", "data", "small.xplane.pb")))
    by, listed = tr["ops_by_program"], [n for n, _ in tr["device_ops"]]
    assert set(by) <= set(tr["modules"])     # every op ran inside a program of the trace
    table = {n: v for ops in by.values() for n, v in ops.items()}
    assert len(listed) == 10 and len(table) == sum(len(ops) for ops in by.values()) == 11
    assert [[n, table[n]] for n in listed] == tr["device_ops"]  # the same seconds
    (dropped,) = set(table) - set(listed)
    assert dropped.startswith("copy-start.1_bf16_64_512")
    assert by["_chunk_impl"][dropped] == pytest.approx(3.1e-08)
    assert 0 < table[dropped] <= min(s for _, s in tr["device_ops"])
    # the scan's body is the decode chunk's, the next longest op an admission's
    assert listed[0] in by["_chunk_impl"] and listed[1] in by["_admit_impl"]
    assert sum(table.values()) <= tr["busy_s"] * 1.001


def test_the_grouped_products_are_read_whatever_outranks_them():
    """_moe.decode_grouped_ops takes the decode program's grouped products
    from the table by program and name: twelve of them under ten longer ops
    (PR 28's refused trace) are all found; an admission's product that XLA
    numbers and shapes alike (the two-row group of the shortest bucket) is
    another program's and adds nothing; none is found when the table has
    none."""
    metrics.load_reader(BENCH, "moe.kernel_roofline.chat")  # puts layer_metrics on the path
    import _moe
    gmm = "%gmm.{} = bf16[256,1536]{{1,0:T(8,128)(2,1)}} custom-call("
    ops = [(f"%copy.{i} = bf16[2,64,8,1024,64]{{4,3,2,1,0}} copy(", 2000 + 100 * i, 90)
           for i in range(10)]
    ops += [(gmm.format(i), 200 + 10 * i + 100 * r, 5) for i in range(12) for r in range(8)]
    admitted = [(gmm.format(3), 5100, 40)]  # inside _admit_impl, which starts at 5000

    def reduced(op_events):
        return xplane.reduce_planes([("/device:TPU:0", [
            ("XLA Ops", op_events),
            ("XLA Modules", [("jit__chunk_impl(3)", 0, 4000), ("jit__admit_impl(4)", 5000, 500)])])])
    tr = reduced(ops + admitted)
    assert all(n.startswith("copy") for n, _ in tr["device_ops"])  # ten outrank every gmm
    assert list(tr["ops_by_program"]["_admit_impl"].values()) == [pytest.approx(40e-9)]
    obs = metrics.Obs(trace=tr, slots=64, cfg={"num_experts_per_tok": 4})
    found = _moe.decode_grouped_ops(obs)
    assert len(found) == 12 and all(cols == 1536 for _, cols in found)
    assert sum(s for s, _ in found) == pytest.approx(12 * 8 * 5e-9)
    assert _moe.decode_grouped_ops(metrics.Obs(trace=reduced(ops[:10] + admitted), slots=64,
                                               cfg={"num_experts_per_tok": 4})) == []
