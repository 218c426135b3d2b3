"""The rule every roofline's need follows (ISSUE 54): a need prices what
the traffic required (the fixed-size state of the slots that were live,
the experts their rows chose, the KV they attended), whatever program
serves it, and it is counted in the seconds its device time comes from.
On the configurations' own files and on planted observations; no JAX."""
import inspect
import json
import os
import time
from types import SimpleNamespace

import pytest

import costs
import family
import metrics

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MAMBA = ["nemotron-3-nano-30b-a3b", "falcon-h1-34b-instruct"]
# configuration -> sparse layers it runs, bytes a weight
SPARSE = {"mixtral-8x7b": (5, 1), "lfm2-24b-a2b": (8, 2), "nemotron-3-nano-30b-a3b": (6, 2),
          "laguna-xs.2": (4, 2), "sdar-30b-a3b-chat": (7, 2)}
# reader -> (configuration, the kernel's op over a slab of 64 slots)
KERNELS = {
    "ssm.update_roofline.chat": (
        MAMBA[0], "ssm_update.25_f32_6_64_64_64_128_4_3_2_1_0_T_8_128_f32_64_64_64"),
    "h1.ssm_update_roofline.chat": (
        MAMBA[1], "ssm_update.10_f32_5_64_32_128_256_4_3_2_1_0_T_8_128_f32_64_128")}


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _fam(name):
    return family.load(BENCH, _cfg(name))


def _takes_touched(fam):
    return "touched" in inspect.signature(fam.decode_step_cost).parameters


# -- (a) the fixed-size state of the live slots, not of the slab ---------------

@pytest.mark.parametrize("name", MAMBA)
def test_a_steps_need_does_not_know_how_large_the_slab_is(name):
    cfg, fam = _cfg(name), _fam(name)
    more = {"touched": 5.0} if _takes_touched(fam) else {}
    wider = dict(cfg, serving=dict(cfg["serving"],
                                   kv_budget_tokens=2 * cfg["serving"]["kv_budget_tokens"]))
    assert fam.decode_step_cost(wider, 3.0, 400.0, **more) == \
        fam.decode_step_cost(cfg, 3.0, 400.0, **more)
    assert not hasattr(fam, "slots_held")


@pytest.mark.parametrize("name", MAMBA)
def test_the_state_bytes_of_a_step_are_linear_in_the_live_rows(name):
    cfg, fam = _cfg(name), _fam(name)
    more = {"touched": 5.0} if _takes_touched(fam) else {}
    (f1, b1), (f2, b2), (f4, b4) = (fam.decode_step_cost(cfg, r, 400.0, **more)
                                    for r in (1.0, 2.0, 4.0))
    a_row = 401 * fam.kv_bytes_per_token(cfg) + 2 * (
        fam.ssm_state_bytes_per_slot(cfg) + fam.conv_state_bytes_per_slot(cfg))
    assert b2 - b1 == pytest.approx(a_row, rel=1e-12)
    assert b4 - b2 == pytest.approx(2 * a_row, rel=1e-12)
    assert f4 - f2 == pytest.approx(2 * (f2 - f1), rel=1e-12)
    # of which the update's own, one layer at a time, is the kernel's closed form
    uf, ub = fam.ssm_update_cost(cfg, 1)
    assert fam.ssm_update_cost(cfg, 3.5) == (3.5 * uf, 3.5 * ub)


# -- (b) the need follows the work, the reading follows the kernel's rate ------

_Obs = metrics.Obs


def _line(ended, **counters):
    """One access line of a request that ended at `ended` (unit's wall
    clock) with the engine's running counters at these values."""
    return "INFO:seldon_tpu.access:request " + json.dumps(dict({
        "rid": 1, "outcome": "ok", "received_unix": ended - 1.1, "executor_wait_ms": 1.0,
        "queue_wait_ms": 9.0, "device_wait_ms": 50.0, "first_token_held_ms": 40.0,
        "decode_ms": 1000.0}, **counters)) + "\n"


def _plant(tmp_path, monkeypatch, text):
    import _access
    log = tmp_path / "unit.log"
    log.write_text("startup {}\n" + text)
    monkeypatch.setattr(_access, "log_path", lambda obs: str(log))


def _clock():
    a = time.perf_counter()
    return a, a + (time.time() - time.perf_counter())


@pytest.mark.parametrize("live", [2.0, 3.92, 16.0])
@pytest.mark.parametrize("reader", sorted(KERNELS))
def test_a_kernel_that_steps_the_live_slots_alone_reads_its_own_rate(
        reader, live, tmp_path, monkeypatch):
    """The same kernel at the same bytes a second: over the whole slab
    with every slot live (what PR 34 to 53 read at a slab of dead slots),
    and over `live` slots in live / 64 of the time, with the live slots
    counted on the access lines between the slice's two ends."""
    roof = metrics.load_reader(BENCH, reader)
    name, op = KERNELS[reader]
    cfg, fam = _cfg(name), _fam(name)
    layers = fam.layer_counts(cfg)["mamba"]
    a, wall = _clock()

    def obs(seconds, rows_per_step, slice_=(a, a + 3.0)):
        return _Obs(cfg=cfg, family=fam, cell={"name": "x"}, slots=64, peaks=PEAKS,
                    trace={"ops_by_program": {"_chunk_impl": {op: seconds, "fusion.1": 9.0}},
                           "slice": slice_,
                           "modules": {"_chunk_impl": {"count": 100, "total_s": 2.4,
                                                       "median_s": 0.024}}},
                    decode_steps=400.0, decode_dispatches=100.0, rows_per_step=rows_per_step)
    # attention layers x slots x steps a second, and the rows the live slots wrote
    text = "".join(_line(wall - 1.0 + t, sampler_steps=130 * t, attn_kv_rows_slots=2 * 64 * 130 * t,
                         attn_kv_rows_written=2 * live * 130 * t) for t in (0.0, 2.5, 5.0))
    _plant(tmp_path, monkeypatch, text)
    whole = obs(1.2, 64.0, slice_=None)        # no line read: /metrics' rows, every slot live
    _, bytes_ = fam.ssm_update_cost(cfg, 64)
    assert roof.read(whole) == pytest.approx(100.0 * bytes_ / 819e9 * layers * 400 / 1.2)
    assert roof.read(obs(1.2 * live / 64, 1.0)) == pytest.approx(roof.read(whole))
    # today's program: the whole slab stepped for the live slots
    assert roof.read(obs(1.2, 1.0)) == pytest.approx(roof.read(whole) * live / 64)


# -- (c) the experts the router chose ------------------------------------------

@pytest.mark.parametrize("name", sorted(SPARSE))
def test_without_a_count_the_closed_form_stands_to_the_last_bit(name):
    cfg, fam = _cfg(name), _fam(name)
    for rows, ctx in ((1.0, 100.0), (2.5, 400.0), (7.0, 1000.0)):
        # what the closed form expects at these rows: a pass of a block model
        # routes Bk positions a slot
        proc = fam.procedure(cfg) if hasattr(fam, "procedure") else None
        positions = rows * (proc["denoise_steps"] + 1) if proc else rows
        expected = fam.experts_touched(cfg, positions)
        assert fam.decode_step_cost(cfg, rows, ctx) == \
            fam.decode_step_cost(cfg, rows, ctx, None) == \
            fam.decode_step_cost(cfg, rows, ctx, touched=expected)


@pytest.mark.parametrize("name", sorted(SPARSE))
def test_a_steps_bytes_are_linear_in_the_experts_counted(name):
    cfg, fam = _cfg(name), _fam(name)
    layers, width = SPARSE[name]
    (f5, b5), (f10, b10), (f20, b20) = (
        fam.decode_step_cost(cfg, 2.5, 400.0, touched=t) for t in (5.0, 10.0, 20.0))
    assert f5 == f10 == f20                    # the rows multiply through k experts each
    assert b10 - b5 == pytest.approx(5 * layers * fam.expert_params(cfg) * width, rel=1e-12)
    assert b20 - b10 == pytest.approx(2 * (b10 - b5), rel=1e-12)


def test_a_dense_model_of_a_family_with_experts_has_none_to_count():
    cfg, fam = _cfg("mistral-7b-v0.3"), _fam("mistral-7b-v0.3")
    assert fam.decode_step_cost(cfg, 2.5, 400.0, touched=3.0) == \
        fam.decode_step_cost(cfg, 2.5, 400.0)
    assert not _takes_touched(_fam("falcon-h1-34b-instruct"))   # no sparse layer priced


# -- (d) the slice's own rows over the slice's own seconds ---------------------

# configuration, routed?, a model that generates by blocks?
STEPS = [("mistral-7b-v0.3", False, False), ("lfm2-24b-a2b", True, False),
         ("nemotron-3-nano-30b-a3b", True, False), ("falcon-h1-34b-instruct", False, False),
         ("laguna-xs.2", True, False), ("sdar-30b-a3b-chat", True, True)]


def _step_obs(name, routed, block, tmp_path, monkeypatch, slice_):
    """A window of ten seconds at 100 steps a second in which 4 slots are
    live and a sparse layer reads 16 experts, but for the three seconds
    3 .. 6, in which 2 are and it reads 9; `slice_` on the window's clock."""
    cfg, fam = _cfg(name), _fam(name)
    a, wall = _clock()
    layers = 2

    def counters(t):
        light = min(max(t - 3.0, 0.0), 3.0)
        steps, slot_steps = 100 * t, 100 * (4 * (t - light) + 2 * light)
        c = {"sampler_steps": steps, "attn_kv_rows_slots": layers * 64 * steps,
             "attn_kv_rows_written": layers * slot_steps}
        if routed:
            c.update(moe_sparse_layer_steps=3 * steps, moe_assignments=0,
                     moe_experts_touched=3 * 100 * (16 * (t - light) + 9 * light))
        if block:
            c.update(diff_slot_passes=slot_steps)
        return c
    ends = [0.5 + i for i in range(10)]                     # a request ends every second
    _plant(tmp_path, monkeypatch, "".join(_line(wall + t, **counters(t)) for t in ends))
    sample = SimpleNamespace(ok=True, tokens=[0] * 100, req=SimpleNamespace(prompt_len=350))
    obs = _Obs(cfg=cfg, family=fam, cell={"name": "x"}, slots=64, peaks=PEAKS,
               t0=a - 2.0, t1=a + 9.0, samples=[sample] * 10,
               trace={"slice": (a + slice_[0], a + slice_[1]),
                      "modules": {"_chunk_impl": {"count": 50, "total_s": 1.0,
                                                  "median_s": 0.019}}},
               decode_steps=4000.0, decode_dispatches=1000.0, rows_per_step=3.3)
    return obs, cfg, fam


def _reading(cfg, fam, slots, touched, routed, block):
    rows = slots * 4 / 3 if block else slots    # Bk = 4 tokens a slot every 3 passes
    more = {"touched": touched} if routed else {}
    least, _ = costs.least_seconds(*fam.decode_step_cost(cfg, rows, 400.0, **more), PEAKS)
    return 100.0 * least * 50 * 4 / 1.0         # 50 chunks of 4 steps took 1.0 s


@pytest.mark.parametrize("name,routed,block", STEPS)
def test_the_steps_roofline_prices_the_slice_it_times(name, routed, block, tmp_path,
                                                      monkeypatch, capsys):
    roof = metrics.load_reader(BENCH, "step.decode_roofline")
    obs, cfg, fam = _step_obs(name, routed, block, tmp_path, monkeypatch, (3.5, 5.5))
    assert roof.read(obs) == pytest.approx(_reading(cfg, fam, 2.0, 9.0, routed, block), rel=1e-6)
    said = capsys.readouterr().out
    assert "rows of the slice" in said and "window," not in said
    if routed:
        assert "9.00 experts a sparse layer counted in the slice" in said
    # the time is the program's total in the slice, not its median chunk
    half = _Obs(obs, trace=dict(obs.trace, modules={"_chunk_impl": dict(
        obs.trace["modules"]["_chunk_impl"], total_s=2.0)}))
    assert roof.read(half) == pytest.approx(roof.read(obs) / 2)


@pytest.mark.parametrize("name,routed,block", STEPS)
def test_short_of_the_slice_it_prices_the_window_and_says_so(name, routed, block, tmp_path,
                                                             monkeypatch, capsys):
    roof = metrics.load_reader(BENCH, "step.decode_roofline")
    # no request has ended after the slice's end: the lines stop at 9.5 s
    obs, cfg, fam = _step_obs(name, routed, block, tmp_path, monkeypatch, (8.0, 11.0))
    # last line less first: nine seconds, three of them light
    slots, touched = (4 * 6 + 2 * 3) / 9.0, (16 * 6 + 9 * 3) / 9.0
    got = roof.read(obs)
    assert got is not None
    assert got == pytest.approx(_reading(cfg, fam, slots, touched, routed, block), rel=1e-6)
    said = capsys.readouterr().out
    assert "rows of the window" in said and "rows of the slice" not in said
    # a program that counts nothing of it (an older one): the window's rows by /metrics
    _plant(tmp_path, monkeypatch, "".join(_line(time.time() + t) for t in range(10)))
    bare = roof.read(obs)
    more = {"touched": None} if routed else {}
    least, _ = costs.least_seconds(*fam.decode_step_cost(cfg, 3.3, 400.0, **more), PEAKS)
    assert bare == pytest.approx(100.0 * least * 200 / 1.0, rel=1e-6)
    assert "window, by /metrics" in capsys.readouterr().out


@pytest.mark.parametrize("name,routed,block", STEPS)
def test_a_slice_the_lines_do_not_resolve_is_priced_at_the_window(name, routed, block, tmp_path,
                                                                  monkeypatch, capsys):
    """A freeze of the machine inside the slice: the lines' counters, linear
    in time between two requests' ends, put 200 steps between the slice's
    ends where the trace ran 80; the rows they would give are other
    seconds', so the window's stand in and a line says why."""
    roof = metrics.load_reader(BENCH, "step.decode_roofline")
    obs, cfg, fam = _step_obs(name, routed, block, tmp_path, monkeypatch, (3.5, 5.5))
    frozen = _Obs(obs, trace=dict(obs.trace, modules={"_chunk_impl": {
        "count": 20, "total_s": 0.4, "median_s": 0.02}}))
    slots, touched = (4 * 6 + 2 * 3) / 9.0, (16 * 6 + 9 * 3) / 9.0
    assert roof.read(frozen) == pytest.approx(
        _reading(cfg, fam, slots, touched, routed, block), rel=1e-6)   # 80 steps in 0.4 s
    said = capsys.readouterr().out
    assert "do not resolve the traced slice: 200 decode steps" in said and "80 by the trace" in said
    assert "rows of the window" in said
    # within a tenth they do: 47 chunks of 4 steps against the lines' 200
    near = _Obs(obs, trace=dict(obs.trace, modules={"_chunk_impl": {
        "count": 47, "total_s": 1.0, "median_s": 0.02}}))
    roof.read(near)
    assert "rows of the slice" in capsys.readouterr().out


def test_a_step_that_scatters_every_slots_row_tells_nothing_of_the_live_ones(
        tmp_path, monkeypatch):
    """Off a TPU the step writes a row of every slot: the rows written are
    slots x layers whatever is live, and the reader takes /metrics' rows."""
    import _need
    a, wall = _clock()
    _plant(tmp_path, monkeypatch, "".join(
        _line(wall + t, attn_kv_rows_slots=128 * t, attn_kv_rows_written=128 * t)
        for t in range(10)))
    obs = _Obs(cfg=_cfg(MAMBA[0]), cell={"name": "x"}, slots=64, rows_per_step=2.5,
               trace={"slice": (a + 2.0, a + 5.0)})
    assert _need.live_slots(obs) == (None, None)
    assert _need.rows(obs) == (2.5, "window, by /metrics")
    assert _need.rows(_Obs(obs, rows_per_step=None)) == (None, None)


# -- (e) one interpolation for every tuple of counters -------------------------

def test_the_generalised_slice_delta_gives_the_sparse_readers_numbers(tmp_path, monkeypatch):
    """tests/test_lfm2_family.py's slice: 8 sparse layers x 80 steps a
    second; before the slice 2 rows a step touch 8 experts, inside it 3.5
    touch 12, after it 6 touch 20; lines end 1 s before, at each end and
    in the middle of the slice, and 1 s after it."""
    import _access
    import _moe
    a, wall = _clock()
    counters, t = [0, 0, 0], wall - 2.0
    text = _line(t, **dict(zip(_moe.FIELDS, counters)))
    for seconds, rows, touched in ((2.0, 2.0, 8.0), (1.5, 3.5, 12.0), (1.5, 3.5, 12.0),
                                   (1.0, 6.0, 20.0)):
        n = 8 * 80 * seconds
        counters = [counters[0] + n, counters[1] + n * touched, counters[2] + n * rows * 4]
        t += seconds
        text += _line(t, **dict(zip(_moe.FIELDS, counters)))
    _plant(tmp_path, monkeypatch, text)
    obs = _Obs(cell={"name": "x"}, trace={"slice": (a, a + 3.0)})
    d = _moe.slice_delta(obs)   # (each call reads the two clocks' offset anew: approx)
    assert _access.slice_delta(obs, _moe.FIELDS) == {k: pytest.approx(v) for k, v in d.items()}
    assert d["moe_sparse_layer_steps"] == pytest.approx(8 * 240, rel=1e-3)
    assert d["moe_experts_touched"] / d["moe_sparse_layer_steps"] == pytest.approx(12.0, rel=1e-3)
    assert d["moe_assignments"] / d["moe_sparse_layer_steps"] / 4 == pytest.approx(3.5, rel=1e-3)
    # any tuple of the fields, in any order, reads the same growth of each
    two = _access.slice_delta(obs, ("moe_assignments", "moe_sparse_layer_steps"))
    assert two == {k: pytest.approx(d[k]) for k in two} and list(two) == [
        "moe_assignments", "moe_sparse_layer_steps"]
    # a field no line carries, a slice no line has reached, no slice: nothing
    assert _access.slice_delta(obs, ("moe_assignments", "no_such_counter")) is None
    assert _access.slice_delta(_Obs(obs, trace={"slice": (a + 2.0, a + 5.0)}), _moe.FIELDS) is None
    assert _access.slice_delta(_Obs(obs, trace={}), _moe.FIELDS) is None


def test_no_reader_prices_the_slab_or_mixes_the_windows_need_with_the_slices_time():
    """ISSUE 54's last criterion, as a grep: nothing under layer_metrics/
    or families/ sums a need over the slots the program holds, and no
    reader divides by the median chunk but step.decode_ms, which is a
    time and no share."""
    hits = []
    for folder in ("layer_metrics", "families"):
        for fn in sorted(os.listdir(os.path.join(BENCH, folder))):
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(BENCH, folder, fn)) as f:
                text = f.read()
            if "slots_held" in text or "ssm_update_cost(obs.cfg, obs.slots)" in text:
                hits.append((fn, "prices the slab"))
            if "decode_step_s(" in text and fn not in ("_trace.py", "step.decode_ms.py"):
                hits.append((fn, "takes a time from the median chunk"))
    assert hits == []
