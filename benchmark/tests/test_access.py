"""The access-log readers (layer_metrics/_access.py and the eight metrics
that read through it) on a hand-made unit.log and observation."""
import json
import os
import time

import pytest

import metrics
import stats
from client import Result
from traffic import Request

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = {  # metric -> what it reads from the log below
    "unit.executor_wait_ms.chat": 0.5,
    "sched.queue_wait_ms.chat": 40.0,
    "sched.device_wait_ms.chat": 460.0,
    "sched.first_token_held_ms.chat": 80.0,
    "sched.waves_ahead.chat": 5.0,
    "e2e.ttft_outside_ms.chat": 7.5,
    "setup.load_s": 19.25,
    "setup.engine_warmup_s": 0.0,
}
N = 20  # sampled requests; request i has 0.1 * i ms more of each phase


def request_line(i, received_unix, **over):
    row = {"rid": i, "outcome": "ok", "prompt_tokens": 256,
           "completion_tokens": 128, "received_unix": received_unix,
           "executor_wait_ms": 0.5 + 0.1 * i, "queue_wait_ms": 40.0 + 0.1 * i,
           "device_wait_ms": 460.0 + 0.1 * i,
           "first_token_held_ms": 80.0 + 0.1 * i, "waves_ahead": 5,
           "decode_ms": 2800.0}
    row.update(over)
    return "INFO:seldon_tpu.access:request " + json.dumps(row)


@pytest.fixture
def cell(tmp_path, monkeypatch):
    """A checkout with one cell's unit.log, and the observation of a run
    whose window is the last ten seconds."""
    metrics.load_reader(BENCH, "setup.load_s")  # puts the readers' folder
    import _access                              # on sys.path, as run.py does
    work = tmp_path / "chiprun_out" / "benchmark" / "toy.chat"
    work.mkdir(parents=True)
    monkeypatch.setattr(_access, "log_path", lambda obs: (
        str(work / "unit.log") if (obs.cell or {}).get("name") else None))
    t1 = time.perf_counter()
    t0 = t1 - 10.0
    off = time.time() - time.perf_counter()
    mids = [t0 + off + 10.0 * (i + 0.5) / N for i in range(N)]
    lines = ["INFO:seldon_tpu.servers.jaxserver:JAXServer loaded: cfg=toy",
             "INFO:seldon_tpu.access:startup " + json.dumps({
                 "since_process_start": True, "imports_device_s": 6.0,
                 "weights_s": 13.25, "weights_ready_s": 19.25,
                 "engine_s": 0.4, "warmup_s": 0.0, "warmup_variants": 0}),
             request_line(900, t0 + off - 3.0, device_wait_ms=9e9),  # lead-in
             "INFO:aiohttp.access:127.0.0.1 \"POST /generate_stream\" 200"]
    lines += [request_line(i, m) for i, m in enumerate(mids)]
    lines += [request_line(901, t1 + off + 2.0, device_wait_ms=9e9),  # tail
              "INFO:seldon_tpu.access:request {torn"]
    samples = [Result(Request(i, "window", 256, 128, 0.0), due=t0, sent=t0,
                      first=t0 + 0.6, last=t0 + 3.0, tokens=[1] * 128)
               for i in range(N)]
    obs = metrics.Obs(cell={"name": "toy.chat"}, t0=t0, t1=t1, samples=samples,
                      ttft_ms=[588.0 + 0.4 * i for i in range(N)])

    def write(extra=()):
        (work / "unit.log").write_text("\n".join(lines + list(extra)) + "\n")
    write()
    return obs, write, work


def read(name, obs):
    return metrics.load_reader(BENCH, name).read(obs)


def test_log_path_follows_run_py():
    metrics.load_reader(BENCH, "setup.load_s")
    import _access
    root = os.path.dirname(BENCH)
    assert _access.log_path(metrics.Obs(cell={"name": "mixtral.chat"})) == \
        os.path.join(root, "chiprun_out", "benchmark", "mixtral.chat",
                     "unit.log")
    assert _access.log_path(metrics.Obs()) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_log(cell, name):
    obs, _, _ = cell
    # trimmed mean over i = 2..17 of base + 0.1 i is base + 0.95; the
    # lead-in and tail lines (9e9) are outside the window and never read
    shift = 0.95 if name.endswith("_ms.chat") and "outside" not in name else 0.0
    want = READERS[name] + shift
    if "outside" in name:  # 588 + 0.4 * 9.5 - (580.5 + 4 * 0.95)
        want = stats.trimmed_mean(obs.ttft_ms) - (580.5 + 4 * 0.95)
        assert want == pytest.approx(READERS[name])
    assert read(name, obs) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_nothing_when_there_is_nothing_to_read(cell, name):
    obs, write, work = cell
    assert read(name, metrics.Obs()) is None           # an empty observation
    os.remove(work / "unit.log")
    assert read(name, obs) is None                     # no log
    (work / "unit.log").write_text("INFO:root:an older program's log\n")
    assert read(name, obs) is None                     # no lines of ours


def test_window_must_hold_the_sampled_requests(cell):
    import _access
    obs, write, _ = cell
    assert len(_access.window(obs)) == N
    off = time.time() - time.perf_counter()
    inside = obs.t0 + off + 1.0
    write([request_line(950 + i, inside) for i in range(2)])
    assert len(_access.window(obs)) == N + 2           # an edge's worth
    write([request_line(950 + i, inside) for i in range(3)])
    assert _access.window(obs) is None                 # more: not the window
    for name in READERS:
        got = read(name, obs)
        assert (got is None) == name.endswith(".chat"), name
    assert _access.window(metrics.Obs(cell=obs.cell, t0=obs.t0, t1=obs.t1)) \
        is None                                        # no samples to match


def test_requests_that_never_reached_a_phase_are_left_out_of_it(cell):
    obs, write, _ = cell
    off = time.time() - time.perf_counter()
    write([request_line(990, obs.t0 + off + 1.0, outcome="deadline",
                        queue_wait_ms=None, device_wait_ms=None,
                        first_token_held_ms=None, waves_ahead=None,
                        decode_ms=None)])
    assert read("sched.waves_ahead.chat", obs) == pytest.approx(5.0)
    assert read("sched.device_wait_ms.chat", obs) == pytest.approx(460.95)
