"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run = one new process. This process never imports JAX: it starts the
unit as a child (benchmark/launcher.py -> the normal microservice entry
point, REST, platform "tpu", tp = the cell's chips), waits for /ready,
checks /metadata, warms up the cell's own shapes, sends the greedy probes
(which also time the hop), runs lead-in + window + tail of the cell's traffic, drains, reads
the counters, stops the child, reduces the trace (--trace 1, in a second
child), checks parity with the plain reference if this checkout has not
done so yet (a third child, once the chip is free), and prints one JSON
object as the last line of stdout. No chip -> non-zero exit, no line.

`--rehearse` runs the same code on the CPU with the tiny presets and
prints its numbers under *_cpu_smoke names only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse
import asyncio
import hashlib
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import family  # noqa: E402  (imports no JAX, nor does loading a family)
import metrics  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
from client import now  # noqa: E402

LOAD_TIMEOUT_S = 900.0
GAUGES = ("jaxserver_completed", "jaxserver_failed_total",
          "jaxserver_tokens_out", "jaxserver_decode_steps",
          "jaxserver_decode_dispatches")
PROBE_LENS = (24, 60, 100, 120)  # two prompt buckets: 32 and 128
PROBE_NEW = 12
GROUP_ATTEMPTS = 3     # bursts sent before an admission group counts as never formed


class BenchFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail_of(path: str, n: int = 30) -> str:
    try:
        with open(path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")
    except OSError:
        return "(no log)"


def stop_child(child: subprocess.Popen) -> None:
    """SIGINT (the entry point's clean exit), then SIGKILL; wait either way."""
    if child.poll() is None:
        child.send_signal(signal.SIGINT)
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(timeout=30)


class Run:
    def __init__(self, args):
        self.args = args
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if args.workload not in cells:
            raise BenchFailure(f"no workload {args.workload!r} in BENCHMARK.json")
        self.cell = cells[args.workload]
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == self.cell["config"])
        self.config_file = os.path.join(ROOT, entry["file"])
        with open(self.config_file) as f:
            self.cfg = json.load(f)
        try:  # what differs between architectures: benchmark/families/<family>.py
            self.family = family.load(HERE, self.cfg)
        except FileNotFoundError as e:
            raise BenchFailure(str(e)) from None
        self.spec = traffic.load_traffic(HERE, self.cell["traffic"],
                                         self.cell["name"], args.rehearse)
        self.window_tokens = int(self.spec["window_tokens"])
        self.slots = int(self.spec.get("slots") or
                         self.cfg["serving"]["kv_budget_tokens"] // self.window_tokens)
        self.platform = "cpu" if args.rehearse else "tpu"
        self.preset = (self.cfg["rehearse_preset"] if args.rehearse
                       else self.cfg["name"])
        self.work = os.path.join(ROOT, "chiprun_out", "benchmark",
                                 self.cell["name"])
        os.makedirs(self.work, exist_ok=True)
        self.profile_dir = os.path.join(self.work, "profile")
        self.obs = metrics.Obs(cfg=self.cfg, family=self.family, spec=self.spec,
                               cell=self.cell, seconds=args.seconds,
                               slots=self.slots)
        self.problems = []  # what makes the run incorrect

    # -- the child ----------------------------------------------------------

    def unit_parameters(self) -> list:
        """The unit's parameters: one model over as many chips as the cell asks."""
        params = [
            {"name": "preset", "value": self.preset, "type": "STRING"},
            {"name": "init_seed", "value": str(self.args.seed % (2 ** 31 - 1)),
             "type": "INT"},
            {"name": "tp", "value": str(self.cell["chips"]), "type": "INT"},
            {"name": "max_slots", "value": str(self.slots), "type": "INT"},
            {"name": "max_seq_len", "value": str(self.window_tokens), "type": "INT"},
            {"name": "platform", "value": self.platform, "type": "STRING"},
        ]
        if not self.args.rehearse:
            params.append({"name": "weight_dtype", "type": "STRING",
                           "value": self.cfg["serving"]["weight_dtype"]})
        return params

    def start_unit(self) -> subprocess.Popen:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["COMPILE_LEDGER"] = "1"      # acts only on a first dispatch
        env["SELDON_TPU_FASTPATH"] = "0"  # REST only: no second listener
        env.pop("BENCH_RUN", None)
        if self.args.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.port = free_port()
        cmd = [sys.executable, os.path.join(HERE, "launcher.py"),
               "--config", self.config_file]
        if self.args.rehearse:
            cmd += ["--preset-name", "bench-" + self.cfg["name"]]
        if self.args.trace:
            shutil.rmtree(self.profile_dir, ignore_errors=True)
            os.makedirs(self.profile_dir)
            cmd += ["--profile-dir", self.profile_dir]
        cmd += ["--", "seldon_tpu.servers.jaxserver.JAXServer",
                "--api-type", "REST", "--host", "127.0.0.1",
                "--http-port", str(self.port),
                "--parameters", json.dumps(self.unit_parameters())]
        self.log_path = os.path.join(self.work, "unit.log")
        with open(self.log_path, "wb") as log:
            return subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                    stderr=subprocess.STDOUT)

    async def wait_ready(self, child, unit: client.Unit) -> None:
        t_spawn = now()
        while True:
            if child.poll() is not None:
                raise BenchFailure(
                    f"unit exited rc={child.returncode} before /ready; "
                    f"log tail:\n{tail_of(self.log_path)}")
            if now() - t_spawn > LOAD_TIMEOUT_S:
                raise BenchFailure("no /ready within the load time limit")
            try:
                status, _ = await unit.get("/ready", timeout=5.0)
                if status == 200:
                    return
            except Exception:
                pass
            await asyncio.sleep(0.25)

    async def check_metadata(self, unit: client.Unit) -> None:
        md = await unit.get_json("/metadata")
        if md is None or "device" not in md:
            raise BenchFailure(f"/metadata carries no device: {md}")
        dev, got = md["device"], md["config"]
        if dev["platform"] != self.platform:
            raise BenchFailure(f"unit ran on {dev['platform']!r}, not {self.platform!r}")
        if dev["count"] < self.cell["chips"]:
            raise BenchFailure(f"{dev['count']} device(s), cell needs {self.cell['chips']}")
        if not self.args.rehearse:
            self.obs["peaks"] = peaks.peaks_for(dev["device_kind"])  # raises if unknown
            for k, v in self.family.model_config_kwargs(self.cfg).items():
                if got.get(k) != v:
                    raise BenchFailure(f"unit's {k}={got.get(k)!r}, configuration says {v!r}")
        eng = md["engine"]
        if (eng["max_slots"], eng["max_seq_len"]) != (self.slots, self.window_tokens):
            raise BenchFailure(f"engine is {eng}, asked {self.slots} x {self.window_tokens}")
        self.device = {"platform": dev["platform"], "kind": dev["device_kind"],
                       "count": dev["count"]}
        self.vocab = unit.vocab = got["vocab_size"]
        self.buckets = sorted(eng["prompt_buckets"])
        say(f"device {self.device}; model {self.preset} L={got['n_layers']} "
            f"d={got['d_model']} ff={got['d_ff']} experts={got['n_experts']} "
            f"weights={got['weight_dtype']} kv={got['kv_cache_dtype']}; engine "
            f"{self.slots} slots x {self.window_tokens}, buckets {self.buckets}")

    # -- set-up: warm-up, hop, probes ---------------------------------------

    def _req(self, plen: int, max_new: int, rng_seed: int, phase: str = "setup"):
        rng = random.Random(rng_seed)
        return traffic.Request(-1, phase, plen, max_new, None,
                               [rng.randrange(self.vocab) for _ in range(plen)])

    async def compile_keys(self, unit: client.Unit) -> dict:
        snap = await unit.get_json("/debug/compile")
        if snap is None:
            raise BenchFailure("/debug/compile is off in the child")
        return {v["key"]: v for v in snap["lattice"]}

    async def warm_up(self, unit: client.Unit) -> None:
        """The cell's own shapes only: every prompt bucket its requests
        reach, at every admission group size the unit can form. Groups
        are padded to powers of two; how large one can get (the engine's
        admission cap, the REST workers) is not copied from the program
        but found: on the first bucket the rungs 1, 2, 4, ... are tried
        until one does not form. A group forms from the requests waiting
        at one chunk boundary, so an opener keeps the engine busy while a
        burst arrives; the compile ledger says whether the group formed."""
        t = now()
        reached = traffic.buckets_reached(self.spec, self.args.seconds, self.buckets)
        say(f"warm-up: buckets {reached}")
        cap = self.slots
        opener = None  # (bucket, task): a stream that keeps the engine busy
        for sb in reached:
            prev = max([b for b in self.buckets if b < sb], default=0)
            plen = min(prev + 1, self.window_tokens - 2)
            room = self.window_tokens - plen - 1
            g = 1
            while g <= cap:
                for _ in range(GROUP_ATTEMPTS):
                    # a lone request of this bucket (admit/sb/1) that then decodes
                    if opener is None or opener[0] != sb or opener[1].done():
                        if opener and not (await opener[1]).ok:
                            raise BenchFailure("a warm-up opener failed")
                        opener = (sb, asyncio.create_task(unit.stream(
                            self._req(plen, min(room, 128), 7))))
                        await asyncio.sleep(0.3)
                    if g == 1:
                        break
                    burst = await asyncio.gather(*[  # the fewest that pad to g
                        unit.generate(self._req(plen, 2, 11 + i))
                        for i in range(g // 2 + 1)])
                    bad = [r.error for r in burst if not r.ok]
                    if bad:
                        raise BenchFailure(f"warm-up admit/{sb}/{g} failed: {bad[0]}")
                    if f"admit/{sb}/{g}" in await self.compile_keys(unit):
                        break
                else:
                    if sb == reached[0]:
                        cap = g // 2
                        say(f"warm-up: admit/{sb}/{g} does not form: groups up to {cap}")
                    else:
                        say(f"warm-up: admit/{sb}/{g} never formed")
                g *= 2
        if opener and not (await opener[1]).ok:
            raise BenchFailure("a warm-up opener failed")
        keys = await self.compile_keys(unit)
        first_s = sum(v["first_dispatch_ms"] for v in keys.values()) / 1000.0
        self.obs["warmup_s"] = now() - t
        say(f"warm-up {self.obs['warmup_s']:.1f}s; variants {sorted(keys)}; "
            f"first dispatches {first_s:.1f}s")

    async def probes(self, unit: client.Unit, stream: bool):
        """4 fixed greedy probes, sent alone (prompts never see --seed).
        Over /generate they also time the REST hop: the client's round
        trip on the idle, warm unit minus the unit's own reported time."""
        outs, hops = [], []
        for i, plen in enumerate(PROBE_LENS):
            req = self._req(min(plen, self.window_tokens - PROBE_NEW - 2),
                            PROBE_NEW, 1000 + i, "probe")
            r = await (unit.stream(req) if stream else unit.generate(req))
            if not r.ok:
                raise BenchFailure(f"probe {i} failed: {r.error}")
            outs.append((req.prompt_ids, r.tokens))
            hops.append(1000.0 * (r.last - r.sent) - r.unit_ms)
        if not stream:
            self.obs["hop_ms"] = hops
        return outs

    # -- the window -----------------------------------------------------------

    def _profile_marks(self, t0: float):
        if not self.args.trace:
            return None
        span = float(self.spec.get("trace_s", 4.0))
        start = t0 + max(0.0, (self.args.seconds - span) / 2.0)
        state = {"n": 0}

        def mark(t: float) -> None:
            if state["n"] == 0 and t >= start:
                open(os.path.join(self.profile_dir, "start"), "w").close()
                state["n"] = 1
            elif state["n"] == 1 and t >= start + span:
                open(os.path.join(self.profile_dir, "stop"), "w").close()
                state["n"] = 2
        return mark

    async def measure(self, unit: client.Unit) -> None:
        spec, secs = self.spec, float(self.args.seconds)
        lead = float(spec["lead_in_s"])
        t0 = now() + lead + 0.2
        self.obs.update(t0=t0, t1=t0 + secs, setup_s=t0 - T_START)
        mark = self._profile_marks(t0)
        reqs = traffic.open_loop(spec, self.args.seed, secs, self.vocab)
        n_win = sum(1 for r in reqs if r.phase == "window")
        say(f"open loop: {spec['rate_rps']} req/s, {n_win} due in the window, "
            f"lead-in {lead}s")
        results = await client.run_open(unit, reqs, t0, mark)
        samples = [r for r in results if r.req.phase == "window"]
        if mark:  # the child writes the trace out before it may be stopped
            deadline = now() + 300.0
            while not os.path.exists(os.path.join(self.profile_dir, "stopped")):
                mark(now())
                if now() > deadline:
                    raise BenchFailure("the profiler never finished writing its trace")
                await asyncio.sleep(0.02)
        self.obs.update(all_results=results, samples=samples)

    # -- after ----------------------------------------------------------------

    def summarise(self) -> None:
        o = self.obs
        s = o.samples
        ok = [r for r in s if r.ok]
        o["attempted"], o["failed"] = len(s), len(s) - len(ok)
        short = [r for r in ok if len(r.tokens) < r.req.max_new]
        o["short_share"] = 100.0 * len(short) / max(len(ok), 1)
        o["late_ms"] = [1000.0 * (r.sent - r.due) for r in s]
        tt, tp = metrics.ttft_ms(s), metrics.tpot_ms(s)
        o["ttft_ms"], o["tpot_ms"] = tt, tp
        for name, xs in (("ttft_ms", tt), ("tpot_ms", tp)):
            if xs:
                say(f"{name}: n={len(xs)} p50={stats.percentile(xs, 50):.2f} "
                    f"p90={stats.percentile(xs, 90):.2f} "
                    f"mid80={stats.trimmed_mean(xs):.2f} max={max(xs):.2f}")
        toks = sum(r.req.prompt_len + len(r.tokens) for r in ok)
        say(f"window: {len(s)} sampled, {o['failed']} failed, {len(short)} short "
            f"of max_new, {toks} tokens, late p99 "
            f"{stats.percentile(o['late_ms'], 99):.2f} ms")
        for r in s:
            if not r.ok:
                say(f"failed request: {r.error}")
                break

    async def run(self) -> dict:
        if not os.path.isdir(os.path.join(ROOT, "seldon_tpu")):
            raise BenchFailure("no seldon_tpu/ beside benchmark/: nothing to measure")
        child = self.start_unit()
        try:
            async with client.Unit(f"http://127.0.0.1:{self.port}") as unit:
                await self.wait_ready(child, unit)
                say(f"load: /ready after {now() - T_START:.1f}s")
                await self.check_metadata(unit)
                await self.warm_up(unit)
                before_probes = await self.probes(unit, stream=False)
                g0 = await unit.gauges(GAUGES)
                k0 = await self.compile_keys(unit)
                await self.measure(unit)
                self.summarise()
                after_probes = await self.probes(unit, stream=True)
                last = await unit.generate(self._req(8, 1, 99))  # refreshes /metrics
                g1 = await unit.gauges(GAUGES)
                k1 = await self.compile_keys(unit)
                md = await unit.get_json("/metadata")
        finally:
            stop_child(child)
        say(f"unit stopped rc={child.returncode}")
        o = self.obs
        # -- counters against the client's own counts
        mine = o.all_results + [last]
        n_ok = sum(1 for r in mine if r.ok) + len(after_probes)
        n_bad = sum(1 for r in mine if not r.ok)
        d = {k: g1[k] - g0[k] for k in GAUGES}
        d_done, d_fail = d["jaxserver_completed"], d["jaxserver_failed_total"]
        if (d_done, d_fail) != (n_ok, n_bad):
            self.problems.append(
                f"/metrics counted {d_done:.0f} completed and {d_fail:.0f} failed, "
                f"the client {n_ok} and {n_bad}")
        steps, disp = d["jaxserver_decode_steps"], d["jaxserver_decode_dispatches"]
        o["decode_steps"], o["decode_dispatches"] = steps, disp
        o["rows_per_step"] = ((d["jaxserver_tokens_out"] - d_done) / steps
                              if steps else None)
        # -- greedy probes repeat, stream and non-stream
        if [t for _, t in before_probes] != [t for _, t in after_probes]:
            self.problems.append("greedy probes changed between set-up (/generate) "
                                 "and after the drain (/generate_stream)")
        # -- no first dispatch after the warm-up
        new = sorted(set(k1) - set(k0))
        o["compile_in_window"] = len(new)
        if new:
            self.problems.append(
                "first dispatch inside lead-in, window or tail: "
                + ", ".join(f"{k} ({k1[k]['first_dispatch_ms'] / 1000:.1f}s)" for k in new))
        mem = [m["peak_bytes_in_use"] for m in md["device"]["memory"]]
        peak = max((m for m in mem if m is not None), default=0)
        o["memory_peak_bytes"] = peak
        self.device["memory_peak_bytes"] = peak
        say(f"rows/step {o['rows_per_step']}, decode steps {steps:.0f} in "
            f"{disp:.0f} dispatches, peak HBM {peak / 1e9:.2f} GB, hop p50 "
            f"{stats.percentile(o['hop_ms'], 50):.2f} ms")
        if self.args.trace:
            self.reduce_trace()
        self.parity(before_probes)
        return self.result()

    def reduce_trace(self) -> None:
        """In a child (the parent stays off JAX), after the unit has gone."""
        wall = {}
        for name in ("started", "stopping"):  # the child's wall clock
            with open(os.path.join(self.profile_dir, name)) as f:
                wall[name] = float(f.read())
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "xplane.py"), self.profile_dir],
            env=env, capture_output=True, text=True, timeout=600)
        if p.returncode != 0:
            raise BenchFailure(f"trace reduction failed: {p.stderr[-2000:]}")
        tr = json.loads(p.stdout.strip().splitlines()[-1])
        # the traced slice on this process's clock
        off = time.time() - now()
        tr["slice"] = (wall["started"] - off, wall["stopping"] - off)
        self.obs["trace"] = tr
        self.device["busy_s"] = tr["busy_s"]
        self.device["window_s"] = tr["window_s"]
        say(f"trace: {tr['window_s']:.2f}s window, busy {tr['busy_s']:.3f}s, "
            f"modules {tr['modules']}")
        shutil.rmtree(self.profile_dir, ignore_errors=True)

    def parity_job(self, probes) -> dict:
        """What the parity child is given. `config_sha` is the digest of the
        criterion and the code that applies it: the configuration's file,
        reference.py and the family's file."""
        h = hashlib.sha256()
        for path in (self.config_file, os.path.join(HERE, "reference.py"),
                     family.file_of(HERE, self.cfg)):
            with open(path, "rb") as f:
                h.update(f.read())
        digest = h.hexdigest()[:16]
        # inside the checkout whatever JAX_COMPILATION_CACHE_DIR says: where two
        # checkouts share a compile cache, neither reads the other's verdict
        marker = os.path.join(ROOT, ".jax_cache", f"benchmark_parity_{self.cfg['name']}.json")
        return {"config": self.config_file, "seed": self.args.seed % (2 ** 31 - 1),
                "probes": probes, "marker": marker, "config_sha": digest}

    def parity(self, probes) -> None:
        """Once per configuration and checkout: the engine's greedy tokens
        against the plain reference's logits, at the widths and depth the
        cell runs, after the unit has freed the chip."""
        if self.args.rehearse:
            self.obs["parity"] = "skipped in a rehearsal (benchmark/tests cover it)"
            return
        job = self.parity_job(probes)
        digest, marker = job["config_sha"], job["marker"]
        if os.path.exists(marker):
            with open(marker) as f:
                m = json.load(f)
            if m.get("config_sha") == digest:
                self.obs["parity"] = m
                if not m["ok"]:
                    self.problems.append(f"parity failed earlier in this checkout: {m}")
                return
        t = now()
        job_file = os.path.join(self.work, "parity_job.json")
        with open(job_file, "w") as f:
            json.dump(job, f)
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run([sys.executable, os.path.join(HERE, "reference.py"), job_file],
                           env=env, capture_output=True, text=True, timeout=900)
        say(f"parity: {now() - t:.1f}s (not part of setup_s)")
        if p.returncode != 0 or not os.path.exists(marker):
            raise BenchFailure(f"parity child failed rc={p.returncode}: {p.stderr[-2000:]}")
        with open(marker) as f:
            m = json.load(f)
        self.obs["parity"] = m
        say(f"parity: {m}")
        if not m["ok"]:
            self.problems.append(f"parity with the plain reference failed: {m}")

    def compared(self) -> list:
        """Each number that `correct` compares, beside its limit."""
        o = self.obs
        lines = [f"compared: failed {o.failed} of {o.attempted} (metrics are +inf "
                 f"over a tenth); compile.in_window {o.compile_in_window} (limit 0); "
                 f"problems {len(self.problems)} (limit 0)"]
        m = o.parity
        if isinstance(m, dict):
            c = m["control"]
            lines.append(
                f"compared: parity of {m['config']} (family {m['family']}, seed "
                f"{m['seed']}, {m['positions']} positions): share within epsilon "
                f"{m['epsilon']} is {m['share_within']} (limit >= {m['min_share_within']}); "
                f"positions beyond {m['epsilon_all']}: {m['over_epsilon_all']} (limit <= "
                f"{m['max_over_epsilon_all']}), widest gap {m['max_gap']}; control "
                f"({c['weights']}): share {c['share_within']}, beyond {c['over_epsilon_all']}, "
                f"widest gap {c['max_gap']}, rejected {c['rejected']}")
        return lines

    def result(self) -> dict:
        o = self.obs
        name = self.cell["name"]
        if self.args.trace:
            ms = metrics.per_layer(self.bench, HERE, name, o)
            e2e = metrics.end_to_end(self.bench, name, o)
            say("traced run's end-to-end values (tracing overhead = these minus "
                "an untraced run's): " + json.dumps({k: v["value"] for k, v in e2e.items()}))
        else:
            ms = metrics.end_to_end(self.bench, name, o)
            say("per-layer readings that need no trace: " + json.dumps(
                {k: v["value"] for k, v in
                 metrics.per_layer(self.bench, HERE, name, o).items()}))
        bad = [k for k, v in ms.items() if not metrics.finite(v["value"])]
        if bad:
            self.problems.append(f"no finite value for {bad}")
            for k in bad:
                ms[k]["value"] = None
        if self.args.rehearse:
            ms = {k + "_cpu_smoke": v for k, v in ms.items()}
        for p in self.problems:
            say("INCORRECT: " + p)
        for line in self.compared():  # the end of stderr is what a refusal keeps
            say(line)
            print("[bench] " + line, file=sys.stderr, flush=True)
        out = {"correct": not self.problems, "attempted": o["attempted"],
               "failed": o["failed"], "metrics": ms, "device": self.device}
        if isinstance(o.parity, dict):  # which comparison decided `correct`
            out["reference"] = {k: o.parity.get(k) for k in
                                ("config", "family", "ok", "epsilon", "min_share_within",
                                 "epsilon_all", "max_over_epsilon_all", "share_within",
                                 "over_epsilon_all", "max_gap", "positions", "control",
                                 "device")}
        tr = o.trace
        if tr:
            out["breakdown"] = {"device_ops": tr["device_ops"][:10],
                                "idle_gaps": tr["idle_gaps"][:10]}
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal with the tiny presets; *_cpu_smoke names")
    args = ap.parse_args(argv)
    try:
        out = asyncio.run(Run(args).run())
    except BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
