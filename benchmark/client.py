"""HTTP side of the benchmark: the unit's REST routes from one asyncio
loop (one process, one thread), and the open load loop.

Times are time.perf_counter() seconds of this process. An open-loop
request is timed from when it was DUE, not from when it was sent."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Callable, Dict, Iterable, List, Optional

import aiohttp

from traffic import Request

now = time.perf_counter


@dataclasses.dataclass
class Result:
    req: Request
    due: float                   # absolute; == sent outside the load loop
    sent: float = 0.0
    first: Optional[float] = None  # first token seen
    last: Optional[float] = None   # last token seen
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: int = 0
    error: str = ""
    unit_ms: float = 0.0         # the unit's own total_ms (/generate only)

    @property
    def ok(self) -> bool:
        return not self.error


class Unit:
    """The unit's REST surface. Loopback only, never through a proxy."""

    def __init__(self, base: str, vocab: int = 0):
        self.base = base
        self.vocab = vocab
        self._session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self) -> "Unit":
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=600),
            trust_env=False,
        )
        return self

    async def __aexit__(self, *exc) -> None:
        await self._session.close()

    async def get(self, path: str, timeout: float = 60.0):
        async with self._session.get(
                self.base + path,
                timeout=aiohttp.ClientTimeout(total=timeout)) as r:
            body = await r.read()
            return r.status, body

    async def get_json(self, path: str, timeout: float = 60.0) -> Optional[Dict]:
        status, body = await self.get(path, timeout)
        return json.loads(body) if status == 200 else None

    async def gauges(self, names: Iterable[str]) -> Dict[str, float]:
        """Named gauges of /metrics (Prometheus text)."""
        status, body = await self.get("/metrics")
        if status != 200:
            raise RuntimeError(f"/metrics -> HTTP {status}")
        out: Dict[str, float] = {}
        for ln in body.decode().splitlines():
            for n in names:
                if ln.startswith(n + " ") or ln.startswith(n + "{"):
                    out.setdefault(n, float(ln.rsplit(" ", 1)[1]))
        return out

    def _body(self, ids: List[int], max_new: int) -> Dict:
        # Greedy throughout: a request's output is a function of the
        # weights and the prompt.
        return {"prompt_token_ids": ids, "max_new_tokens": max_new,
                "temperature": 0.0}

    def _check(self, res: Result) -> None:
        """A response counts as succeeded only with in-vocabulary tokens
        and no more of them than asked (none at all is a sampled EOS at
        the first position: short, not failed)."""
        t = res.tokens
        if res.error:
            return
        if res.first is None:
            res.first = res.last = now()
        if len(t) > res.req.max_new:
            res.error = f"{len(t)} tokens, asked for {res.req.max_new}"
        elif self.vocab and not all(
                isinstance(x, int) and 0 <= x < self.vocab for x in t):
            res.error = "token id outside the vocabulary"

    async def generate(self, req: Request) -> Result:
        """POST /generate (whole answer at once)."""
        res = Result(req, due=now())
        res.sent = res.due
        try:
            async with self._session.post(
                    self.base + "/generate",
                    json=self._body(req.prompt_ids, req.max_new)) as r:
                res.status = r.status
                raw = await r.read()
                res.first = res.last = now()
                if r.status != 200:
                    res.error = f"HTTP {r.status}: {raw[:200]!r}"
                else:
                    out = json.loads(raw)
                    res.tokens = list(out.get("token_ids", []))
                    res.unit_ms = float(out.get("total_ms", 0.0))
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            res.error = f"{type(e).__name__}: {e}"
        self._check(res)
        return res

    async def stream(self, req: Request, due: Optional[float] = None) -> Result:
        """POST /generate_stream (NDJSON, one line per decode burst)."""
        res = Result(req, due=now() if due is None else due)
        res.sent = now()
        try:
            async with self._session.post(
                    self.base + "/generate_stream",
                    json=self._body(req.prompt_ids, req.max_new)) as r:
                res.status = r.status
                if r.status != 200:
                    raw = await r.read()
                    res.error = f"HTTP {r.status}: {raw[:200]!r}"
                else:
                    async for line in r.content:
                        if not line.strip():
                            continue
                        t = now()
                        chunk = json.loads(line)
                        if "error" in chunk:
                            res.error = f"stream error: {chunk['error']}"
                            break
                        toks = chunk.get("token_ids", [])
                        if toks:
                            if res.first is None:
                                res.first = t
                            res.last = t
                            res.tokens.extend(toks)
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            res.error = f"{type(e).__name__}: {e}"
        self._check(res)
        return res


async def run_open(unit: Unit, reqs: List[Request], t0: float,
                   on_profile: Callable[[float], None] = None) -> List[Result]:
    """Open loop: send each request when it is due (t0 + req.due),
    whatever the unit is doing. The tail is cut once every window request
    has finished; what is in flight then drains."""
    results: List[Result] = []
    tasks: List[asyncio.Task] = []
    window_left = sum(1 for r in reqs if r.phase == "window")

    async def one(req: Request, due: float) -> None:
        nonlocal window_left
        res = await unit.stream(req, due)
        results.append(res)
        if req.phase == "window":
            window_left -= 1

    def tail_over(req: Request) -> bool:
        return req.phase == "tail" and window_left == 0

    for req in reqs:
        due = t0 + req.due
        # short sleeps, so that the tail's end is noticed and the
        # profiler's marks are on time
        while now() < due and not tail_over(req):
            await asyncio.sleep(max(0.0, min(due - now(), 0.05)))
            if on_profile:
                on_profile(now())
        if tail_over(req):
            break
        tasks.append(asyncio.create_task(one(req, due)))
    while window_left > 0:
        await asyncio.sleep(0.05)
        if on_profile:
            on_profile(now())
    if tasks:
        await asyncio.gather(*tasks)
    return results
