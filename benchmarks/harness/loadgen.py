"""Load generator: SSE clients over the HTTP proxy, from ONE thread
(asyncio), closed or open loop. Every token is stamped when the client
receives it; nothing is averaged here.
"""

from __future__ import annotations

import asyncio
import json
import time

from harness import traffic


class Request:
    __slots__ = ("index", "prompt_len", "max_new", "t_due", "t_send",
                 "t_tokens", "tokens_ok", "error", "t_end")

    def __init__(self, index, prompt_len, max_new, t_due):
        self.index = index
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.t_due = t_due
        self.t_send = None
        self.t_tokens = []      # arrival time of every output token
        self.tokens_ok = True   # every id an int inside the vocabulary
        self.error = None
        self.t_end = None       # `event: done` (or the failure) seen


async def _sse(addr, route, req: Request, tokens, vocab: int):
    """One streamed request. Fills ``req``; never raises except on
    cancellation (the run ending with the request in flight)."""
    body = json.dumps({"tokens": tokens,
                       "max_new_tokens": req.max_new}).encode()
    head = (f"POST {route} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Accept: text/event-stream\r\nX-Request-Deadline: 600\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    writer = None
    try:
        req.t_send = time.monotonic()
        reader, writer = await asyncio.open_connection(
            addr["host"], addr["port"], limit=1 << 20)
        writer.write(head.encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            rest = await reader.read(400)
            raise RuntimeError(f"{status.decode().strip()} {rest[-200:]!r}")
        done = False
        while True:
            line = await reader.readline()
            if not line:
                break
            if line.startswith(b"data: {\"token\""):
                req.t_tokens.append(time.monotonic())
                tok = json.loads(line[6:])["token"]
                if not (isinstance(tok, int) and 0 <= tok < vocab):
                    req.tokens_ok = False
            elif line.startswith(b"event: done"):
                done = True
                break
            elif line.startswith(b"event:"):
                detail = await reader.read(400)
                raise RuntimeError(f"{line.decode().strip()} {detail!r}")
        if not done:
            raise RuntimeError("stream closed without `event: done`")
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 - a failed request is a datum
        req.error = f"{type(e).__name__}: {e}"
    finally:
        req.t_end = time.monotonic()
        if writer is not None:
            writer.close()


class Load:
    """The offered load of one run. ``start()`` begins it; it keeps
    going (cycling the seed's order) until ``stop()``."""

    def __init__(self, addr, route, params: dict, seed: int, vocab: int,
                 horizon_s: float):
        self.addr, self.route, self.vocab = addr, route, vocab
        self.params, self.seed = params, seed
        self.order = traffic.order(params, seed)
        self.requests: list[Request] = []
        self.late_s: list[float] = []   # open loop: send minus due
        self._next = 0
        self._tasks: list[asyncio.Task] = []
        self._horizon = horizon_s
        self.t_start = None

    def _new(self, t_due, cut: float = 1.0) -> tuple[Request, list]:
        i = self._next
        self._next += 1
        p, o = self.order[i % len(self.order)]
        o = max(2, round(o * cut))
        req = Request(i, p, o, t_due)
        self.requests.append(req)
        return req, traffic.prompt_tokens(self.seed, i, p, self.vocab)

    async def _caller(self, delay: float, first_cut: float):
        """``first_cut`` shortens this caller's FIRST reply to a share
        of its length, as if the run had joined a job already under way:
        the callers then finish out of step from the start instead of
        marching in the cohorts they were started in."""
        await asyncio.sleep(delay)
        cut = first_cut
        while True:
            req, toks = self._new(time.monotonic(), cut)
            cut = 1.0
            await _sse(self.addr, self.route, req, toks, self.vocab)

    async def _arrivals(self):
        for due in traffic.arrivals(self.params, self.seed,
                                    self._horizon):
            t_due = self.t_start + due
            wait = t_due - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            self.late_s.append(time.monotonic() - t_due)
            req, toks = self._new(t_due)
            self._tasks.append(asyncio.create_task(
                _sse(self.addr, self.route, req, toks, self.vocab)))

    def start(self):
        self.t_start = time.monotonic()
        if self.params["kind"] == "closed":
            for d, cut in zip(traffic.staggers(self.params, self.seed),
                              traffic.first_cuts(self.params, self.seed)):
                self._tasks.append(
                    asyncio.create_task(self._caller(d, cut)))
        elif self.params["kind"] == "open":
            self._tasks.append(asyncio.create_task(self._arrivals()))
        else:
            raise ValueError(f"traffic kind {self.params['kind']!r}")

    async def stop(self):
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)


async def warm(addr, route, shapes, seed: int, vocab: int,
               concurrency: int = 4) -> list:
    """Send each warm shape once, a few at a time; returns the
    requests (a failed one fails the run)."""
    sem = asyncio.Semaphore(concurrency)
    reqs = []

    async def one(i, p, o):
        req = Request(-1 - i, p, o, time.monotonic())
        reqs.append(req)
        async with sem:
            await _sse(addr, route, req,
                       traffic.prompt_tokens(seed, -1 - i, p, vocab),
                       vocab)
    await asyncio.gather(*[one(i, p, o)
                           for i, (p, o) in enumerate(shapes)])
    return reqs
