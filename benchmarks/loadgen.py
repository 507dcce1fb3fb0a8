"""The client side: requests over HTTP to the frontend, timed on the host
clock of this one process (one thread, asyncio).

Every request is ``POST /v1/completions`` with the prompt as token ids,
``stream: true``, greedy, ``nvext.ignore_eos``. The benchmark's tokenizer
names each token's id in the streamed text (``modeldir.py``), so a chunk's
token count is exact. In an open loop a request is timed from when it was
*due*, not from when it was sent, so a stalled server or a late generator
shows in the latency of the requests behind it; how late the generator
itself ran is reported apart.
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from traffic import Request

DRAIN_S = 30.0     # requests not finished this long after the window fail


class Client:
    def __init__(self, base_url: str, model: str):
        self.url = base_url + "/v1/completions"
        self.model = model
        self.session: aiohttp.ClientSession = None
        self.t0 = 0.0                 # monotonic origin of the timeline
        self.window = (0.0, 0.0)      # measured window on the timeline
        self.window_tokens = 0        # tokens streamed inside the window
        self.last_token = -1.0        # when the newest token of any request
        #                               arrived, and the longest silence
        self.longest_silence = 0.0    # between two arrivals since a reset
        self.tasks: set = set()

    async def __aenter__(self):
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300))
        return self

    async def __aexit__(self, *exc):
        await self.cancel_all()
        await self.session.close()

    async def cancel_all(self) -> None:
        """Drop every request still open: closing the connection cancels
        the sequence in the server."""
        tasks = list(self.tasks)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await asyncio.sleep(1.0)

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def send(self, r: Request, extra: dict = None) -> list:
        """Stream one completion into ``r``; returns the parsed chunks that
        carry ``logprobs`` (only the probes ask for them)."""
        body = {"model": self.model, "prompt": r.prompt,
                "max_tokens": r.max_tokens, "stream": True,
                "temperature": 0, "nvext": {"ignore_eos": True}}
        if extra:
            body.update(extra)
        kept = []
        r.sent = self.now()
        try:
            async with self.session.post(self.url, json=body) as resp:
                if resp.status != 200:
                    r.error = f"HTTP {resp.status}: " \
                        f"{(await resp.text())[:200]}"
                    return kept
                done = False
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    data = raw[5:].strip()
                    if data == b"[DONE]":
                        done = True
                        break
                    now = self.now()
                    n = data.count(b"<") if extra is None else 0
                    if extra is not None or b'"error"' in data:
                        chunk = json.loads(data)
                        if chunk.get("error"):
                            r.error = str(chunk["error"])[:200]
                            return kept
                        choice = chunk["choices"][0]
                        n = choice.get("text", "").count("<")
                        if choice.get("logprobs"):
                            kept.append(choice["logprobs"])
                    if n:
                        if self.last_token >= 0:
                            self.longest_silence = max(
                                self.longest_silence, now - self.last_token)
                        self.last_token = now
                        if r.first < 0:
                            r.first = now
                        r.last = now
                        r.tokens += n
                        if self.window[0] <= now < self.window[1]:
                            self.window_tokens += n
                r.ok = done and r.tokens == r.max_tokens
                if not r.ok and not r.error:
                    r.error = (f"{r.tokens} of {r.max_tokens} tokens, "
                               f"done={done}")
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError) as e:
            r.error = f"{type(e).__name__}: {e}"[:200]
        return kept

    def settled(self, armed_at: float, quiet_s: float) -> bool:
        """Has a burst of tokens just ended? True once nothing has arrived
        for ``quiet_s`` and the burst that ended belongs to the time since
        ``armed_at`` (it may have begun a little before: a burst takes a
        moment to stream)."""
        return (self.last_token >= armed_at - quiet_s / 2
                and self.now() - self.last_token >= quiet_s)

    def launch(self, coro) -> asyncio.Task:
        t = asyncio.ensure_future(coro)
        self.tasks.add(t)
        t.add_done_callback(self.tasks.discard)
        return t

    async def send_at(self, r: Request) -> None:
        delay = r.due - self.now()
        if delay > 0:
            await asyncio.sleep(delay)
        await self.send(r)

    async def wait_done(self, requests: list, deadline: float) -> None:
        """Until every request has an outcome or the timeline passes
        ``deadline``; what is still open then has failed."""
        while self.now() < deadline and any(
                not (r.ok or r.error) for r in requests):
            await asyncio.sleep(0.05)
        for r in requests:
            if not (r.ok or r.error):
                r.error = f"not finished {DRAIN_S:.0f}s after the window"
