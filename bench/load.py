"""The load client: runs a traffic plan against ``POST /v1/generate`` from a
thread of its own with its own event loop, so that a Router round that
blocks the server's loop shows as latency and not as a late generator.

Every request is timed from when it came due (open loop) or was sent
(closed loop); each streamed token is stamped when it arrives.  A closed
loop's client ``i`` sends its first request once the clients together have
been streamed ``start_tokens[i]`` tokens: a count, not a clock, so that
every run admits its clients in the same engine rounds.
"""
from __future__ import annotations

import asyncio
import json
import threading
import time


class Record:
    __slots__ = ("prompt", "max_new", "due", "sent", "times", "tokens",
                 "status", "code")

    def __init__(self, prompt, max_new, due):
        self.prompt = prompt
        self.max_new = max_new
        self.due = due
        self.sent = None
        self.times: list = []
        self.tokens: list = []
        self.status = None            # terminal status, or None in flight
        self.code = None


async def _request(port: int, rec: Record, on_token=None) -> None:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps({"prompt": rec.prompt.tolist(),
                           "max_new": rec.max_new}).encode()
        rec.sent = time.perf_counter()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        rec.code = int(head.split(b" ", 2)[1])
        if rec.code != 200:
            rec.status = "refused"
            return
        while True:
            n = int((await reader.readuntil(b"\r\n")).strip(), 16)
            if n == 0:
                return
            ev = json.loads((await reader.readexactly(n + 2))[:-2])
            if "token" in ev:
                rec.times.append(time.perf_counter())
                rec.tokens.append(int(ev["token"]))
                if on_token is not None:
                    on_token()
            elif ev.get("restart"):
                rec.times.clear()
                rec.tokens.clear()
            else:
                rec.status = ev.get("status")
    finally:
        writer.close()


class LoadClient:
    """Drives ``plan`` (generators/) from ``t0``: requests come due up to
    ``stop_t``; requests in flight at ``stop_t`` are left running until
    ``close()``."""

    def __init__(self, port: int, plan: dict, t0: float, stop_t: float):
        self.port = port
        self.plan = plan
        self.t0 = t0
        self.stop_t = stop_t
        self.records: list = []
        self.errors: list = []
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._main, daemon=True)
        self._tasks: set = set()
        self.streamed = 0                   # tokens streamed to all clients
        self._progress = asyncio.Event()     # set on every streamed token

    def start(self) -> None:
        self._thread.start()

    def close(self) -> None:
        """Drop every connection still open and stop the thread."""
        def cancel():
            for t in list(self._tasks):
                t.cancel()
        if not self._loop.is_closed():     # not yet through its plan
            try:
                self._loop.call_soon_threadsafe(cancel)
            except RuntimeError:            # closed meanwhile
                pass
        self._thread.join(timeout=30)

    def _spawn(self, coro):
        t = self._loop.create_task(coro)
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)
        return t

    async def _one(self, rec: Record) -> None:
        try:
            await _request(self.port, rec, self._streamed)
        except asyncio.CancelledError:
            raise
        except Exception as e:                  # noqa: BLE001
            rec.status = "error"
            self.errors.append(repr(e))

    async def _open(self) -> None:
        runs = []
        for r in self.plan["requests"]:
            due = self.t0 + r["due"]
            if due >= self.stop_t:
                break
            await asyncio.sleep(max(0.0, due - time.perf_counter()))
            rec = Record(r["prompt"], r["max_new"], due)
            self.records.append(rec)
            runs.append(self._spawn(self._one(rec)))
        await asyncio.gather(*runs, return_exceptions=True)

    def _streamed(self) -> None:
        self.streamed += 1
        self._progress.set()

    async def _closed_client(self, start_tokens: int, reqs: list) -> None:
        while self.streamed < start_tokens:
            self._progress.clear()
            await self._progress.wait()
        for r in reqs:
            now = time.perf_counter()
            if now >= self.stop_t:
                return
            rec = Record(r["prompt"], r["max_new"], now)
            self.records.append(rec)
            await self._one(rec)

    async def _drive(self) -> None:
        if self.plan["kind"] == "open":
            await self._open()
        else:
            await asyncio.gather(*(
                self._spawn(self._closed_client(s, reqs))
                for s, reqs in zip(self.plan["start_tokens"],
                                   self.plan["clients"])),
                return_exceptions=True)

    def _main(self) -> None:
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(self._spawn(self._drive()))
        except asyncio.CancelledError:
            pass
        finally:
            rest = asyncio.all_tasks(self._loop)
            for t in rest:
                t.cancel()
            if rest:
                self._loop.run_until_complete(
                    asyncio.gather(*rest, return_exceptions=True))
            self._loop.close()
