"""Open-loop streaming client of the sidecar's chat-completions endpoint.

It runs on a thread and event loop of its own, so that the server's loop
cannot hold back its sends.  Each request goes out at its due time over a
new loopback connection, with ``"stream": true``; the client records when
each content delta arrived, the text and the terminal status, and how late
each send was.  Every time is ``time.monotonic()``.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Record:
    index: int
    klass: str
    max_tokens: int
    prompt: str
    due: float                      # monotonic due time
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    deltas: list = field(default_factory=list)
    status: Optional[int] = None    # HTTP status
    finish: Optional[str] = None    # finish_reason of the last frame
    done: bool = False              # the stream ended with [DONE]
    error: Optional[str] = None
    request_id: Optional[int] = None

    @property
    def text(self) -> str:
        return "".join(self.deltas)

    @property
    def n_tokens(self) -> int:
        return len(self.text.split())

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done and self.finish == "stop"
                and self.error is None)


def body(rec: Record) -> bytes:
    """The request body.  ``output_tokens`` and ``klass`` are the sidecar's
    oracle fields: the generation length and the true class, which only
    ``sjf_oracle`` would read; ``sjf`` ranks by the predictor."""
    return json.dumps({
        "messages": [{"role": "user", "content": rec.prompt}],
        "max_tokens": rec.max_tokens, "output_tokens": rec.max_tokens,
        "klass": rec.klass, "stream": True}).encode()


async def fetch(host: str, port: int, rec: Record) -> None:
    """Send one request and read its SSE stream to the end."""
    payload = body(rec)
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"POST /v1/chat/completions HTTP/1.1\r\n"
                      f"Host: {host}:{port}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(payload)}\r\n\r\n").encode()
                     + payload)
        await writer.drain()
        status = await reader.readline()
        rec.status = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec.status != 200:
            rec.error = (await reader.read()).decode(errors="replace")
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                rec.done = True
                break
            frame = json.loads(data)
            if "error" in frame:
                rec.error = json.dumps(frame["error"])
                continue
            rec.request_id = int(frame["id"].rsplit("-", 1)[1])
            choice = frame["choices"][0]
            text = choice["delta"].get("content")
            if text:
                now = time.monotonic()
                if rec.t_first is None:
                    rec.t_first = now
                rec.t_last = now
                rec.deltas.append(text)
            if choice.get("finish_reason"):
                rec.finish = choice["finish_reason"]
    finally:
        writer.close()


class OpenLoop:
    """Sends ``records`` at their due times from a thread of its own, then
    waits for the open ones until ``deadline`` (monotonic); a request still
    open then is cancelled and stays failed."""

    def __init__(self, host: str, port: int, records: list,
                 deadline: float):
        self.host, self.port = host, port
        self.records = records
        self.deadline = deadline
        self.lateness = []             # send time - due time, per request
        self.unfinished = 0
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="chipbench-client")

    def start(self) -> None:
        self._thread.start()

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("client thread did not finish")

    def _main(self) -> None:
        asyncio.run(self._run())

    async def _one(self, rec: Record) -> None:
        try:
            await fetch(self.host, self.port, rec)
        except asyncio.CancelledError:
            raise
        except Exception as e:            # recorded: a failed request
            rec.error = f"{type(e).__name__}: {e}"

    async def _run(self) -> None:
        tasks = []
        for rec in self.records:
            delay = rec.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            self.lateness.append(time.monotonic() - rec.due)
            tasks.append(asyncio.create_task(self._one(rec)))
        if tasks:
            _, pending = await asyncio.wait(
                tasks, timeout=max(0.0, self.deadline - time.monotonic()))
            self.unfinished = len(pending)
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
