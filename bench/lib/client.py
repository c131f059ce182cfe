"""The load generator: an asyncio HTTP/1.1 client for the engine's own fast
lane — SSE generation streams (``/api/v0.1/generate/stream``) and the
control documents (``/stats``, ``/genperf``, ``/perf``, ``/profile/*``).
One thread, one event loop, a new connection per request.

Open loop: every request is sent at its due time whether or not earlier
ones have finished, and is timed from when it was DUE."""

from __future__ import annotations

import asyncio
import json
import time
from typing import Callable, List, Optional

from lib.arith import tpot_ms

STREAM_PATH = "/api/v0.1/generate/stream"


def stream_body(tokens: List[int], max_new: int, chunk: int) -> bytes:
    return json.dumps({
        "data": {"ndarray": [[float(t) for t in tokens]]},
        "max_new": int(max_new), "chunk": int(chunk),
    }).encode()


def rows_body(rows: List[List[int]], max_new: int, chunk: int) -> bytes:
    """A request of several rows of one length (the warm-up ladder)."""
    return json.dumps({
        "data": {"ndarray": [[float(t) for t in r] for r in rows]},
        "max_new": int(max_new), "chunk": int(chunk),
    }).encode()


async def http_json(port: int, method: str, path: str,
                    body: Optional[dict] = None, timeout: float = 60.0):
    """One control request; returns (status, parsed JSON or raw text)."""
    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            raw = json.dumps(body).encode() if body is not None else b""
            head = (f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(raw)}\r\n"
                    f"Connection: close\r\n\r\n").encode()
            writer.write(head + raw)
            await writer.drain()
            status_line = await reader.readline()
            status = int(status_line.split()[1])
            clen = None
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                if line.lower().startswith(b"content-length:"):
                    clen = int(line.split(b":", 1)[1])
            data = (await reader.readexactly(clen) if clen is not None
                    else await reader.read())
            try:
                return status, json.loads(data)
            except ValueError:
                return status, data.decode("utf-8", "replace")
        finally:
            writer.close()
    return await asyncio.wait_for(go(), timeout)


async def stream_once(port: int, body: bytes, now: Callable[[], float],
                      timeout: float) -> dict:
    """Send one SSE generation request and read it to its end.  Returns
    status, the times of the first and last token chunk (``now()`` clock),
    the tokens per row and whether the stream reached its done event."""
    rec = {"status": 0, "t_sent": None, "t_first": None, "t_last": None,
           "tokens": None, "done": False, "error": None}

    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            head = (f"POST {STREAM_PATH} HTTP/1.1\r\nHost: bench\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode()
            writer.write(head + body)
            rec["t_sent"] = now()
            await writer.drain()
            status_line = await reader.readline()
            rec["status"] = int(status_line.split()[1])
            if rec["status"] != 200:
                rec["error"] = (await reader.read(400)).decode(
                    "utf-8", "replace")
                return
            rows = None
            while True:
                line = await reader.readline()
                if not line:
                    break  # closed without a done event
                if not line.startswith(b"data: "):
                    continue
                t = now()
                event = json.loads(line[6:])
                if "error" in event:
                    rec["error"] = str(event)[:300]
                    break
                if event.get("done"):
                    rec["done"] = True
                    break
                chunk_rows = event["tokens"]
                if rows is None:
                    rows = rec["tokens"] = [[] for _ in chunk_rows]
                    rec["t_first"] = t
                rec["t_last"] = t
                for r, c in zip(rows, chunk_rows):
                    r.extend(c)
        finally:
            writer.close()

    try:
        await asyncio.wait_for(go(), timeout)
    except (asyncio.TimeoutError, OSError, ValueError, IndexError,
            asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def finish_record(req, rec: dict, vocab: int, seconds: float,
                  reserved=()) -> dict:
    """One request's line of the run: times relative to the window, the
    checks every answer gets (exactly ``out_len`` ids, all in range and
    none that the configuration reserves: a mask id is never an answer)
    and its TTFT / TPOT.  A request that failed, was refused or had not
    finished when the window ended at ``seconds`` is charged as if its
    missing chunks arrived at that instant: a first token it never got
    counts at the window's end, and its time per output token is the time
    from its first token (or from when it was due) to the window's end
    over the tokens it did get.  So a tail over all requests SENT gets
    worse when requests fail, never better."""
    toks = rec["tokens"][0] if rec["tokens"] else []
    in_range = all(float(t) == int(t) and 0 <= int(t) < vocab
                   and int(t) not in reserved for t in toks)
    ok = (rec["status"] == 200 and rec["done"] and rec["error"] is None
          and len(toks) == req.out_len and in_range)
    out = {
        "index": req.index, "due_s": req.due_s, "prompt_len": req.prompt_len,
        "out_len": req.out_len, "measured": req.measured,
        "status": rec["status"], "ok": ok, "n_out": len(toks),
        "error": rec["error"],
        "t_sent": rec["t_sent"], "t_first": rec["t_first"],
        "t_last": rec["t_last"],
        "late_ms": (None if rec["t_sent"] is None
                    else 1e3 * (rec["t_sent"] - req.due_s)),
    }
    if ok:
        out["ttft_ms"] = 1e3 * (rec["t_first"] - req.due_s)
        out["tpot_ms"] = tpot_ms(rec["t_first"], rec["t_last"], len(toks))
    else:
        end = max(seconds, req.due_s)
        first = rec["t_first"] if rec["t_first"] is not None else end
        out["ttft_ms"] = 1e3 * (first - req.due_s)
        since = rec["t_first"] if rec["t_first"] is not None else req.due_s
        out["tpot_ms"] = 1e3 * (end - since) / max(len(toks) - 1, 1)
    return out


async def run_open_loop(port: int, requests: list, bodies: List[bytes],
                        vocab: int, t0: float, seconds: float,
                        inflight_samples: Optional[list] = None,
                        reserved=()) -> List[dict]:
    """Offer every request at ``t0 + due_s`` (``time.monotonic`` clock) and
    stop reading at ``t0 + seconds``: what has not finished by then has
    failed."""
    def now():
        return time.monotonic() - t0

    records: List[Optional[dict]] = [None] * len(requests)
    inflight = [0]

    async def one(i, req):
        delay = req.due_s - now()
        if delay > 0:
            await asyncio.sleep(delay)
        inflight[0] += 1
        try:
            rec = await stream_once(port, bodies[i], now,
                                    max(seconds - now(), 0.05))
        finally:
            inflight[0] -= 1
        records[i] = finish_record(req, rec, vocab, seconds, reserved)

    async def sampler():
        while True:
            inflight_samples.append((now(), inflight[0]))
            await asyncio.sleep(0.25)

    tasks = [asyncio.ensure_future(one(i, r)) for i, r in enumerate(requests)]
    samp = (asyncio.ensure_future(sampler())
            if inflight_samples is not None else None)
    try:
        await asyncio.gather(*tasks)
    finally:
        if samp is not None:
            samp.cancel()
    return records
