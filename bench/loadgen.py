"""The load generator: drives ``POST /v1/completions`` over sockets.

Standard library only, so it runs in a child process that never
imports JAX: the server's drive loop and the generator then share
neither an event loop nor an interpreter lock.

    python3 bench/loadgen.py < spec.json > result.json

The spec (made by the harness from a traffic kind) is one JSON object:

* ``host``, ``port``: the server;
* ``mode``: ``open`` (each request is sent when it is due, whether or
  not earlier ones have finished) or ``closed`` (each client sends its
  next request when its last one has finished);
* ``t0``: the window's start on the ``time.monotonic`` clock (half a
  second after the generator starts when absent); ``seconds``: the
  window; ``drain_s``: how long after the window
  an open-loop request may take to finish before it counts as never
  answered;
* ``requests`` (open): ``[{"due": s, "prompt": [ids], "max_new": n}]``,
  ``due`` measured from the window's start;
* ``clients`` (closed): one list of ``{"prompt", "max_new"}`` per
  client, sent in order.

The result has the window's start and end (``time.monotonic``, one
clock for every process of the machine) and, per request, when it was
due and sent, its HTTP status, the arrival time and size of every token
event, the token ids, the finish reason and any error. A closed-loop
request still streaming at the window's end is hung up on there and
keeps the events it had.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time


def _record(client: int, index: int, due: float) -> dict:
    return {"client": client, "index": index, "due": due, "sent": None,
            "status": None, "events": [], "tokens": [], "finish": None,
            "error": None}


async def complete(host: str, port: int, prompt, max_new: int,
                   rec: dict) -> None:
    """One streamed completion; fills ``rec`` as events arrive, so a
    cancelled call leaves what it had received."""
    body = json.dumps({"prompt": list(prompt), "max_new_tokens": max_new,
                       "stream": True}).encode()
    rec["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        rec["status"] = status
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if status != 200:
            rec["error"] = (await reader.read()).decode(errors="replace")
            return
        while True:
            line = await reader.readline()
            if not line:
                rec["error"] = rec["error"] or "stream ended early"
                return
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                return
            ev = json.loads(data)
            if "error" in ev:
                rec["error"] = str(ev["error"])
                continue
            choice = ev["choices"][0]
            ids = choice["delta"]["token_ids"]
            if ids:
                rec["events"].append([time.monotonic(), len(ids)])
                rec["tokens"].extend(ids)
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
    finally:
        writer.close()


async def _guarded(host, port, prompt, max_new, rec):
    try:
        await complete(host, port, prompt, max_new, rec)
    except (OSError, ValueError, IndexError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"


async def run_open(spec: dict, t0: float) -> list[dict]:
    recs, tasks = [], []
    for i, r in enumerate(spec["requests"]):
        rec = _record(0, i, r["due"])
        recs.append(rec)
        delay = t0 + r["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(_guarded(
            spec["host"], spec["port"], r["prompt"], r["max_new"], rec)))
    end = t0 + spec["seconds"] + spec["drain_s"]
    if tasks:
        _, pending = await asyncio.wait(
            tasks, timeout=max(0.0, end - time.monotonic()))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for rec in recs:
        if rec["finish"] is None and rec["error"] is None:
            rec["error"] = "not answered within the drain"
    return recs


async def run_closed(spec: dict, t0: float) -> list[dict]:
    t1 = t0 + spec["seconds"]
    recs: list[dict] = []

    async def client(c: int, reqs: list[dict]):
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        for i, r in enumerate(reqs):
            now = time.monotonic()
            if now >= t1:
                return
            rec = _record(c, i, now - t0)
            recs.append(rec)
            task = asyncio.create_task(_guarded(
                spec["host"], spec["port"], r["prompt"], r["max_new"], rec))
            done, _ = await asyncio.wait({task},
                                         timeout=t1 - time.monotonic())
            if not done:
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)
                return
        recs.append({**_record(c, len(reqs), time.monotonic() - t0),
                     "error": "client ran out of requests"})

    await asyncio.gather(*(client(c, reqs)
                           for c, reqs in enumerate(spec["clients"])))
    return recs


async def main_async(spec: dict) -> dict:
    t0 = spec.get("t0") or time.monotonic() + 0.5
    if spec["mode"] == "open":
        recs = await run_open(spec, t0)
    elif spec["mode"] == "closed":
        recs = await run_closed(spec, t0)
    else:
        raise ValueError(f"unknown mode {spec['mode']!r}")
    return {"t0": t0, "t1": t0 + spec["seconds"], "requests": recs}


def main() -> int:
    spec = json.load(sys.stdin)
    out = asyncio.run(main_async(spec))
    json.dump(out, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
