"""Load generator: a process of its own that never imports JAX.

Reads one JSON plan on stdin (``traffic.generate`` plus ``base``, ``model``
and ``t0``: the window's first second on this machine's monotonic clock,
which parent and child share), offers the requests over ``/v1/completions``
with token-id prompts and streaming, and writes one JSON document on stdout:
for each request its due and send times and the arrival time of every output
token, in seconds on the window's clock.

Open loop: a request is sent at its due time whether or not earlier ones have
ended (a turn of a session: ``think_s`` after its predecessor ended). Requests
counted in the window are followed to their end. Closed loop: each client
sends its next request when its last one ends, stops asking at the window's
end, and what is then in flight is cut.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import sys
import time

import aiohttp


async def _stream(session, plan, req, out, t0):
    body = {"model": plan["model"], "prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "temperature": 0, "stream": True, "stream_options": {"include_usage": True},
            "nvext": {"ignore_eos": True}}
    rec = {"id": req["id"], "due": req["due"], "sent": time.monotonic() - t0, "tokens": [],
           "ok": False, "want": req["max_tokens"], "counted": req["counted"], "cached": 0,
           "prompt_tokens": len(req["prompt"]), "status": None, "cancelled": False}
    out[req["id"]] = rec
    chunks: list[float] = []
    usage_tokens = None
    try:
        async with session.post(plan["base"] + "/v1/completions", json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                return rec
            async for line in resp.content:
                if not line.startswith(b"data:"):
                    continue
                payload = line[5:].strip()
                if payload == b"[DONE]":
                    continue
                now = time.monotonic() - t0
                doc = json.loads(payload)
                if "error" in doc:
                    return rec
                usage = doc.get("usage")
                if usage:
                    usage_tokens = usage.get("completion_tokens", usage_tokens)
                    rec["cached"] = (usage.get("prompt_tokens_details") or {}).get("cached_tokens", 0)
                if doc.get("choices"):
                    chunks.append(now)
                    rec["tokens"] = chunks
        rec["ok"] = True
    except asyncio.CancelledError:
        rec["cancelled"], rec["ok"] = True, bool(chunks)
        raise
    except Exception as e:  # a refused or broken request is a failed request
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec["end"] = time.monotonic() - t0
        rec["usage_tokens"] = usage_tokens
    return rec


async def _open_loop(session, plan, out, t0):
    done: dict[int, asyncio.Future] = {}
    loop = asyncio.get_running_loop()

    async def one(req):
        due = req["due"]
        if req["after"] is not None:
            prev = await done[req["after"]]
            due = max(due, prev["end"] + req["think_s"])
            req = dict(req, due=due)
        delay = t0 + due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            rec = await _stream(session, plan, req, out, t0)
        finally:
            if not done[req["id"]].done():
                done[req["id"]].set_result(out.get(req["id"], {"end": time.monotonic() - t0}))
        return rec

    for r in plan["requests"]:
        done[r["id"]] = loop.create_future()
    tasks = {r["id"]: asyncio.ensure_future(one(r)) for r in plan["requests"]}
    counted = [tasks[r["id"]] for r in plan["requests"] if r["counted"]]
    await asyncio.gather(*counted, return_exceptions=True)
    rest = [t for t in tasks.values() if not t.done()]
    for t in rest:  # the lead-in's leftovers: nothing waits for them
        t.cancel()
    await asyncio.gather(*rest, return_exceptions=True)


async def _closed_loop(session, plan, out, t0):
    by_id = {r["id"]: r for r in plan["requests"]}
    end = t0 + plan["seconds"]

    async def client(ids, offset):
        delay = t0 - plan["lead_in_s"] + offset - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        for lap in itertools.count():  # a client that runs out of its list starts it again
            for i in ids:
                if time.monotonic() >= end:
                    return
                req = dict(by_id[i], id=i + lap * len(by_id), due=time.monotonic() - t0)
                await _stream(session, plan, req, out, t0)

    # Clients start a few milliseconds apart, in order: no thundering herd on connect.
    tasks = [asyncio.ensure_future(client(ids, 0.005 * k)) for k, ids in enumerate(plan["clients"])]
    await asyncio.sleep(max(0.0, end - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def amain() -> int:
    plan = json.loads(sys.stdin.read())
    t0 = float(plan["t0"])
    out: dict[int, dict] = {}
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        if plan["loop"] == "closed":
            await _closed_loop(session, plan, out, t0)
        else:
            await _open_loop(session, plan, out, t0)
    sys.stdout.write(json.dumps({"results": [out[k] for k in sorted(out)],
                                 "never_sent": [r["id"] for r in plan["requests"]
                                                if r["id"] not in out and plan["loop"] == "open"]}))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(amain()))
