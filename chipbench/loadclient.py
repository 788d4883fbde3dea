#!/usr/bin/env python3
"""The load generator's client: one process, one thread, asyncio, standard
library only (it never imports jax, so it cannot take the chip).

    python3 chipbench/loadclient.py <schedule.json> <records.json> <host> <port>

It reads the schedule, waits for the line "go" on standard input, then sends
each request of an open loop at its due time (or runs each client of a closed
loop until the window closes), streams `/v1/completions` over SSE, and writes
one record a request: when it was due, when it was sent, when each token
came (seconds since "go"), the token ids, how it ended.  Every answer is
waited for, `drain_s` past the close at the most.
"""

import asyncio
import json
import sys
import time


async def one_request(host, port, req, t0, rec):
    payload = json.dumps(req["body"]).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n")
    rec["sent_s"] = time.monotonic() - t0
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(head.encode() + payload)
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1]) if status else 0
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                rec["done"] = True
                break
            choice = json.loads(data)["choices"][0]
            ids = choice.get("token_ids") or []
            if ids:
                now = time.monotonic() - t0
                rec["token_s"].extend([now] * len(ids))
                rec["token_ids"].extend(int(i) for i in ids)
            if choice.get("finish_reason") is not None:
                rec["finish"] = choice["finish_reason"]
    except Exception as e:           # a failed request is a record
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        writer.close()
    rec["end_s"] = time.monotonic() - t0


def new_record(req):
    return {"index": req["index"], "due_s": req["due_s"],
            "client": req["client"], "greedy": req["greedy"],
            "prompt_len": req["prompt_len"], "max_tokens": req["max_tokens"],
            "sent_s": None, "end_s": None, "status": None, "done": False,
            "finish": None, "token_s": [], "token_ids": [], "error": None}


async def open_loop(host, port, sched, t0, records):
    async def at_due(req, rec):
        delay = req["due_s"] - (time.monotonic() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        await one_request(host, port, req, t0, rec)

    tasks = []
    for req in sched["requests"]:
        rec = new_record(req)
        records.append(rec)
        tasks.append(asyncio.ensure_future(at_due(req, rec)))
    await asyncio.wait(tasks, timeout=sched["seconds"] + sched["drain_s"])
    for t in tasks:
        if not t.done():
            t.cancel()


async def closed_loop(host, port, sched, t0, records):
    by_client = {}
    for req in sched["requests"]:
        by_client.setdefault(req["client"], []).append(req)

    async def client(reqs):
        for req in reqs:
            if time.monotonic() - t0 >= sched["seconds"]:
                return               # the window closed: send no more
            rec = new_record(req)
            rec["due_s"] = time.monotonic() - t0
            records.append(rec)
            await one_request(host, port, req, t0, rec)

    tasks = [asyncio.ensure_future(client(r)) for r in by_client.values()]
    await asyncio.wait(tasks, timeout=sched["seconds"] + sched["drain_s"])
    for t in tasks:
        if not t.done():
            t.cancel()


def main(argv):
    sched_path, out_path, host, port = argv[0], argv[1], argv[2], int(argv[3])
    with open(sched_path) as f:
        sched = json.load(f)
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 2
    t0 = time.monotonic()
    records = []
    loop = closed_loop if sched["mode"] == "closed" else open_loop
    asyncio.run(loop(host, port, sched, t0, records))
    with open(out_path, "w") as f:
        json.dump({"t0_monotonic": t0, "records": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
