"""The load generator: a child process that never imports JAX.

The parent holds the chip and runs the engine; this process shares no
interpreter lock with it. It gets the generated schedule and a port,
and nothing else. One thread, one asyncio loop: requests are plain
HTTP/1.0 POSTs on sockets of their own, and every SSE token event is
stamped with the monotonic clock the moment its line is read. It
computes no metric; it writes the rows and the parent reduces them.

Phases, in order: ``background`` streams start and each must produce a
token; ``shapes`` run one after another (beside a holder stream where
there is no background, so that chunked admissions meet a decoding
batch as they will in the window); the window's start t0 is then fixed
as now + warm_s and printed (``T0 <monotonic seconds>``) for the parent;
``warm`` and ``main`` follow on that clock. After t0 + window_s nothing
new is sent; requests of the window get ``grace_s`` to finish.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Any, Dict, List, Optional

now = time.monotonic


def new_row(req: Dict[str, Any], phase: str, prompt_tokens: int
            ) -> Dict[str, Any]:
    return dict(id=req["id"], phase=phase, due=req.get("due"), sent=None,
               status=None, error=None, done=False, tokens=[], stamps=[],
               t_done=None, cancelled=False, max_tokens=req["max_tokens"],
               prompt_tokens=prompt_tokens, warm=bool(req.get("warm")))


async def do_request(port: int, prompt: List[int], row: Dict[str, Any],
                     first_token: Optional[asyncio.Event] = None) -> None:
    """One streamed completion; fills ``row`` with absolute stamps."""
    body = json.dumps({"prompt": prompt, "max_tokens": row["max_tokens"],
                       "stream": True}).encode()
    row["sent"] = now()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"POST /v1/completions HTTP/1.0\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        status = await reader.readline()
        row["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass                                    # headers
        if row["status"] != 200:
            row["error"] = (await reader.read(400)).decode("replace")
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            t = now()
            ev = json.loads(line[6:])
            if "token" in ev:
                row["stamps"].append(t)
                row["tokens"].append(ev["token"])
                if first_token is not None:
                    first_token.set()
            elif ev.get("done"):
                row["done"] = True
                row["t_done"] = t
            elif "error" in ev:
                row["error"] = str(ev["error"])
    except asyncio.CancelledError:
        row["cancelled"] = True         # the generator's own doing
        raise
    except (OSError, ValueError, IndexError) as e:
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
        if first_token is not None:
            first_token.set()


async def run(schedule: Dict[str, Any], port: int, window_s: float,
              warm_s: float, grace_s: float) -> Dict[str, Any]:
    pool = schedule["pool"]
    rows: List[Dict[str, Any]] = []
    tasks: List[asyncio.Task] = []

    def prompt_of(req):
        out: List[int] = []
        for p in req["parts"]:
            out += pool[p]
        return out

    def launch(req, phase, first_token=None) -> asyncio.Task:
        prompt = prompt_of(req)
        row = new_row(req, phase, len(prompt))
        rows.append(row)
        t = asyncio.ensure_future(do_request(port, prompt, row, first_token))
        tasks.append(t)
        return t

    # background streams (or a holder for the shapes phase)
    holders = []
    streams = [(r, "background") for r in schedule["background"]]
    if not streams and schedule["shapes"]:
        streams = [({"id": "holder", "max_tokens": 2048,
                     "parts": schedule["shapes"][0]["parts"][:1]}, "holder")]
    for req, phase in streams:
        ev = asyncio.Event()
        holders.append((launch(req, phase, ev), phase))
        await ev.wait()
    for req in schedule["shapes"]:
        await launch(req, "shapes")
    for t, phase in holders:
        if phase == "holder":
            t.cancel()
    t0 = now() + warm_s
    print(f"T0 {t0!r}", flush=True)
    t_end = t0 + window_s
    window_tasks: List[asyncio.Task] = []
    ran_out = False         # a closed loop that emptied its main queue

    if schedule["loop"] == "open":
        reqs = sorted(schedule["warm"] + schedule["main"],
                      key=lambda r: r["due"])
        main_ids = {r["id"] for r in schedule["main"]}
        for req in reqs:
            delay = t0 + req["due"] - now()
            if delay > 0:
                await asyncio.sleep(delay)
            req = dict(req, due=t0 + req["due"])
            phase = "main" if req["id"] in main_ids else "warm"
            t = launch(req, phase)
            if phase == "main":
                window_tasks.append(t)
    else:
        queues = {"warm": list(reversed(schedule["warm"])),
                  "main": list(reversed(schedule["main"]))}

        async def client():
            nonlocal ran_out
            while now() < t_end:
                phase = "main" if now() >= t0 else "warm"
                if not queues[phase]:
                    if phase == "main":
                        ran_out = True
                        return
                    await asyncio.sleep(max(0.0, t0 - now()))
                    continue
                t = launch(queues[phase].pop(), phase)
                if phase == "main":
                    window_tasks.append(t)
                await asyncio.wait([t])

        await asyncio.gather(*(client() for _ in range(schedule["clients"])))
    delay = t_end - now()
    if delay > 0:
        await asyncio.sleep(delay)
    if window_tasks:
        await asyncio.wait(window_tasks, timeout=grace_s)
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)

    def rel(x):
        return None if x is None else x - t0

    for r in rows:
        r["due"], r["sent"], r["t_done"] = (rel(r["due"]), rel(r["sent"]),
                                            rel(r["t_done"]))
        r["stamps"] = [s - t0 for s in r["stamps"]]
    return {"t0": t0, "rows": rows, "ran_out_of_schedule": ran_out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--window-s", type=float, required=True)
    ap.add_argument("--warm-s", type=float, required=True)
    ap.add_argument("--grace-s", type=float, required=True)
    a = ap.parse_args(argv)
    with open(a.schedule) as f:
        schedule = json.load(f)
    result = asyncio.run(run(schedule, a.port, a.window_s, a.warm_s,
                             a.grace_s))
    with open(a.out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
