"""Open-loop load generator: a child process on the standard library.

It never imports JAX or the program, so it neither takes the chip nor
shares the engine's interpreter lock. One thread (asyncio) sends each
request of a schedule at the instant it is due, whatever the server is
doing, streams the reply, and stamps every line with the system-wide
monotonic clock, which the parent reads too. The samples go to a file.

    python3 chipbench/loadgen.py <plan.json>

``plan.json`` (written by the parent)::

    {"port": 1234, "seed": 7, "vocab": 32000, "out": "<samples.json>",
     "requests": [...], "window": [start_s, end_s],   # traffic.make_schedule
     "drain_s": 45, "cut": false}

After building every request body the child prints one line,
``{"t_begin": <monotonic seconds>}``: the schedule's zero. It stops when
every request has ended, or ``drain_s`` after the window's end, or --
with ``cut`` -- at the window's end; requests still open then are
recorded as ``"open"``.
"""
import asyncio
import json
import os
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench.traffic import prompt_tokens  # noqa: E402

#: seconds between the last body built and the schedule's zero
START_DELAY_S = 0.3


async def _one(req, body, port, t_begin, sample):
    due = t_begin + req["due_s"]
    await asyncio.sleep(max(0.0, due - time.monotonic()))
    sample["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(b"POST /v1/generate HTTP/1.0\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        await writer.drain()
        status_line = await reader.readline()
        sample["http"] = int(status_line.split()[1])
        while (await reader.readline()).strip():
            pass                                  # headers
        if sample["http"] != 200:
            sample["end"] = "refused"
            sample["error"] = (await reader.read(2000)).decode(
                "utf-8", "replace")
            return
        while True:
            raw = await reader.readline()
            if not raw:
                break
            now = time.monotonic()
            line = json.loads(raw)
            toks = line.get("tokens")
            if toks:
                sample["tokens"].extend(toks)
                sample["events"].append([now, len(toks)])
            if "status" in line:
                sample["end"] = ("done" if line == {"status": "done"}
                                 else json.dumps(line))
                sample["t_end"] = now
    except (OSError, ValueError, asyncio.IncompleteReadError) as exc:
        sample["end"] = "error"
        sample["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        writer.close()


async def _run(plan, bodies, t_begin):
    samples = [{"i": r["i"], "due": t_begin + r["due_s"], "timed": r["timed"],
                "prompt_len": r["prompt_len"], "asked": r["max_new_tokens"],
                "sent": None, "http": None, "end": "open", "t_end": None,
                "tokens": [], "events": []} for r in plan["requests"]]
    tasks = [asyncio.ensure_future(_one(r, b, plan["port"], t_begin, s))
             for r, b, s in zip(plan["requests"], bodies, samples)]
    stop_at = t_begin + plan["window"][1] + (
        0.0 if plan.get("cut") else float(plan.get("drain_s", 0.0)))
    if tasks:
        _, pending = await asyncio.wait(
            tasks, timeout=max(0.0, stop_at - time.monotonic()))
        for task in pending:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return samples


def main(argv):
    with open(argv[0]) as fh:
        plan = json.load(fh)
    bodies = [json.dumps({
        "prompt": prompt_tokens(plan["seed"], r["i"], r["prompt_len"],
                                plan["vocab"]),
        "max_new_tokens": r["max_new_tokens"], "stream": True,
        **plan.get("request_fields", {})}).encode()
        for r in plan["requests"]]
    t_begin = time.monotonic() + START_DELAY_S
    print(json.dumps({"t_begin": t_begin}), flush=True)
    samples = asyncio.run(_run(plan, bodies, t_begin))
    with open(plan["out"], "w") as fh:
        json.dump({"t_begin": t_begin, "samples": samples}, fh)


if __name__ == "__main__":
    main(sys.argv[1:])
