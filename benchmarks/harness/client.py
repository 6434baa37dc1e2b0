"""The load generator: a child process that never imports jax.

    python3 benchmarks/harness/client.py <job.json>

It speaks HTTP/SSE to the gateway over the loopback socket from its own
interpreter, so it shares no lock with the tick threads it times. The
job file names the port, the seed, the mix's parameters, the rate or
client count, and ``t0``: the ``time.monotonic()`` reading (one clock
for every process of a Linux machine) at which the schedule starts. It
writes ``job["out"]``: one record per request it sent, times on that
same clock.

Open loop: a session's first turn is due at ``t0 + arrival`` whatever
the server is doing; a later turn is due a think time after its
predecessor's last token. Closed loop: a client's next request is due
the moment its last one completed. Nothing due at or after the end of
the measured window is sent. Each request is timed from when it was due.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness.traffic import plan  # noqa: E402


async def _sleep_until(t: float):
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        # asyncio's timer is good to about a millisecond; spin the rest
        await asyncio.sleep(d - 0.002 if d > 0.004 else 0)


async def heartbeat(out: dict, period: float = 0.02):
    """How late this process's own event loop ran: the longest overshoot
    of a short sleep. A stall here is the generator's, not the server's."""
    out["loop_lag_max_ms"] = 0.0
    while True:
        t = time.monotonic()
        await asyncio.sleep(period)
        lag = (time.monotonic() - t - period) * 1e3
        out["loop_lag_max_ms"] = max(out["loop_lag_max_ms"], lag)


async def send(port: int, body: dict, rec: dict):
    """POST one streaming request and fold the answer into ``rec``.
    ``token_times`` are the arrival times of the SSE token events."""
    payload = json.dumps(body).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        rec["sent"] = time.monotonic()
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      f"X-Request-Id: {body['request_id']}\r\n"
                      f"Content-Length: {len(payload)}\r\n\r\n").encode()
                     + payload)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        rec["status"] = status
        headers = {}
        while (line := await reader.readline()) not in (b"\r\n", b"\n", b""):
            k, _, v = line.decode("latin1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if not headers.get("content-type", "").startswith(
                "text/event-stream"):
            n = int(headers.get("content-length", "0") or 0)
            rec["error"] = (await reader.readexactly(n)).decode(
                "utf8", "replace")[:300]
            return
        while line := await reader.readline():
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            ev = json.loads(line[6:])
            if ev.get("done"):
                rec["end"] = now
                rec["final_tokens"] = ev.get("tokens")
                rec["final_lps"] = ev.get("logprobs")
                rec["finish_reason"] = ev.get("finish_reason")
                if ev.get("error"):
                    rec["error"] = str(ev["error"])[:300]
                return
            rec["token_times"].append(now)
            rec["tokens"].append(ev["token"])
            rec["lps"].append(ev["lp"])
        rec["error"] = "stream ended without a done event"
    except asyncio.CancelledError:
        rec["cancelled"] = True
        raise
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        if writer is not None:
            writer.close()
            try:
                await writer.wait_closed()
            except OSError:
                pass


async def run_session(job, sess, systems, sampling, records, due, w_end):
    """The turns of one session, in order. ``due`` is when the first is
    due; returns when the last one it sent has ended."""
    history = list(systems[sess["tenant"]]) if sess["tenant"] is not None \
        else []
    for k, turn in enumerate(sess["turns"]):
        if due >= w_end:
            return
        await _sleep_until(due)
        prompt = history + turn["message"]
        rid = f"r{job['seed']}s{sess['index']}t{k}"
        body = {"request_id": rid, "prompt": prompt,
                "max_new_tokens": turn["max_new_tokens"], "stream": True,
                "tenant": f"t{sess['tenant']}"
                if sess["tenant"] is not None else "default"}
        if not sess["greedy"]:
            body.update(sampling, seed=sess["seed"] + k)
        rec = {"id": rid, "session": sess["index"], "turn": k,
               "greedy": sess["greedy"], "due": due, "sent": None,
               "prompt": prompt, "max_new_tokens": turn["max_new_tokens"],
               "token_times": [], "tokens": [], "lps": [], "status": None}
        records.append(rec)
        await send(job["port"], body, rec)
        if rec.get("error") or rec.get("status") != 200:
            return              # a broken conversation has no next turn
        history = prompt + rec["tokens"]
        due = (rec.get("end") or time.monotonic()) + \
            (sess["turns"][k + 1]["think_s"]
             if k + 1 < len(sess["turns"]) else 0.0)


async def run_client(job, sessions, systems, sampling, records, t0, w_end):
    """One closed-loop caller: its requests one after another."""
    due = t0
    for sess in sessions:
        if due >= w_end:
            return
        await run_session(job, sess, systems, sampling, records, due, w_end)
        due = time.monotonic()


async def main_async(job: dict) -> dict:
    p = plan(job["seed"], job["mix"], job["vocab"], job["seconds"],
             rate=job.get("rate"), clients=job.get("clients"),
             max_context=job["max_context"])
    t0 = float(job["t0"])
    w0 = t0 + p["lead_in_s"]
    w1 = w0 + float(job["seconds"])
    give_up = w1 + p["drain_s"]
    records: list = []
    lag: dict = {}
    beat = asyncio.ensure_future(heartbeat(lag))
    args = (p["systems"], p["sampling"], records)
    if p["loop"] == "open":
        tasks = [asyncio.ensure_future(run_session(
            job, s, *args, t0 + s["arrival"], w1)) for s in p["sessions"]]
    else:
        n = job["clients"]
        tasks = [asyncio.ensure_future(run_client(
            job, [s for s in p["sessions"] if s["client"] == c], *args,
            t0, w1)) for c in range(n)]
    await _sleep_until(w1)
    # open loop: the requests due in the window get ``drain_s`` to end;
    # closed loop: the window's tokens are in, the rest is not waited for
    if tasks:
        await asyncio.wait(tasks, timeout=max(give_up - time.monotonic(), 0))
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    beat.cancel()
    return {"loop_lag_max_ms": lag["loop_lag_max_ms"], "t0": t0, "w0": w0, "w1": w1, "give_up": give_up,
            "ended": time.monotonic(), "records": records}


def main(argv) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    out = asyncio.run(main_async(job))
    tmp = job["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, job["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
