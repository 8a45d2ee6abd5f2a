#!/usr/bin/env python3
"""Load generator: a child process of its own, stdlib only, never JAX.

The parent holds the chip and the server; this process holds the clients, so
their threads share no GIL with the engine's pump.  It reads a schedule file
(harness/schedule.py), says READY, waits for ``GO <t_start> <t_stop>`` on
stdin (both ``time.monotonic()`` seconds: CLOCK_MONOTONIC is one clock for
every process of the machine), drives ``POST /v1/generate`` with
``"stream": true`` and writes one record per request:

    id, due, sent, status, first_byte, token_times[], tokens[], done, error

``due`` is absolute; in a closed loop it is the moment the client became
free.  Open loop: every request is sent at its due time whatever the server
does; none is sent after t_stop.  Closed loop: ``clients`` threads, each
sending its next request when the last one is done, until t_stop.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time


def new_record(req: dict, due: float) -> dict:
    return {"id": req["id"], "due": due, "sent": None, "status": None,
            "token_times": [], "tokens": [], "done": None, "error": None,
            "n_prompt": req["n_prompt"], "max_new": req["max_new"]}


def one_request(port: int, rec: dict, body: bytes, timeout: float) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json",
                      "X-Request-Id": rec["id"]})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(300).decode(errors="replace")
            return rec
        event = None
        while True:
            line = resp.readline()
            if not line:
                rec["error"] = rec["error"] or "stream ended without done"
                break
            line = line.rstrip(b"\r\n")
            if line.startswith(b"event:"):
                event = line[6:].strip()
            elif line.startswith(b"data:"):
                now = time.monotonic()
                if event == b"token":
                    rec["token_times"].append(now)
                    rec["tokens"].append(
                        json.loads(line[5:])["token"])
                elif event == b"done":
                    rec["done"] = now
                    break
                elif event == b"restart":
                    rec["token_times"].clear()
                    rec["tokens"].clear()
                elif event in (b"error", b"cancelled",
                               b"deadline_exceeded"):
                    rec["error"] = f"{event.decode()}: " + \
                        line[5:].decode(errors="replace")[:300]
                    break
    except Exception as e:      # recorded, judged by the parent
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--records", required=True)
    ap.add_argument("--loop", choices=("open", "closed"), required=True)
    ap.add_argument("--timeout", type=float, default=120.0)
    args = ap.parse_args()

    reqs = []
    with open(args.schedule) as f:
        for line in f:
            r = json.loads(line)
            tokens = r.pop("tokens")
            body = json.dumps({"tokens": tokens, "max_new": r["max_new"],
                               "stream": True}).encode()
            r["n_prompt"] = len(tokens)
            reqs.append((r, body))
    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if len(go) != 3 or go[0] != "GO":
        print(f"loadgen: expected GO, got {go}", file=sys.stderr)
        return 2
    t_start, t_stop = float(go[1]), float(go[2])

    records, lock, threads = [], threading.Lock(), []

    def run_one(req, body, due):
        # registered before it is sent: one that is never answered is
        # still in the records, unanswered
        rec = new_record(req, due)
        with lock:
            records.append(rec)
        return one_request(args.port, rec, body, args.timeout)

    if args.loop == "open":
        reqs.sort(key=lambda rb: rb[0]["due"])
        for req, body in reqs:
            due = t_start + req["due"]
            if due >= t_stop:
                break
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=run_one, args=(req, body, due),
                                 daemon=True)
            t.start()
            threads.append(t)
    else:
        by_client = {}
        for req, body in reqs:
            by_client.setdefault(req["client"], []).append((req, body))
        exhausted = []

        def client(rows):
            rows.sort(key=lambda rb: rb[0]["seq"])
            wait = t_start - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            for req, body in rows:
                due = time.monotonic()
                if due >= t_stop:
                    return
                run_one(req, body, due)
            exhausted.append(rows[0][0]["client"])

        for rows in by_client.values():
            t = threading.Thread(target=client, args=(rows,), daemon=True)
            t.start()
            threads.append(t)
    deadline = t_stop + args.timeout
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    unanswered = sum(t.is_alive() for t in threads)
    with lock:      # a thread still waiting keeps its record as it stands
        out = [json.dumps(rec) for rec in records]
    with open(args.records, "w") as f:
        f.write("\n".join(out) + ("\n" if out else ""))
    summary = {"records": len(out), "threads_still_waiting": unanswered}
    if args.loop == "closed":
        summary["clients_out_of_requests"] = len(exhausted)
    print("DONE " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
