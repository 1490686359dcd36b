#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax.

    python3 benchmark/harness/loadgen.py <plan.json> <results.json>

It sends the plan's requests to ``POST <url>`` over HTTP, a thread per
client (closed loop: a client sends its next request when the last is
answered) or a pool of sender threads taking arrivals in due order
(open loop: a request is sent when it falls due, whatever came back),
for ``send_for_s`` seconds from ``start_at``; then it waits for every
answer outstanding (``grace_s``, a minute) and writes one row per
request sent: when it was due, sent and answered on this process's
clock (``time.time()``), the status, and the answered tokens.
"""

import http.client
import json
import sys
import threading
import time
import urllib.parse

OPEN_LOOP_SENDERS = 192


def post(conn, path, request, timeout):
    body = json.dumps({"tokens": request["tokens"],
                       "n_tokens": request["n_tokens"]})
    conn.request("POST", path, body=body,
                 headers={"Content-Type": "application/json"})
    response = conn.getresponse()
    raw = response.read()
    tokens = None
    if response.status == 200:
        tokens = json.loads(raw.decode()).get("tokens")
    return response.status, tokens


def sender(plan, url, take, rows, lock):
    """One thread: take requests until ``take`` gives None."""
    conn = http.client.HTTPConnection(url.hostname, url.port,
                                      timeout=plan["timeout_s"])
    try:
        while True:
            item = take()
            if item is None:
                return
            index, request, due = item
            wait = due - time.time()
            if wait > 0:
                time.sleep(wait)
            sent = time.time()
            try:
                status, tokens = post(conn, url.path, request,
                                      plan["timeout_s"])
            except (OSError, http.client.HTTPException) as exc:
                status, tokens = "error: %r" % (exc,), None
                conn.close()
            row = {"i": index, "due": due, "sent": sent,
                   "done": time.time(), "status": status,
                   "n_prompt": len(request["tokens"]),
                   "n_tokens": request["n_tokens"], "tokens": tokens}
            with lock:
                rows.append(row)
    finally:
        conn.close()


def main(argv):
    with open(argv[1]) as fin:
        plan = json.load(fin)
    url = urllib.parse.urlparse(plan["url"])
    start = plan["start_at"]
    stop = start + plan["send_for_s"]
    rows, lock = [], threading.Lock()
    requests = plan["requests"]
    takers = []
    if plan["loop"] == "closed":
        for client in range(plan["clients"]):
            mine = [(i, r) for i, r in enumerate(requests)
                    if r["client"] == client]
            state = {"n": 0}

            def take(mine=mine, state=state):
                if time.time() >= stop or not mine:
                    return None
                index, request = mine[state["n"] % len(mine)]
                state["n"] += 1
                return index, request, max(time.time(), start)

            takers.append(take)
    else:
        state = {"n": 0}

        def take():
            with lock:
                n = state["n"]
                if n >= len(requests):
                    return None
                state["n"] = n + 1
            return n, requests[n], start + requests[n]["due_s"]

        takers = [take] * OPEN_LOOP_SENDERS
    threads = [threading.Thread(target=sender,
                                args=(plan, url, take, rows, lock),
                                daemon=True) for take in takers]
    for thread in threads:
        thread.start()
    deadline = stop + plan["grace_s"]
    for thread in threads:
        thread.join(timeout=max(0.0, deadline - time.time()))
    unanswered = sum(thread.is_alive() for thread in threads)
    with lock:
        done = sorted(rows, key=lambda row: row["due"])
    with open(argv[2], "w") as fout:
        json.dump({"start_at": start, "stop_at": stop,
                   "unanswered": unanswered, "rows": done}, fout)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
