"""Open-loop load generator: a child process that imports neither ``jax``
nor ``repro``.

Reads a JSON job on standard input (the server's address, the absolute
``time.monotonic()`` at which the schedule starts, the schedule of
``chipbench.gen``) and writes one JSON result on standard output.

Two lanes.  The ingest lane is ordered, as the engine requires: whenever it
is free it POSTs every event that is due and not yet sent, as a membership
agent would.  Route queries are handed, each at its due time, to a pool of
senders with one keep-alive connection each, so one slow reply never
delays a later send.  Every latency runs from the request's due time to
its reply.  A route picks its pair when it is sent, from the nodes that
are live by the events acknowledged so far and that no event of the
schedule removes: a query held in a queue must not name a node whose
leave lands before the query is served.
"""
from __future__ import annotations

import bisect
import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import gen  # noqa: E402
from chipbench.httpclient import Connection  # noqa: E402


class LiveSet:
    """Sorted ids of the nodes routes may name, behind one lock."""

    def __init__(self, n0: int, doomed: set):
        self.lock = threading.Lock()
        self.doomed = doomed            # removed by some event of the run
        self.nodes = [u for u in range(n0) if u not in doomed]

    def add(self, u: int) -> None:
        if u in self.doomed:
            return
        with self.lock:
            i = bisect.bisect_left(self.nodes, u)
            if i == len(self.nodes) or self.nodes[i] != u:
                self.nodes.insert(i, u)


def ingest_lane(job, live: LiveSet, out: list, deadline: float) -> None:
    """POST the events in order; ``out[i]`` = [status, ack latency s, sent
    late by s]."""
    t0, events = job["t0"], job["schedule"]["events"]
    conn = Connection(job["host"], job["port"])
    i = 0
    while i < len(events):
        wait = t0 + events[i][0] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        now = time.monotonic()
        if now > deadline:
            break
        j = i
        while j < len(events) and t0 + events[j][0] <= now:
            j += 1
        batch = events[i:j]
        status, _ = conn.request("POST", "/v1/events", {"events": [
            {"time": due * 1e3, "kind": kind, "node": node}
            for due, kind, node in batch]})
        ack = time.monotonic()
        for k, (due, kind, node) in enumerate(batch, start=i):
            out[k] = [status, ack - (t0 + due), now - (t0 + events[i][0])]
            if kind == "join" and 200 <= status < 300:
                live.add(node)
        i = j
    conn.close()


def route_lane(job, live: LiveSet, out: list, deadline: float) -> None:
    """Dispatch each query at its due time to a pool of senders;
    ``out[k]`` = [status, latency s, dispatched late by s, sent late by s,
    src, dst, distance, bound, path]."""
    sched, t0 = job["schedule"], job["t0"]
    todo: "queue.Queue" = queue.Queue()

    def sender():
        conn = Connection(job["host"], job["port"])
        while True:
            item = todo.get()
            if item is None:
                break
            k, (u1, u2), due, late = item
            sent = time.monotonic()
            if sent > deadline:
                continue
            with live.lock:
                src, dst = gen.pick_pair(live.nodes, u1, u2)
            status, body = conn.request("GET", f"/v1/route?src={src}&dst={dst}")
            body = body or {}
            out[k] = [status, time.monotonic() - due, late, sent - due, src,
                      dst, body.get("distance"), body.get("bound"),
                      body.get("path")]
        conn.close()

    pool = [threading.Thread(target=sender, daemon=True)
            for _ in range(int(job["route_senders"]))]
    for th in pool:
        th.start()
    for k, (due, u1, u2) in enumerate(sched["routes"]):
        due = t0 + due
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put((k, (u1, u2), due, time.monotonic() - due))
    for _ in pool:
        todo.put(None)
    for th in pool:
        th.join(max(0.0, deadline - time.monotonic()) + 5.0)


def main() -> int:
    job = json.load(sys.stdin)
    sched = job["schedule"]
    deadline = (job["t0"] + sched["warmup_s"] + sched["window_s"]
                + float(job["drain_s"]))
    live = LiveSet(sched["n0"], {node for _, kind, node in sched["events"]
                                 if kind != "join"})
    events = [None] * len(sched["events"])
    routes = [None] * len(sched["routes"])
    lanes = [threading.Thread(target=ingest_lane,
                              args=(job, live, events, deadline), daemon=True),
             threading.Thread(target=route_lane,
                              args=(job, live, routes, deadline), daemon=True)]
    for th in lanes:
        th.start()
    for th in lanes:
        th.join(max(0.0, deadline - time.monotonic()) + 10.0)
    json.dump({"events": events, "routes": routes}, sys.stdout)
    sys.stdout.flush()
    # a sender still blocked on a reply past the deadline is a daemon
    # thread: leave without waiting for it
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
