"""The load generator: a process of its own with one thread per client.

Standard library only (never JAX: a second process that touches JAX
loses the chip), keep-alive HTTP/1.1 connections, so that generator and
server do not share one interpreter lock. It replays request sequences
that the harness wrote, in a closed loop (next request when the reply is
in, no sooner than ``max_rate_per_s`` allows where that is given) or an
open loop (on a schedule, timed from when each was due), and
keeps every reply of a run to compact after the run has ended.

Started as ``python loadgen.py <spec.json>``; then one command per line
on standard input, one JSON reply per line on standard output:

    run <seconds> <tag>       all clients, from where each sequence stands
    run <seconds> <tag> <name>  only the clients of that group
    burst <n> <tag>           n first-group requests released together
    quit

Records of a run go to ``<outdir>/<tag>.json``.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time

DRAIN_SECONDS = 60.0  # an answer may come this long past the close


class Client:
    """One connection and its place in one request sequence."""

    def __init__(self, port: int, spec: dict):
        self.port = port
        self.group = spec["group"]
        self.index = spec["client"]
        self.loop = spec["loop"]
        self.rate = spec.get("rate_per_s")
        self.max_rate = spec.get("max_rate_per_s")
        self.wrap = spec["wrap"]
        with open(spec["file"], encoding="utf-8") as f:
            self.requests = [json.loads(line) for line in f]
        self.bodies = [r["body"].encode("utf-8") for r in self.requests]
        self.position = 0
        self.conn = None
        self.exhausted = False

    def send(self, seq: int, timeout: float):
        """(status, body bytes, seconds at send, seconds at reply)."""
        req = self.requests[seq]
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout)
            t0 = time.monotonic()
            try:
                self.conn.request(req["method"], req["path"],
                                  body=self.bodies[seq],
                                  headers={"Content-Type": req["ctype"]})
                resp = self.conn.getresponse()
                data = resp.read()
                return resp.status, data, t0, time.monotonic()
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError):
                # a keep-alive connection the server closed: once more
                self.conn.close()
                self.conn = None
                if attempt:
                    return -1, b"", t0, time.monotonic()
            except OSError:  # time-out, refused
                self.conn.close()
                self.conn = None
                return -1, b"", t0, time.monotonic()

    def next_seq(self):
        if self.position >= len(self.requests):
            if not self.wrap:
                self.exhausted = True
                return None
            self.position = 0
        seq = self.position
        self.position += 1
        return seq

    def run(self, start: float, seconds: float, out: list) -> None:
        end = start + seconds
        n = 0
        while True:
            now = time.monotonic()
            due = now
            if self.loop == "open":
                due = start + n / self.rate
                if due >= end:
                    return
                if due > now:
                    time.sleep(due - now)
            else:
                if self.max_rate:  # a closed loop that paces itself
                    due = max(now, start + n / self.max_rate)
                if due >= end:
                    return
                if due > now:
                    time.sleep(due - now)
                    due = time.monotonic()
            seq = self.next_seq()
            if seq is None:
                return
            status, data, t_send, t_done = self.send(seq, DRAIN_SECONDS)
            out.append((self.group, self.index, seq, due, t_send, t_done,
                        status, data))
            n += 1


def compact(kind: str, status: int, data: bytes) -> dict:
    """What the harness reads of one reply; parsed after the run."""
    out = {"status": status}
    if status != 200:
        out["error"] = data[:300].decode("utf-8", "replace")
        return out
    r = json.loads(data)
    if kind == "bulk":
        items = r.get("items", [])
        out.update(took=r.get("took"), errors=r.get("errors"),
                   items=len(items),
                   created=sum(1 for i in items
                               if next(iter(i.values())).get("status") == 201))
        return out
    hits = r.get("hits", {})
    out.update(
        took=r.get("took"), timed_out=r.get("timed_out"),
        plane=r.get("_plane"),
        shards_failed=r.get("_shards", {}).get("failed"),
        total=hits.get("total"),
        ids=[h["_id"] for h in hits.get("hits", [])],
        scores=[h["_score"] for h in hits.get("hits", [])])
    if "aggregations" in r:
        out["aggs"] = {
            name: [[b["key"], b["doc_count"]] for b in agg.get("buckets", [])]
            for name, agg in r["aggregations"].items()}
    if "profile" in r:
        prof = r["profile"]
        out["phases"] = {p["phase"]: p["time_in_nanos"]
                         for p in prof.get("phases", [])}
        out["annotations"] = prof.get("annotations", {})
    return out


def write_records(path: str, raw: list, clients: list, t0: float,
                  seconds: float) -> dict:
    kinds = {(c.group, c.index): c.requests for c in clients}
    records = []
    for group, index, seq, due, t_send, t_done, status, data in raw:
        req = kinds[(group, index)][seq]
        rec = compact(req["kind"], status, data)
        rec.update(group=group, client=index, seq=seq, id=req["id"],
                   kind=req["kind"], due=due, sent=t_send, done=t_done)
        records.append(rec)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"start": t0, "seconds": seconds, "records": records}, f)
    return {"records": len(records),
            "exhausted": [f"{c.group}[{c.index}]" for c in clients
                          if c.exhausted]}


def main(argv) -> int:
    with open(argv[1], encoding="utf-8") as f:
        spec = json.load(f)
    clients = [Client(spec["port"], c) for c in spec["clients"]]
    print(json.dumps({"ready": len(clients)}), flush=True)
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "quit":
            break
        raw: list = []
        if words[0] == "run":
            seconds, tag = float(words[1]), words[2]
            chosen = [c for c in clients
                      if len(words) < 4 or c.group == words[3]]
            outs = [[] for _ in chosen]
            t0 = time.monotonic() + 0.05
            threads = [threading.Thread(target=c.run, args=(t0, seconds, o))
                       for c, o in zip(chosen, outs)]
        elif words[0] == "burst":
            n, tag, seconds = int(words[1]), words[2], 0.0
            chosen = [c for c in clients
                      if c.group == clients[0].group][:n]
            outs = [[] for _ in chosen]
            t0 = time.monotonic() + 0.05
            gate = threading.Barrier(len(chosen))

            def one(c, o):
                gate.wait()
                seq = c.next_seq()
                status, data, t_send, t_done = c.send(seq, DRAIN_SECONDS)
                o.append((c.group, c.index, seq, t_send, t_send, t_done,
                          status, data))

            threads = [threading.Thread(target=one, args=(c, o))
                       for c, o in zip(chosen, outs)]
        else:
            print(json.dumps({"error": f"unknown command {words[0]}"}),
                  flush=True)
            continue
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        closed = time.monotonic()
        for o in outs:
            raw.extend(o)
        reply = write_records(f"{spec['outdir']}/{tag}.json", raw, chosen,
                              t0, seconds)
        reply.update(tag=tag, start=t0, closed=closed)
        print(json.dumps(reply), flush=True)
    for c in clients:
        if c.conn is not None:
            c.conn.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
