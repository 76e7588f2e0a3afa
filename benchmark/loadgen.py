"""The load generator: a process of its own that sends a schedule of streamed
``POST /v1/completions`` requests to a port on this machine and records,
per request, when each SSE event arrived.

Standard library only, and it never imports JAX: the chip belongs to the
server's process. One thread, non-blocking sockets under ``selectors``: it
sends each request when it is due on the schedule, whether or not earlier
ones have finished (an open loop), and stamps every event with the
monotonic clock as it is read. It stops when every counted request (due
before ``--count-until``) has ended and that time has passed, or at
``--hard-stop``, and then writes every record to ``--out`` (the format is
in ``latency.py``).

    python benchmark/loadgen.py --schedule s.json --port 8100 --t0 <clock>
        --count-until 46 --hard-stop 61 --out records.json

``--t0`` is the value of ``time.monotonic()`` (one clock for every process
on the machine) at which the schedule starts; ``due_s``, ``--count-until``
and ``--hard-stop`` are seconds after it.
"""
from __future__ import annotations

import argparse
import errno
import json
import selectors
import socket
import time


class _Conn:
    """One request in flight."""

    def __init__(self, req, t0):
        self.req = req
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "stream": True}).encode()
        self.out = (b"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                    b"Content-Type: application/json\r\n"
                    b"X-Request-Id: " + req["id"].encode() + b"\r\n"
                    b"Content-Length: " + str(len(body)).encode() +
                    b"\r\nConnection: close\r\n\r\n" + body)
        self.buf = b""
        self.in_body = False
        self.rec = {"id": req["id"], "due": t0 + req["due_s"], "send": None,
                    "status": None, "max_tokens": req["max_tokens"],
                    "events": [], "done": False, "end": None, "error": None}
        self.sock = self.fd = None

    def feed(self, data, now):
        """Parse what has arrived: the status line once, then every whole
        ``data:`` line, stamped ``now``."""
        self.buf += data
        if not self.in_body:
            head, sep, rest = self.buf.partition(b"\r\n\r\n")
            if not sep:
                return
            self.rec["status"] = int(head.split(None, 2)[1])
            self.in_body, self.buf = True, rest
        while True:
            line, sep, rest = self.buf.partition(b"\n")
            if not sep:
                return
            self.buf = rest
            if not line.startswith(b"data: ") or self.rec["status"] != 200:
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                self.rec["done"] = True
                continue
            n = len(json.loads(payload)["choices"][0]["tokens"])
            if n:
                self.rec["events"].append([now, n])


def run(schedule, port, t0, count_until, hard_stop):
    """Drive the schedule; returns the records of every request sent."""
    sel = selectors.DefaultSelector()
    pending = sorted(schedule, key=lambda r: r["due_s"])
    nxt, live, records = 0, {}, []
    counted_open = 0            # counted requests sent and not yet ended

    def drop(c):
        try:
            sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        del live[c.fd]

    def close(c, now, error=None):
        nonlocal counted_open
        if error is not None and c.rec["error"] is None:
            c.rec["error"] = error
        c.rec["end"] = now
        if c.req["due_s"] < count_until:
            counted_open -= 1
        drop(c)

    while True:
        now = time.monotonic()
        rel = now - t0
        if rel >= hard_stop:
            break
        counted_left = (nxt < len(pending)
                        and pending[nxt]["due_s"] < count_until)
        if rel >= count_until and not counted_open and not counted_left:
            break
        while nxt < len(pending) and pending[nxt]["due_s"] <= rel:
            c = _Conn(pending[nxt], t0)
            nxt += 1
            c.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            c.sock.setblocking(False)
            c.fd = c.sock.fileno()
            c.rec["send"] = time.monotonic()
            records.append(c.rec)
            live[c.fd] = c
            if c.req["due_s"] < count_until:
                counted_open += 1
            rc = c.sock.connect_ex(("127.0.0.1", port))
            if rc not in (0, errno.EINPROGRESS):
                close(c, time.monotonic(), f"connect: {errno.errorcode[rc]}")
                continue
            sel.register(c.sock, selectors.EVENT_WRITE, c)
        wait = min(hard_stop - rel, 0.05)
        if nxt < len(pending):
            wait = min(wait, pending[nxt]["due_s"] - rel)
        for key, mask in sel.select(max(wait, 0.0)):
            c = key.data
            now = time.monotonic()
            try:
                if mask & selectors.EVENT_WRITE:
                    err = c.sock.getsockopt(socket.SOL_SOCKET,
                                            socket.SO_ERROR)
                    if err:
                        raise OSError(err, errno.errorcode.get(err, "?"))
                    sent = c.sock.send(c.out)
                    c.out = c.out[sent:]
                    if not c.out:
                        sel.modify(c.sock, selectors.EVENT_READ, c)
                else:
                    data = c.sock.recv(1 << 16)
                    if data:
                        c.feed(data, now)
                    else:
                        close(c, now)
            except BlockingIOError:
                continue
            except (OSError, ValueError, KeyError, IndexError) as e:
                close(c, now, f"{type(e).__name__}: {e}")
    for c in list(live.values()):       # still in flight: no end, not done
        drop(c)
    sel.close()
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--count-until", type=float, required=True)
    ap.add_argument("--hard-stop", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.schedule) as f:
        schedule = json.load(f)
    records = run(schedule, a.port, a.t0, a.count_until, a.hard_stop)
    with open(a.out, "w") as f:
        json.dump(records, f)


if __name__ == "__main__":
    main()
