#!/usr/bin/env python3
"""The closed-loop load generator: a child process that never imports JAX.

``clients`` threads, each with one keep-alive connection over plain sockets,
each sending its next request the moment its last one has completed.  The
requests come from :mod:`benchmark.traffic` (the same multiset of lengths for
every seed; the seed orders them and draws the ids); every request is
streamed, and every token is stamped with ``time.monotonic()`` as its chunk
is read, which on Linux is one clock for this process and the parent.

The ramp: the window opens when every client has completed one request, so
completions are staggered from its first second.  Then the window lasts
``seconds``; at its end no new request is sent, the ones in flight are waited
for (a minute at most), and everything is reported.

Lines on standard output, each one JSON: ``{"event": "ramp_start"}``,
``{"event": "window_start", "t": ...}``, ``{"event": "window_end", "t":
...}``, and last ``{"event": "result", ...}`` with one record per request
sent (ramp included): index, client, prompt and output lengths, send time,
each token's arrival time, the tokens, and an error if there was one.

Arguments: one JSON object on standard input.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import traffic as traffic_mod  # noqa: E402


class Client(threading.Thread):
    def __init__(self, k: int, shared: "Shared"):
        super().__init__(name=f"client-{k}", daemon=True)
        self.k, self.s = k, shared
        self.sock: Optional[socket.socket] = None
        self.rfile = None
        self.completed = 0
        self.think: List[float] = []

    def _connect(self) -> None:
        self.sock = socket.create_connection((self.s.host, self.s.port),
                                             timeout=self.s.timeout_s)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rfile = self.sock.makefile("rb", buffering=65536)

    def _close(self) -> None:
        for c in (self.rfile, self.sock):
            try:
                if c is not None:
                    c.close()
            except OSError:
                pass
        self.sock = self.rfile = None

    def _one(self, req: Dict[str, Any], rec: Dict[str, Any]) -> None:
        body = json.dumps({"ids": req["ids"],
                           "max_new_tokens": req["max_new_tokens"],
                           "stream": True}).encode()
        head = (f"POST {self.s.path} HTTP/1.1\r\nHost: {self.s.host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n"
                "Connection: keep-alive\r\n\r\n").encode()
        if self.sock is None:
            self._connect()
        rec["t_send"] = time.monotonic()
        self.sock.sendall(head + body)
        status = self.rfile.readline()
        if not status:
            raise ConnectionError("connection closed before a reply")
        code = int(status.split()[1])
        chunked, length = False, 0
        while True:
            line = self.rfile.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, val = line.decode("latin1").partition(":")
            if key.lower() == "transfer-encoding" and "chunked" in val.lower():
                chunked = True
            elif key.lower() == "content-length":
                length = int(val)
        if code != 200 or not chunked:
            text = self.rfile.read(length) if length else b""
            raise RuntimeError(f"HTTP {code}: {text[:200]!r}")
        tokens, times, done = rec["tokens"], rec["times"], None
        while True:
            size = int(self.rfile.readline().strip() or b"0", 16)
            if size == 0:
                self.rfile.readline()
                break
            data = self.rfile.read(size)
            self.rfile.readline()
            now = time.monotonic()
            for ln in data.splitlines():
                if not ln.strip():
                    continue
                msg = json.loads(ln)
                if "token" in msg:
                    tokens.append(int(msg["token"]))
                    times.append(now)
                elif msg.get("done"):
                    done = msg
                elif "error" in msg:
                    raise RuntimeError(f"stream error: {msg}")
        if done is None:
            raise RuntimeError("stream ended without a done line")
        if [int(t) for t in done.get("ids", [])] != tokens:
            raise RuntimeError("the done line's ids differ from the tokens "
                               "streamed")

    def run(self) -> None:
        s = self.s
        last_done = None
        while True:
            with s.lock:
                if s.stop:
                    break
                index = s.next_index
                s.next_index += 1
            req = traffic_mod.request(s.traffic, s.seed, index, s.vocab, s.order)
            rec = {"index": index, "client": self.k, "prompt_len": len(req["ids"]),
                   "max_new_tokens": req["max_new_tokens"], "t_send": None,
                   "tokens": [], "times": [], "error": None}
            if last_done is not None:
                self.think.append(time.monotonic() - last_done)
            try:
                self._one(req, rec)
            except Exception as e:                     # noqa: BLE001
                # the boundary that must keep the loop running: the failure
                # is the request's record, and the connection starts anew
                rec["error"] = f"{type(e).__name__}: {e}"
                self._close()
            last_done = time.monotonic()
            with s.lock:
                s.records.append(rec)
                self.completed += 1
        self._close()


class Shared:
    def __init__(self, a: Dict[str, Any]):
        self.host, self.port, self.path = a["host"], int(a["port"]), a["path"]
        self.traffic = traffic_mod.load(a["traffic"])
        self.seed, self.vocab = int(a["seed"]), int(a["vocab"])
        self.order = traffic_mod.request_order(self.traffic, self.seed)
        self.timeout_s = float(a.get("timeout_s", 120))
        self.lock = threading.Lock()
        self.stop = False
        self.next_index = 0
        self.records: List[Dict[str, Any]] = []


def emit(obj: Dict[str, Any]) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    args = json.loads(sys.stdin.readline())
    shared = Shared(args)
    clients = [Client(k, shared) for k in range(int(shared.traffic["clients"]))]
    emit({"event": "ramp_start", "t": time.monotonic(), "clients": len(clients)})
    for c in clients:
        c.start()
    ramp_deadline = time.monotonic() + float(args.get("ramp_timeout_s", 240))
    while min(c.completed for c in clients) < 1:
        if time.monotonic() > ramp_deadline:
            emit({"event": "error", "what": "the ramp did not finish"})
            return 1
        time.sleep(0.005)
    t0 = time.monotonic()
    emit({"event": "window_start", "t": t0})
    time.sleep(max(0.0, t0 + float(args["seconds"]) - time.monotonic()))
    with shared.lock:
        shared.stop = True
    t1 = time.monotonic()
    emit({"event": "window_end", "t": t1})
    deadline = t1 + float(args.get("drain_timeout_s", 60))
    for c in clients:
        c.join(max(0.0, deadline - time.monotonic()))
    alive = sum(c.is_alive() for c in clients)
    with shared.lock:
        records = list(shared.records)
    think = sorted(t for c in clients for t in c.think)
    emit({"event": "result", "t0": t0, "t1": t1, "records": records,
          "never_answered": alive,
          "client_think_p90_ms": (1e3 * think[int(0.9 * (len(think) - 1))]
                                  if think else 0.0)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
