#!/usr/bin/env python3
"""The open-loop load generator: a child process that never imports JAX.

The server process (the one that holds the chip) starts this file with
``subprocess`` and speaks to it over stdin/stdout in pickled messages
that only these two processes write:

    {"op": "prepare", "pool", "pages", "num_clients"} -> {"op": "prepared"}
    {"op": "warmup" or "window", "host", "port", "schedule",
     "connections"}                                     -> records
    {"op": "exit"}                                      -> {"jax": bool}

A schedule is a list of requests ``(due_s, kind, client, lag, pool,
page)`` sorted by ``due_s``.  A dispatcher thread hands each request to a
free connection at its due time, whatever happened to the earlier ones
(open loop); each connection is one keep-alive HTTP/1.1 socket on a
thread of its own.  Every request is timed from when it was due, and
how late it was sent is kept, so a starved generator shows.  A client
never has two uploads in flight: a device sends its next delta after the
last one was acknowledged, so its uploads reach the server in the order
they were sent.  An upload's ``base_version`` is the newest version this
generator has seen minus the request's lag, never below 0.

The upload frame is the service's wire format (``MAGIC | version | u32
header length | header JSON | arrays``), built here from the pool's
pre-serialized arrays.
"""
from __future__ import annotations

import json
import pickle
import queue
import socket
import struct
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

MAGIC, WIRE_VERSION = b"RPFN", 1
_PREFIX = struct.Struct(">4sBI")
RESPONSE_WAIT_S = 60.0          # how long past the close a reply may come


# ---------------------------------------------------------------------------
# the wire format (encoding side)
# ---------------------------------------------------------------------------
def serialize_tree(tree: Any):
    """(skeleton, manifest, payload bytes) of a pytree of float32 arrays
    in dicts and lists, as the wire frame carries them."""
    manifest: List[Dict[str, Any]] = []
    chunks: List[bytes] = []

    def node(x):
        if isinstance(x, dict):
            return {"d": {k: node(v) for k, v in x.items()}}
        if isinstance(x, (list, tuple)):
            return {"l" if isinstance(x, list) else "t": [node(v) for v in x]}
        a = np.ascontiguousarray(np.asarray(x, np.float32))
        manifest.append({"dtype": "float32", "shape": list(a.shape)})
        chunks.append(a.tobytes())
        return {"a": len(manifest) - 1}
    skeleton = node(tree)
    return skeleton, manifest, b"".join(chunks)


def upload_frame(part, meta: Dict[str, Any]) -> List[bytes]:
    skeleton, manifest, payload = part
    header = json.dumps({"kind": "upload", "meta": meta, "tree": skeleton,
                         "arrays": manifest},
                        separators=(",", ":")).encode("utf-8")
    return [_PREFIX.pack(MAGIC, WIRE_VERSION, len(header)), header, payload]


def page_json(counts: np.ndarray) -> bytes:
    """``{"bow": [[...], ...]}`` of an integer count matrix."""
    rows = (",".join(map(str, r)) for r in counts.astype(np.int64).tolist())
    return ('{"bow":[' + ",".join(f"[{r}]" for r in rows) + "]}").encode()


# ---------------------------------------------------------------------------
# HTTP/1.1 over one keep-alive socket
# ---------------------------------------------------------------------------
class Conn:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.sock = None

    def _connect(self):
        s = socket.create_connection((self.host, self.port), timeout=120)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock = s
        self.buf = b""

    def request(self, method: str, path: str, parts: List[bytes],
                ctype: str):
        if self.sock is None:
            self._connect()
        n = sum(len(p) for p in parts)
        head = (f"{method} {path} HTTP/1.1\r\nHost: {self.host}\r\n"
                f"Content-Type: {ctype}\r\nContent-Length: {n}\r\n"
                "Connection: keep-alive\r\n\r\n").encode("latin-1")
        try:
            self.sock.sendall(head)
            for p in parts:
                self.sock.sendall(p)
            return self._response()
        except OSError:
            self.close()
            raise

    def _response(self):
        while b"\r\n\r\n" not in self.buf:
            self._fill()
        head, _, rest = self.buf.partition(b"\r\n\r\n")
        lines = head.decode("latin-1").split("\r\n")
        status = int(lines[0].split(" ")[1])
        length = 0
        for ln in lines[1:]:
            k, _, v = ln.partition(":")
            if k.strip().lower() == "content-length":
                length = int(v.strip())
        self.buf = rest
        while len(self.buf) < length:
            self._fill()
        body, self.buf = self.buf[:length], self.buf[length:]
        return status, body

    def _fill(self):
        chunk = self.sock.recv(1 << 20)
        if not chunk:
            raise ConnectionError("server closed the connection")
        self.buf += chunk

    def close(self):
        if self.sock is not None:
            self.sock.close()
            self.sock = None


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------
class Generator:
    def __init__(self, pool, pages, num_clients: int):
        self.pool = pool
        self.num_clients = num_clients
        self.pages = pages
        self.seen = 0                       # newest model version seen
        self.lock = threading.Lock()
        self.busy_clients: Dict[int, int] = {}

    def _note_version(self, v):
        with self.lock:
            self.seen = max(self.seen, int(v))

    def _one(self, conn: Conn, req, rec: Dict[str, Any]):
        due, kind, client, lag, pool, page = req
        try:
            if kind == "upload":
                with self.lock:
                    base = max(0, self.seen - int(lag))
                rec["base_version"] = base
                meta = {"client": int(client), "base_version": base,
                        "weight": float(self.pool[pool]["weight"])}
                status, body = conn.request(
                    "POST", "/v1/upload",
                    upload_frame(self.pool[pool]["part"], meta),
                    "application/x-repro-wire")
                receipt = json.loads(body)
                rec["receipt"] = receipt
                rec["ok"] = status == 200 and bool(receipt.get("accepted"))
                if "version" in receipt:
                    self._note_version(receipt["version"])
            else:
                status, body = conn.request("POST", "/v1/infer",
                                            [self.pages[page]],
                                            "application/json")
                rec["ok"] = status == 200
                if status == 200:
                    out = json.loads(body)
                    rec["version"] = int(out["version"])
                    rec["theta"] = np.asarray(out["theta"], np.float32)
                    self._note_version(out["version"])
            rec["status"] = status
        except (OSError, ValueError) as e:
            rec["ok"], rec["error"] = False, f"{type(e).__name__}: {e}"
        rec["recv"] = time.perf_counter()

    def run(self, host: str, port: int, schedule, connections: int,
            close_s: float) -> List[Dict[str, Any]]:
        """Send every request of ``schedule`` at its due time."""
        idle: "queue.Queue[Conn]" = queue.Queue()
        for _ in range(connections):
            idle.put(Conn(host, port))
        records: List[Dict[str, Any]] = [None] * len(schedule)
        done = threading.Semaphore(0)
        t0 = time.perf_counter()

        def worker(conn, req, rec, client):
            self._one(conn, req, rec)
            if client is not None:
                with self.lock:
                    self.busy_clients.pop(client, None)
            idle.put(conn)
            done.release()

        for i, req in enumerate(schedule):
            due = t0 + req[0]
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            client = None
            if req[1] == "upload":
                client = self._free_client(req[2])
                req = (req[0], req[1], client) + tuple(req[3:])
            conn = idle.get()
            rec = {"kind": req[1], "client": int(req[2]), "pool": req[4],
                   "page": req[5], "due": due - t0,
                   "sent": time.perf_counter() - t0}
            records[i] = rec
            threading.Thread(target=worker, args=(conn, req, rec, client),
                             daemon=True).start()
        deadline = time.perf_counter() + close_s
        for _ in schedule:
            if not done.acquire(timeout=max(0.0, deadline -
                                            time.perf_counter())):
                break
        while not idle.empty():
            idle.get().close()
        for r in records:
            if r is not None and "recv" in r:
                r["recv"] -= t0
        return records

    def _free_client(self, client: int) -> int:
        """``client``, or the next id with no upload in flight."""
        with self.lock:
            c = int(client)
            while c in self.busy_clients:
                c = (c + 1) % self.num_clients
            self.busy_clients[c] = 1
            return c


def main() -> int:
    inp, out = sys.stdin.buffer, sys.stdout.buffer
    gen = None
    while True:
        msg = pickle.load(inp)
        op = msg["op"]
        if op == "prepare":
            pages = [page_json(p) for p in msg["pages"]]
            gen = Generator(msg["pool"], pages, msg["num_clients"])
            reply = {"op": "prepared", "page_bytes": sum(map(len, pages))}
        elif op in ("warmup", "window"):
            records = gen.run(msg["host"], msg["port"], msg["schedule"],
                              msg["connections"], msg.get("close_s",
                                                          RESPONSE_WAIT_S))
            reply = {"op": op, "records": records}
        elif op == "exit":
            pickle.dump({"op": "exit", "jax": "jax" in sys.modules}, out)
            out.flush()
            return 0
        else:
            raise ValueError(f"unknown op {op!r}")
        pickle.dump(reply, out)
        out.flush()


if __name__ == "__main__":
    sys.exit(main())
