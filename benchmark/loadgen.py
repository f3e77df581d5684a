"""One load-generator process: threaded raw-socket HTTP/1.1 over keep-alive
connections, one ``sendall`` and a minimal parse per request (the socket code
is ``bench.py``'s ``client_src``; the loops around it are new). Imports
nothing but the standard library, and above all never JAX: the process that
holds the chip is the server's.

``python loadgen.py <spec.json>`` reads its schedule from the spec and writes
``spec["result"]`` when done. Clocks are ``time.monotonic()``, which on Linux
is one clock for every process of the host, so the parent's ``t0`` and this
process's stamps can be set side by side.

- ``mode`` "open": request i is due at ``t0 + due[i]``; a thread claims the
  next request, sleeps until it is due, sends it, and records due, sent and
  done. The schedule never waits for a reply: with every connection busy a
  request goes out late, and the lateness is in the result.
- ``mode`` "closed": each connection sends its next request when the reply is
  in, from ``t0 + start_s`` until ``t0 + stop_s``.
"""

import json
import socket
import sys
import threading
import time

REQUEST = (
    "POST %s HTTP/1.1\r\nHost: 127.0.0.1\r\n"
    "Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
)


def connect(port, timeout_s):
    sock = socket.create_connection(("127.0.0.1", port), timeout=timeout_s)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


def exchange(sock, wire):
    """Send one request, read one reply: ``(status, body)``."""
    sock.sendall(wire)  # headers and body in one syscall, one packet
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise OSError("connection closed")
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    status = int(head.split(None, 2)[1])
    length = 0
    for line in head.split(b"\r\n")[1:]:
        name, _, value = line.partition(b":")
        if name.strip().lower() == b"content-length":
            length = int(value)
            break
    while len(rest) < length:
        chunk = sock.recv(65536)
        if not chunk:
            raise OSError("connection closed")
        rest += chunk
    return status, rest


class Generator:
    def __init__(self, spec):
        self.spec = spec
        self.port = spec["port"]
        self.timeout_s = float(spec["timeout_s"])
        self.t0 = float(spec["t0"])
        self.users = spec["users"]
        self.due = spec.get("due")
        self.keep = set(spec.get("keep", ()))
        self.item_marker = spec["item_marker"].encode()
        self.items_expected = int(spec["items_expected"])
        self.lock = threading.Lock()
        self.next = 0
        self.rows = []  # (index, due, sent, done, ok)
        self.kept = {}
        self.connects = 0
        self.errors = []

    def wire(self, index):
        body = (self.spec["body_format"] % self.users[index]).encode()
        return (REQUEST % (self.spec["path"], len(body))).encode() + body

    def claim(self):
        with self.lock:
            index = self.next
            self.next += 1
        return index

    def one(self, sock, index, due):
        """Send request ``index``; returns the socket to go on with."""
        wire = self.wire(index)
        sent = time.monotonic()
        ok = False
        try:
            status, body = exchange(sock, wire)
            ok = status == 200 and body.count(self.item_marker) == self.items_expected
            if not ok:
                self.note("status %d, %d items" % (status, body.count(self.item_marker)))
            elif index in self.keep:
                self.kept[index] = body.decode()
        except (OSError, ValueError) as exc:
            # timed out or torn: the request is failed, the connection is
            # replaced (a late reply must not be read as the next one's)
            self.note("%s: %s" % (type(exc).__name__, exc))
            sock.close()
            sock = self.reconnect()
        done = time.monotonic()
        if due is None:
            due = sent
        self.rows.append((index, due, sent, done, ok))
        return sock

    def note(self, message):
        if len(self.errors) < 8:
            self.errors.append(message)

    def reconnect(self):
        with self.lock:
            self.connects += 1
        while True:
            try:
                return connect(self.port, self.timeout_s)
            except OSError:
                time.sleep(0.05)

    def open_worker(self):
        sock = self.reconnect()
        n = len(self.due)
        while True:
            index = self.claim()
            if index >= n:
                break
            due = self.t0 + self.due[index]
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sock = self.one(sock, index, due)
        sock.close()

    def closed_worker(self):
        sock = self.reconnect()
        start, stop = self.t0 + self.spec["start_s"], self.t0 + self.spec["stop_s"]
        wait = start - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        n = len(self.users)
        while time.monotonic() < stop:
            sock = self.one(sock, self.claim() % n, None)
        sock.close()

    def run(self):
        worker = self.open_worker if self.spec["mode"] == "open" else self.closed_worker
        threads = [
            threading.Thread(target=worker, daemon=True)
            for _ in range(int(self.spec["connections"]))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rows = sorted(self.rows)
        return {
            "index": [r[0] for r in rows],
            "due": [round(r[1] - self.t0, 6) for r in rows],
            "sent": [round(r[2] - self.t0, 6) for r in rows],
            "done": [round(r[3] - self.t0, 6) for r in rows],
            "ok": [int(r[4]) for r in rows],
            "kept": {str(k): v for k, v in self.kept.items()},
            "connects": self.connects,
            "errors": self.errors,
        }


def main(argv):
    with open(argv[1]) as f:
        spec = json.load(f)
    result = Generator(spec).run()
    tmp = spec["result"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    import os

    os.replace(tmp, spec["result"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
