"""Open-loop HTTP load generator, run as a child process that never imports JAX.

The serving process starts it, so that the client does not share the
server's interpreter lock. It speaks one JSON line at a time:

    parent -> child  {"seed", "pool", "channels", "hw", "arrivals",
                      "seconds", "connections", "warm", "timeout_s"}
    child  -> parent {"ready": true}       bodies built
    parent -> child  {"port": p}
    child  -> parent {"warm": n}           n warm-up requests answered
    parent -> child  {"go": true}
    child  -> parent {"t0": ..., "requests": [...], "late": {...}}

Each request is one image, ``POST /v1/run``, sent at its scheduled time on
one of ``connections`` keep-alive connections; bodies are built before the
window from a pool of distinct seeded images. ``requests`` holds, per
request, its pool index, scheduled and sent times (``time.monotonic``,
shared with the parent), completion time, HTTP status, and its logits
(base64 float32) when it succeeded.
"""

from __future__ import annotations

import base64
import http.client
import json
import queue
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import traffic  # noqa: E402


def _post(conn, body: bytes) -> tuple[int, bytes]:
    conn.request("POST", "/v1/run", body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.read()


class Client:
    def __init__(self, port: int, bodies: list[bytes], connections: int,
                 timeout_s: float):
        self.port = port
        self.bodies = bodies
        self.timeout_s = timeout_s
        self.todo: queue.Queue = queue.Queue()
        self.results: dict[int, tuple] = {}
        self.lock = threading.Lock()
        self.threads = [
            threading.Thread(target=self._work, daemon=True)
            for _ in range(connections)
        ]
        for t in self.threads:
            t.start()

    def _connect(self):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout_s)
        conn.connect()
        return conn

    def _work(self) -> None:
        conn = self._connect()
        while True:
            item = self.todo.get()
            if item is None:
                conn.close()
                return
            rid, body_idx = item
            sent = time.monotonic()
            try:
                status, data = _post(conn, self.bodies[body_idx])
            except (OSError, http.client.HTTPException) as e:
                status, data = 0, str(e).encode()
                conn.close()
                conn = self._connect()
            with self.lock:
                self.results[rid] = (sent, time.monotonic(), status, data)

    def close(self) -> None:
        for _ in self.threads:
            self.todo.put(None)
        for t in self.threads:
            t.join(timeout=self.timeout_s)


def _say(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _hear() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("loadgen: parent closed the pipe")
    return json.loads(line)


def _logits(data: bytes) -> str:
    arr = np.asarray(json.loads(data)["logits"], np.float32)
    return base64.b64encode(arr.tobytes()).decode()


def main() -> int:
    cfg = _hear()
    images = traffic.make_images(cfg["seed"], cfg["pool"], cfg["channels"], cfg["hw"])
    bodies = [json.dumps({"image": im.tolist()}).encode() for im in images]
    sched = traffic.schedule(cfg["arrivals"], cfg["seconds"], cfg["seed"])
    order = traffic.request_order(cfg["seed"], len(sched), cfg["pool"])
    _say({"ready": True})

    client = Client(_hear()["port"], bodies, cfg["connections"], cfg["timeout_s"])
    warm = cfg["warm"]
    for i in range(warm):
        client.todo.put((-1 - i, i % len(bodies)))
    deadline = time.monotonic() + cfg["timeout_s"]
    while len(client.results) < warm and time.monotonic() < deadline:
        time.sleep(0.01)
    warm_ok = sum(1 for r in client.results.values() if r[2] == 200)
    client.results.clear()
    _say({"warm": warm_ok})

    _hear()  # go
    t0 = time.monotonic()
    late = []
    for rid, t in enumerate(sched):
        due = t0 + t
        while (now := time.monotonic()) < due:
            time.sleep(min(due - now, 0.002))
        late.append(time.monotonic() - due)
        client.todo.put((rid, int(order[rid])))
    deadline = time.monotonic() + cfg["timeout_s"]
    while len(client.results) < len(sched) and time.monotonic() < deadline:
        time.sleep(0.005)
    with client.lock:
        results = dict(client.results)
    client.close()

    out = []
    for rid, t in enumerate(sched):
        rec = {"pool": int(order[rid]), "due": t0 + float(t)}
        if rid in results:
            sent, done, status, data = results[rid]
            rec.update(sent=sent, done=done, status=status)
            if status == 200:
                rec["logits"] = _logits(data)
        out.append(rec)
    sent_late = [r["sent"] - r["due"] for r in out if "sent" in r]
    _say({
        "t0": t0,
        "requests": out,
        "late": {
            "dispatch_p99_ms": float(np.percentile(late, 99)) * 1e3,
            "send_p50_ms": float(np.percentile(sent_late, 50)) * 1e3 if sent_late else None,
            "send_p99_ms": float(np.percentile(sent_late, 99)) * 1e3 if sent_late else None,
            "send_max_ms": float(np.max(sent_late)) * 1e3 if sent_late else None,
        },
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
