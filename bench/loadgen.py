"""Load generator: the child process that plays the cell's clients.

    python3 bench/loadgen.py < job.json > records.json

It imports the standard library and numpy only (never JAX), so its
clients do not share an interpreter lock with the server's scheduler
thread. It reads one JSON job and then a line ``go`` from stdin, then
sends streamed greedy ``POST /v1/completions`` requests to
the loopback port for ``seconds`` and writes one JSON object of records
to stdout once every request sent has ended (or ``drain_s`` has passed
since the window closed).

Job: ``{"port", "seconds", "drain_s", "seed", "vocab", "mix": {...}}``,
the mix as :mod:`bench.traffic` reads it.

Each record stamps the request on the host's monotonic clock (shared
with the parent): ``due`` (when it should have gone out: the open
loop's schedule, or the closed loop's send), ``sent``, and the arrival
of every SSE token frame. Open-loop latency counts from ``due``, so a
late generator shows as latency, and ``lateness = sent - due`` is
reported on its own.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import traffic  # noqa: E402

SOCKET_TIMEOUT_S = 120.0


def stream_one(port: int, prompt: list, max_tokens: int, rec: dict) -> None:
    """One socket client: POST a streamed completion and stamp every
    SSE data frame as it arrives. Fills ``rec`` in place."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True, "temperature": 0.0}).encode()
    tokens, stamps = [], []
    rec.update(tokens=tokens, stamps=stamps, status=0, done=False)
    try:
        s = socket.create_connection(("127.0.0.1", port),
                                     timeout=SOCKET_TIMEOUT_S)
    except OSError as e:
        rec["error"] = repr(e)
        return
    try:
        rec["sent"] = time.monotonic()
        s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        buf, head = b"", True
        while True:
            chunk = s.recv(65536)
            now = time.monotonic()
            if not chunk:
                break
            buf += chunk
            if head and b"\r\n\r\n" in buf:
                line, buf = buf.split(b"\r\n\r\n", 1)
                rec["status"] = int(line.split(b" ", 2)[1])
                head = False
            if head:
                continue
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                for ln in frame.split(b"\n"):
                    if not ln.startswith(b"data: "):
                        if ln.startswith(b"event: error"):
                            rec["error"] = frame.decode(errors="replace")
                        continue
                    data = ln[6:].decode()
                    if data == "[DONE]":
                        rec["done"] = True
                        continue
                    event = json.loads(data)
                    if "choices" not in event:
                        rec["error"] = data
                        continue
                    tokens.append(event["choices"][0]["token"])
                    stamps.append(now)
            if rec["done"]:
                break
        if not head and rec["status"] != 200:
            rec["error"] = buf.decode(errors="replace")[:500]
    except (OSError, ValueError) as e:
        rec["error"] = repr(e)
    finally:
        s.close()


def run(job: dict, start_signal=None) -> dict:
    """Play the job's mix; → {"t_start", "t_end", "records": [...]}."""
    mix, seed, seconds = job["mix"], int(job["seed"]), float(job["seconds"])
    port, vocab = int(job["port"]), int(job["vocab"])
    records: list[dict] = []
    lock = threading.Lock()
    threads: list[threading.Thread] = []
    if start_signal is not None:
        start_signal()
    t0 = time.monotonic()
    t_end = t0 + seconds

    def send(stream: int, index: int, plen: int, mt: int, due: float):
        rec = {"stream": stream, "index": index, "prompt_len": plen,
               "max_tokens": mt, "due": due}
        with lock:
            records.append(rec)
        stream_one(port, traffic.prompt(seed, stream, index, plen, vocab),
                   mt, rec)

    if mix["loop"] == "closed":
        def client(c: int) -> None:
            i = 0
            while time.monotonic() < t_end:
                plen, mt = traffic.closed_sizes(mix, seed, c, i)
                send(c, i, plen, mt, time.monotonic())
                i += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(int(mix["clients"]))]
        for th in threads:
            th.start()
    else:
        for i, arr in enumerate(traffic.open_schedule(mix, seed, seconds)):
            due = t0 + arr["at"]
            while (wait := due - time.monotonic()) > 0:
                time.sleep(wait)
            th = threading.Thread(target=send, daemon=True, args=(
                0, i, arr["prompt_len"], arr["max_tokens"], due))
            th.start()
            threads.append(th)
    deadline = t_end + float(job.get("drain_s", 60.0))
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    with lock:
        recs = [dict(r) for r in records]
    return {"t_start": t0, "t_end": t_end, "records": recs,
            "unfinished": sum(th.is_alive() for th in threads)}


def main() -> int:
    job = json.loads(sys.stdin.readline())

    def wait_go():
        line = sys.stdin.readline().strip()
        if line != "go":
            raise SystemExit(f"loadgen: expected 'go', got {line!r}")

    out = run(job, start_signal=wait_go)
    sys.stdout.write(json.dumps(out))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
