"""The load generator: a child process of the standard library only.

It never imports JAX, so it cannot touch the chip and does not share the
server's interpreter lock. It reads one JSON object from standard input
({"mix", "seed", "vocab", "port", "seconds"}), sends the mix's requests to
POST /generate as SSE streams, and prints JSON lines: {"event":
"window_open", "t_open", "t_close"} as soon as the window is known, and at
the end {"event": "result", "records": [...], ...}. Every stamp is
time.monotonic(), which is system-wide on Linux, so the parent's and the
flight recorder's stamps subtract from these.

Open loop: arrivals are due on a schedule fixed before the first is sent;
a request is timed from when it was DUE, and how late it was fired is
reported. Arrivals that find `max_in_flight` streams open are not sent and
count as failed. The window opens `ramp.open_after_s` after the first due
time. Closed loop: `clients` callers each wait for their answer; each
client's first answer is cut short (Schedule.first_outputs) and the window
opens when every client has finished one request.
"""

import http.client
import itertools
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402  (stdlib-only sibling)

REQUEST_TIMEOUT_S = 120.0


class Window:
    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t_open = None
        self.t_close = None
        self.stop = threading.Event()

    def open_at(self, t_open: float) -> None:
        self.t_open, self.t_close = t_open, t_open + self.seconds
        print(json.dumps({"event": "window_open", "t_open": self.t_open,
                          "t_close": self.t_close}), flush=True)

    def holds(self, t: float) -> bool:
        return self.t_open is not None and self.t_open <= t < self.t_close


def stream_one(port: int, seed: int, vocab: int, req: dict, n_out: int,
               window: Window, rec: dict) -> dict:
    """One SSE request. Never raises: what went wrong lands in rec["error"]."""
    ids = traffic.prompt_ids(seed, req["index"], req["prompt_tokens"], vocab)
    body = json.dumps({"prompt": traffic.ids_to_text(ids), "stream": True,
                       "max_tokens": n_out, "min_tokens": n_out,
                       "temperature": 0.0})
    rec.update(index=req["index"], prompt_tokens=req["prompt_tokens"],
               output_tokens=n_out, tokens=[], tok_in_window=0, done=False,
               cancelled=False, error=None, t_first=None, t_last=None)
    conn = None
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=REQUEST_TIMEOUT_S)
        rec["t_send"] = time.monotonic()
        conn.request("POST", "/generate", body=body, headers={
            "Content-Type": "application/json",
            # the flight recorder files the request under this trace id
            "traceparent": "00-%032x-%016x-01" % (req["index"] + 1, 1)})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"status {resp.status}: {resp.read()[:200]!r}"
            return rec
        buf = b""
        while not rec["done"]:
            if window.stop.is_set():
                rec["cancelled"] = True
                break
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = time.monotonic()
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                payload = json.loads(event[6:])
                if payload.get("done"):
                    rec["done"] = True
                    rec["server_tokens"] = payload.get("tokens")
                    break
                if "text" not in payload:
                    continue
                rec["tokens"].append(traffic.char_to_id(payload["text"]))
                if rec["t_first"] is None:
                    rec["t_first"] = now
                rec["t_last"] = now
                if window.holds(now):
                    rec["tok_in_window"] += 1
        if rec["done"]:
            # take the body to its end, so that the close is a clean FIN and
            # the server's handler thread does not die on a reset
            while resp.read1(65536):
                pass
        if not rec["done"] and not rec["cancelled"]:
            rec["error"] = "stream ended without its done event"
        elif rec["done"] and len(rec["tokens"]) != n_out:
            rec["error"] = (f"asked for {n_out} tokens, got "
                            f"{len(rec['tokens'])}")
    except Exception as exc:  # noqa: BLE001 - a failed request is a result
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        if conn is not None:
            conn.close()
        rec["t_end"] = time.monotonic()
    return rec


def run_closed(spec: dict, window: Window) -> dict:
    mix, seed = spec["mix"], spec["seed"]
    clients = int(mix["clients"])
    sched = traffic.Schedule(mix, seed)
    first = sched.first_outputs(clients)
    counter = itertools.count(clients)
    lock = threading.Lock()
    records, finished_one = [], set()
    t_start = time.monotonic()

    def client(c: int) -> None:
        index, cut = c, first[c]
        while not window.stop.is_set():
            rec = {"client": c, "ramp": cut is not None}
            stream_one(spec["port"], seed, spec["vocab"], sched.request(index),
                       cut or sched.request(index)["output_tokens"], window,
                       rec)
            with lock:
                records.append(rec)
                finished_one.add(c)
                if len(finished_one) == clients and window.t_open is None:
                    window.open_at(time.monotonic())
                index = next(counter)
            cut = None
            if rec["error"]:
                time.sleep(0.05)        # a refusing server is not hammered

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    max_ramp = float(mix.get("ramp", {}).get("max_s", 120))
    while window.t_open is None:
        if time.monotonic() - t_start > max_ramp:
            window.stop.set()
            return {"records": records, "fatal": f"the ramp did not finish "
                    f"in {max_ramp} s: {len(finished_one)} of {clients} "
                    f"clients had an answer"}
        time.sleep(0.01)
    time.sleep(max(0.0, window.t_close - time.monotonic()))
    window.stop.set()
    for t in threads:
        t.join(timeout=REQUEST_TIMEOUT_S)
    return {"records": records, "fatal": None}


def run_open(spec: dict, window: Window) -> dict:
    mix, seed = spec["mix"], spec["seed"]
    sched = traffic.Schedule(mix, seed)
    ramp = mix.get("ramp", {})
    cap = int(mix.get("max_in_flight", 1 << 30))
    lock = threading.Lock()
    records, threads, in_flight = [], [], [0]
    t0 = time.monotonic() + 0.25
    window.open_at(t0 + float(ramp.get("open_after_s", 10)))

    def worker(req: dict, rec: dict) -> None:
        stream_one(spec["port"], seed, spec["vocab"], req,
                   req["output_tokens"], window, rec)
        with lock:
            in_flight[0] -= 1

    due, at_mid = t0, None
    t_mid = (window.t_open + window.t_close) / 2.0
    for i in itertools.count():
        req = sched.request(i)
        due += req["gap_s"]
        if due >= window.t_close:
            break
        time.sleep(max(0.0, due - time.monotonic()))
        if at_mid is None and due >= t_mid:
            at_mid = in_flight[0]
        rec = {"due": due, "fired": time.monotonic(), "index": i}
        with lock:
            records.append(rec)
            if in_flight[0] >= cap:
                rec.update(error=f"{cap} streams in flight: not sent",
                           shed=True, tokens=[], tok_in_window=0, done=False,
                           cancelled=False, t_first=None, t_last=None,
                           prompt_tokens=req["prompt_tokens"],
                           output_tokens=req["output_tokens"])
                continue
            in_flight[0] += 1
        t = threading.Thread(target=worker, args=(req, rec), daemon=True)
        t.start()
        threads.append(t)
    with lock:
        at_close = in_flight[0]
    # requests due in the window may still finish: what has not answered
    # drain_s after the close is cancelled and counts as failed
    deadline = window.t_close + float(ramp.get("drain_s", 30))
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    window.stop.set()
    for t in threads:
        t.join(timeout=REQUEST_TIMEOUT_S)
    return {"records": records, "fatal": None, "in_flight_at_mid": at_mid,
            "in_flight_at_close": at_close}


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    window = Window(float(spec["seconds"]))
    run = run_closed if spec["mix"]["loop"] == "closed" else run_open
    out = run(spec, window)
    out.update(event="result", t_open=window.t_open, t_close=window.t_close)
    print(json.dumps(out), flush=True)
    return 1 if out["fatal"] else 0


if __name__ == "__main__":
    sys.exit(main())
