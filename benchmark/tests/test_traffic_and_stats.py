"""Traffic generation, percentiles, and timing from DUE on a stalled server."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from harness import data, stats, traffic

CHILD = os.path.join(data.BENCH_DIR, "harness", "loadgen_child.py")


def test_percentile_interpolates_like_numpy():
    values = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(values, 50) == 30.0
    assert stats.percentile(values, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("mix", ["decode-closed", "chat-open",
                                 "longchat-closed"])
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    spec = data._merged(data._json("traffic", mix), False)
    a, b = traffic.Schedule(spec, 1), traffic.Schedule(spec, 2 ** 31 + 9)
    n = a.grid
    for block in (0, 3):
        rows_a = [a.request(block * n + i) for i in range(n)]
        rows_b = [b.request(block * n + i) for i in range(n)]
        for key in ("prompt_tokens", "output_tokens", "gap_s"):
            assert sorted(r[key] for r in rows_a) == sorted(r[key] for r in rows_b)
        assert [r["prompt_tokens"] for r in rows_a] != [r["prompt_tokens"] for r in rows_b]
    lo, hi = spec["prompt_tokens"]["lo"], spec["prompt_tokens"]["hi"]
    assert all(lo <= a.request(i)["prompt_tokens"] <= hi for i in range(n))
    if spec["loop"] == "open":
        mean_gap = sum(a.request(i)["gap_s"] for i in range(n)) / n
        assert mean_gap == pytest.approx(1.0 / spec["rate_rps"], rel=0.05)


def test_closed_loop_ramp_is_dephased():
    spec = data._merged(data._json("traffic", "decode-closed"), False)
    sched = traffic.Schedule(spec, 5)
    cuts = sched.first_outputs(spec["clients"])
    full = [sched.request(c)["output_tokens"] for c in range(spec["clients"])]
    assert all(1 <= cut <= n for cut, n in zip(cuts, full))
    shares = sorted(cut / n for cut, n in zip(cuts, full))
    # the shares are the quantile grid of uniform(0, 1]: evenly spread
    assert shares[0] < 0.03 and shares[-1] > 0.97
    assert max(b - a for a, b in zip(shares, shares[1:])) < 0.03


def test_prompts_are_unique_and_round_trip_the_servers_tokenizer():
    from gofr_tpu.models.tokenizer import DebugTokenizer

    tok = DebugTokenizer(92544)
    ids = traffic.prompt_ids(3, 17, 40, 92544)
    assert len(ids) == 40 and ids[0] == tok.BOS
    assert tok.encode(traffic.ids_to_text(ids)) == ids
    assert ids != traffic.prompt_ids(3, 18, 40, 92544)
    for token in (0, 65, 255, 259, 92543):
        assert traffic.char_to_id(tok.decode_token(token)) == token
    assert traffic.char_to_id(tok.decode_token(tok.EOS)) == -1


class _Stalled(http.server.BaseHTTPRequestHandler):
    """An SSE server whose first token comes late: STALL_S after the
    request, then the rest at once."""
    STALL_S = 0.3
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Connection", "close")
        self.end_headers()
        time.sleep(self.STALL_S)
        for _ in range(body["max_tokens"]):
            self.wfile.write(b'data: {"text": "\\ue200"}\n\n')
            self.wfile.flush()
        self.wfile.write(b'data: {"done": true, "tokens": %d}\n\n'
                         % body["max_tokens"])
        self.close_connection = True

    def log_message(self, *args):
        pass


def _drive(mix: dict, seconds: float, port: int) -> dict:
    spec = {"mix": mix, "seed": 1, "vocab": 92544, "port": port,
            "seconds": seconds}
    out = subprocess.run([sys.executable, CHILD], input=json.dumps(spec) + "\n",
                         capture_output=True, text=True, timeout=120)
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines[0]["event"] == "window_open", out.stderr
    assert lines[-1]["event"] == "result" and not lines[-1]["fatal"], out.stderr
    return lines[-1]


@pytest.fixture()
def stalled_port():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stalled)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()


def test_open_loop_times_from_due_on_a_stalled_server(stalled_port):
    mix = {"loop": "open", "arrivals": "poisson", "rate_rps": 20.0,
           "max_in_flight": 64, "grid": 8,
           "prompt_tokens": {"dist": "fixed", "value": 8},
           "output_tokens": {"dist": "fixed", "value": 4},
           "ramp": {"open_after_s": 0.5, "drain_s": 5}}
    result = _drive(mix, 2.0, stalled_port)
    ends = stats.end_to_end(result, "open")
    assert ends["attempted"] >= 25 and ends["failed"] == 0
    # the stall is the server's: time to first token holds all of it
    assert ends["metrics"]["ttft_p50_ms"] >= _Stalled.STALL_S * 1e3
    assert ends["metrics"]["ttft_p50_ms"] < _Stalled.STALL_S * 1e3 + 150
    tried = stats.attempted(result["records"], "open", result["t_open"],
                            result["t_close"])
    lags = [r["fired"] - r["due"] for r in tried]
    assert 0.0 <= min(lags) and stats.percentile(lags, 95) < 0.05
    assert all(r["tokens"] == [0xE200 - 0xE000] * 4 for r in tried)
    assert ends["metrics"]["out_tok_s"] == pytest.approx(20.0 * 4, rel=0.35)


def test_open_loop_counts_what_it_could_not_send_as_failed(stalled_port):
    mix = {"loop": "open", "arrivals": "poisson", "rate_rps": 40.0,
           "max_in_flight": 2, "grid": 8,
           "prompt_tokens": {"dist": "fixed", "value": 8},
           "output_tokens": {"dist": "fixed", "value": 2},
           "ramp": {"open_after_s": 0.2, "drain_s": 5}}
    ends = stats.end_to_end(_drive(mix, 1.5, stalled_port), "open")
    assert ends["failed"] > 0 and ends["failed"] < ends["attempted"]


def test_closed_loop_opens_when_every_client_has_an_answer(stalled_port):
    mix = {"loop": "closed", "clients": 3, "grid": 4,
           "prompt_tokens": {"dist": "fixed", "value": 8},
           "output_tokens": {"dist": "uniform", "lo": 4, "hi": 8},
           "ramp": {"max_s": 20}}
    result = _drive(mix, 1.5, stalled_port)
    ramp = [r for r in result["records"] if r["ramp"]]
    assert len(ramp) == 3
    assert result["t_open"] >= max(r["t_end"] for r in ramp) - 0.01
    ends = stats.end_to_end(result, "closed")
    assert ends["attempted"] >= 6 and ends["failed"] == 0
    assert "ttft_p50_ms" not in ends["metrics"]
    # a stream the close cut is neither answered nor failed
    assert all(stats.answered(r) or r["cancelled"] for r in result["records"])
