"""The arithmetic from the load generator's records to the end-to-end
metrics. A copy of the idea in gofr_tpu/loadgen/scorecard.py, with time to
first token taken from when a request was DUE, not from when it was fired.
"""

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks, as numpy's default does."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def in_window(t, t_open: float, t_close: float) -> bool:
    return t is not None and t_open <= t < t_close


def answered(rec: dict) -> bool:
    return bool(rec.get("done")) and not rec.get("error")


def attempted(records, loop: str, t_open: float, t_close: float):
    """The requests the window is judged on: due in it (open loop) or sent
    in it (closed loop)."""
    key = "due" if loop == "open" else "t_send"
    return [r for r in records if in_window(r.get(key), t_open, t_close)]


def failed(rec: dict) -> bool:
    """Dropped, refused, timed out or token-less. A closed-loop stream cut
    by the window's close is neither answered nor failed; an open-loop one
    still unanswered when the drain ends has timed out."""
    if rec.get("error"):
        return True
    if rec.get("cancelled"):
        return "due" in rec
    return not rec.get("tokens")


def completed_in_window(records, t_open: float, t_close: float):
    return [r for r in records
            if answered(r) and in_window(r.get("t_last"), t_open, t_close)]


def tpot_ms(rec: dict):
    """(last token - first token) / (tokens - 1), as the client saw them."""
    n = len(rec["tokens"])
    if n < 2:
        return None
    return (rec["t_last"] - rec["t_first"]) / (n - 1) * 1e3


def tpots_ms(records, t_open: float, t_close: float):
    """Time per output token of each request completed in the window."""
    done = completed_in_window(records, t_open, t_close)
    return [t for t in (tpot_ms(r) for r in done) if t is not None]


def ttfts_ms(tried, t_close: float):
    """First token minus DUE of every request in `tried`. One that never
    answered is charged up to the moment the generator gave the last one
    up: it misses any limit."""
    giveup = max([t_close] + [r.get("t_end") or t_close for r in tried])
    return [((r["t_first"] if r.get("t_first") is not None else giveup)
             - r["due"]) * 1e3 for r in tried]


def lateness_p95_ms(tried):
    """How late the generator fired the requests that were due in the
    window (open loop; a closed loop has no schedule to be late for)."""
    lags = [(r["fired"] - r["due"]) * 1e3 for r in tried if "fired" in r]
    return percentile(lags, 95) if lags else None


# the end-to-end metrics this arithmetic yields, with their units
UNITS = {"out_tok_s": "tokens/s", "tpot_p95_ms": "ms", "ttft_p50_ms": "ms",
         "ttft_p95_ms": "ms", "setup_s": "s"}


def end_to_end(result: dict, loop: str) -> dict:
    """{"metrics": {name: value}, "attempted", "failed", "counts",
    "generator_late_p95_ms"}."""
    records = result["records"]
    t_open, t_close = result["t_open"], result["t_close"]
    tried = attempted(records, loop, t_open, t_close)
    metrics = {"out_tok_s": sum(r.get("tok_in_window", 0) for r in records)
               / (t_close - t_open)}
    tpots = tpots_ms(records, t_open, t_close)
    if tpots:
        metrics["tpot_p95_ms"] = percentile(tpots, 95)
    if loop == "open" and tried:
        ttfts = ttfts_ms(tried, t_close)
        metrics["ttft_p50_ms"] = percentile(ttfts, 50)
        metrics["ttft_p95_ms"] = percentile(ttfts, 95)
    return {"metrics": metrics, "attempted": len(tried),
            "failed": sum(1 for r in tried if failed(r)),
            "generator_late_p95_ms": lateness_p95_ms(tried),
            "counts": {"records": len(records), "completed_in_window":
                       len(completed_in_window(records, t_open, t_close)),
                       "tpot_samples": len(tpots)}}
