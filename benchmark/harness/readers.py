"""What the per-layer metric readers share: how to pick the window's own
requests and step records out of a run (harness/serve.py `measure`)."""

from . import stats


def judged(run: dict):
    """The client records the window is judged on (due or sent in it)."""
    return stats.attempted(run["result"]["records"],
                           run["loaded"]["mix"]["loop"],
                           run["t_open"], run["t_close"])


def recorder_by_index(run: dict) -> dict:
    """The flight recorder's request stamps, by the load generator's
    request index (the client sends it as the traceparent's trace id)."""
    out = {}
    for rec in run["requests"]:
        if rec.get("trace_id"):
            out[int(rec["trace_id"], 16) - 1] = rec
    return out


def paired(run: dict):
    """(client record, recorder record) for each judged request that the
    recorder still holds."""
    held = recorder_by_index(run)
    return [(c, held[c["index"]]) for c in judged(run) if c["index"] in held]


def decode_steps(run: dict):
    return [s for s in run["steps"] if s["phase"] == "decode"]


def trace_of(run: dict):
    trace = run.get("trace") or {}
    return trace if trace.get("devices") else None


def live(run: dict, instants: int = 32):
    """(rows, tokens): how many requests were decoding and how many tokens
    of context they held, the mean over `instants` moments of the traced
    window, from the clients' own stamps. A request's context at time t is
    its prompt plus the tokens it had been SENT by then, which is never
    more than the device held, so bytes computed from it are never too
    many."""
    trace = trace_of(run)
    t0, t1 = trace["t0"], trace["t1"]
    rows = tokens = 0.0
    recs = [r for r in run["result"]["records"]
            if r.get("t_first") is not None and r["t_last"] > r["t_first"]]
    for i in range(instants):
        t = t0 + (t1 - t0) * (i + 0.5) / instants
        for r in recs:
            if r["t_first"] <= t <= r["t_last"]:
                share = (t - r["t_first"]) / (r["t_last"] - r["t_first"])
                rows += 1.0 / instants
                tokens += (r["prompt_tokens"] + 1
                           + share * (len(r["tokens"]) - 1)) / instants
    return rows, tokens


def decode_steps_traced(run: dict):
    """How many decode steps the traced window ran: one paged-read kernel
    call a layer a step."""
    trace = trace_of(run)
    if trace is None:
        return 0.0
    layers = run["loaded"]["dims"]["L"]
    return trace["kernels"]["read"]["calls"] / layers
