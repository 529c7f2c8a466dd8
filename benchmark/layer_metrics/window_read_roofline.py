"""The windowed paged read's share of its roofline, bound by bytes: the
least bytes a decode step's sliding_attention blocks have to read (each
live row's min(context, window) tokens' K and V once, its queries in and
its output out: benchmark/reference/afmoe.py `window_read_bytes`) over the
chip's 819 GB/s, divided by the device time a step of the kernel named
`window_read`. `harness/readers.live` gives rows and whole contexts, so
the tokens INSIDE the window are summed here, from the clients' own stamps
as `live` reckons a context (its prompt plus the tokens it had been SENT,
never more than the device held). A family whose program launches no such
kernel, and a program that has no such scope, report nothing."""
from harness import peaks, readers

NAME, UNIT, BETTER = "window_read_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def inside(run: dict, window: int, instants: int = 32):
    """(rows, tokens inside the window): the means over `instants` moments
    of the traced window, as `readers.live` takes them."""
    trace = readers.trace_of(run)
    t0, t1 = trace["t0"], trace["t1"]
    rows = tokens = 0.0
    recs = [r for r in run["result"]["records"]
            if r.get("t_first") is not None and r["t_last"] > r["t_first"]]
    for i in range(instants):
        t = t0 + (t1 - t0) * (i + 0.5) / instants
        for r in recs:
            if r["t_first"] <= t <= r["t_last"]:
                share = (t - r["t_first"]) / (r["t_last"] - r["t_first"])
                context = (r["prompt_tokens"] + 1
                           + share * (len(r["tokens"]) - 1))
                rows += 1.0 / instants
                tokens += min(context, window) / instants
    return rows, tokens


def read(run):
    window = run["facts"].get("window")
    traced, stated = readers.kernel(run, "window_read")
    steps = readers.decode_steps_traced(run)
    if not window or not steps or not traced or not traced["seconds"] \
            or not stated:
        return None
    rows, tokens = inside(run, window)
    least = stated["least_bytes"](rows, tokens, tokens) \
        / readers.trace_of(run)["devices"]
    peak = peaks.of(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / (traced["seconds"] / steps)
