"""Open loop: time per output token as the client sees it, 95th percentile
over the requests completed in the window. In the closed cells this is the
end-to-end `tpot_p95_ms`; in the open cell it spread by 6-8 % between runs
of one code (PR 23), so there it is read from the traced run, unbounded."""
from harness import stats

NAME, UNIT, BETTER = "tpot_open_p95_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "engine loop", "host_clock", "out_tok_s", "open"


def read(run):
    tpots = stats.tpots_ms(run["result"]["records"], run["t_open"],
                           run["t_close"])
    return stats.percentile(tpots, 95) if tpots else None
