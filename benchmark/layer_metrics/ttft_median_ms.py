"""Open loop: first token - DUE, the median over the requests due in the
window. Not an end-to-end metric with a bound: two sets of six runs of one
code spread by 7-10 % (PR 23), wider than any bound may be. It stands
beside `ttft_p95_ms`, read from the traced run."""
from harness import readers, stats

NAME, UNIT, BETTER = "ttft_median_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "admission", "host_clock", "ttft_p95_ms", "open"


def read(run):
    tried = readers.judged(run)
    return (stats.percentile(stats.ttfts_ms(tried, run["t_close"]), 50)
            if tried else None)
