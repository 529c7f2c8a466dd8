"""How late the load generator fired: fired - due, 95th percentile over the
requests due in the window. A starved generator must not read as a fast
server."""
from harness import readers, stats

NAME, UNIT, BETTER = "gen_lag_p95_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "load generator", "host_clock", "ttft_p95_ms", "open"


def read(run):
    return stats.lateness_p95_ms(readers.judged(run))
