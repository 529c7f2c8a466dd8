"""The front door's share of time to first token: (recorder enqueued -
client send) + (client first token - recorder first token), median. HTTP
parse, handler and tokenizer on the way in; SSE encode and socket on the
way out."""
from harness import readers, stats

NAME, UNIT, BETTER = "frontdoor_p50_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "front door", "program_span", "ttft_p95_ms", "open"


def read(run):
    spans = [(rec["enqueued_at"] - c["t_send"]) + (c["t_first"] - rec["first_token_at"])
             for c, rec in readers.paired(run)
             if c.get("t_first") and rec.get("first_token_at")]
    return stats.percentile(spans, 50) * 1e3 if spans else None
