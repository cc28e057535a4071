"""itl_p95_ms: the 95th percentile of every gap between successive output
tokens of every request, both tokens emitted in the window.  Host clock,
milliseconds."""

from chipbench.readers import token_gaps_ms
from chipbench.stats import percentile


def read(run):
    gaps = token_gaps_ms(run)
    return percentile(gaps, 95) if gaps else None
