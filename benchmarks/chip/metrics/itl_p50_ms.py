"""itl_p50_ms: the median of every gap between successive output tokens
of every request, both tokens emitted in the window — the pace a
streaming reader sees between admissions.  Host clock, milliseconds."""

from chipbench.readers import token_gaps_ms
from chipbench.stats import percentile


def read(run):
    gaps = token_gaps_ms(run)
    return percentile(gaps, 50) if gaps else None
