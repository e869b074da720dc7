"""collective-permute ops in the compiled executable of one call (round
loop), counted from its HLO."""

from bench.hlo import collective_stats


def read(r):
    n = collective_stats(r.hlo).ops_by_kind.get("collective-permute", 0)
    return float(n) if n else None
