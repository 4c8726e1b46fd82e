"""95th percentile over every gap between successive generated tokens of
every request, for the gaps that end inside the window."""

from chipbench.readings import percentile, token_gaps


def read(run):
    p = percentile(token_gaps(run), 95)
    return None if p is None else p * 1e3
