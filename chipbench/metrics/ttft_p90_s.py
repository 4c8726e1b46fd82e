"""90th percentile, over every request due in the window, of due -> first
generated token; a request still waiting when the run stopped enters at
(stop - due)."""

from chipbench.readings import percentile, ttfts


def read(run):
    return percentile(ttfts(run), 90)
