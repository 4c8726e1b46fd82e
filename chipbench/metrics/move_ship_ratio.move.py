"""Mean over the moves begun in the window of the bytes shipped over the
bytes of the session's live keys and values (its positions so far)."""

from chipbench.counts import kv_bytes_per_position
from chipbench.readings import window_moves


def read(run):
    per = kv_bytes_per_position(run.model)
    ratios = [m.shipped_bytes / (m.live_positions * per)
              for m in window_moves(run) if m.live_positions]
    return sum(ratios) / len(ratios) if ratios else None
