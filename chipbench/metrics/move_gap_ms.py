"""Mean over the moves begun in the window of the moved session's gap:
its last token on the source to its first token on the destination."""

from chipbench.readings import move_gap, window_moves


def read(run):
    gaps = [g for g in (move_gap(run, m) for m in window_moves(run))
            if g is not None]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
