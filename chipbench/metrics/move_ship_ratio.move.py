"""Mean over the moves begun in the window of the bytes shipped over the
bytes of the session's live state at its positions so far, as the cell's
architecture counts it (a dense decoder's: its keys and values)."""

from chipbench.readings import window_moves


def read(run):
    ratios = [m.shipped_bytes
              / run.cell.arch.session_bytes(run.model, m.live_positions)
              for m in window_moves(run) if m.live_positions]
    return sum(ratios) / len(ratios) if ratios else None
