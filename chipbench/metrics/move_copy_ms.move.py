"""Mean over the moves begun in the window of the host-clock time from the
``export_slot`` call until the destination's cache is ready."""

from chipbench.readings import window_moves


def read(run):
    moves = window_moves(run)
    return (sum(m.t_ready - m.t_begin for m in moves) / len(moves) * 1e3
            if moves else None)
