"""Model FLOPs of the tokens the traced window's steps processed (2 x
matrix parameters with the output head, plus attention at each token's
context) over (traced window x the chip's bf16 peak)."""

from chipbench.readings import traced_work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    flops, _, steps, _ = traced_work(run)
    if not steps:
        return None
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops_per_s"])
