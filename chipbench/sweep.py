"""Find a configuration's knee on one chip: the highest offered rate at
which the waiting queue does not grow across a window.

    python3 -m chipbench.sweep --workload <cell> --rates 0.8,1.0,1.2 \
        [--seconds 40] [--seed 1] [--replicas 1]

One process builds the cell's engines once and offers each rate in turn,
open loop, for the mix's ``warm_s`` and then ``--seconds``; each rate
prints one JSON line with the queue at the window's start and end, the
requests finished and tokens served in the window, and the latency tails.
``--replicas 1`` drives one replica without moves, whatever the mix says
(the one-chip sweep behind a multi-replica cell).  A cell's rate is fixed
from such a sweep once, when the cell is defined; runs never search.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import generator, program, serving, weights
from chipbench.readings import RunRecord, percentile, token_gaps, ttfts
from chipbench.run import chip_devices, configure_compile_cache, log
from chipbench.spec import load_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--replicas", type=int, default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    mix = dict(cell.traffic, drain_s=0)
    if args.replicas:
        mix.update(replicas=args.replicas, move_every_s=0)
    devices = chip_devices(int(mix.get("replicas", 1)))
    configure_compile_cache()
    config, model, arch = cell.config, cell.config["model"], cell.arch
    cfg = arch.model_config(config)
    params = program.params(
        arch, weights.generate(arch, model, args.seed, devices[0]), cfg)
    for rate in (float(r) for r in args.rates.split(",")):
        rec = serving.Recorder()
        note = serving.Annotator(False)
        replicas = [serving.Replica(i, program.engine(config, cfg, params,
                                                      devices[i]), rec, note)
                    for i in range(int(mix.get("replicas", 1)))]
        for rep in replicas:
            serving.warm_up(rep)
        queued = {}

        def on_edge(label, replicas=replicas, queued=queued):
            queued[label] = sum(len(r.engine.queue) for r in replicas)

        arrivals = generator.schedule(mix, rate, args.seconds, args.seed,
                                      model["vocab_size"])
        warm = float(mix["warm_s"])
        win = serving.Runner(replicas, arrivals, mix, args.seconds, rec,
                             [("start", warm), ("end", warm + args.seconds)],
                             on_edge).run()
        run = RunRecord(cell, win, win.start, win.end, 0.0, rec, None, None)
        lo, hi = win.start, win.end
        done = [r for r in rec.requests.values()
                if r.request.done and r.tokens and lo <= r.tokens[-1] <= hi]
        tokens = sum(1 for r in rec.requests.values() for t in r.tokens
                     if lo <= t <= hi)
        line = {"rate": rate, "queue_start": queued["start"],
                "queue_end": queued["end"], "finished": len(done),
                "tokens_per_s": tokens / (hi - lo),
                "ttft_p90_s": percentile(ttfts(run), 90),
                "itl_p95_ms": percentile([g * 1e3 for g in token_gaps(run)], 95),
                "steps": len(rec.steps)}
        print(json.dumps(line), flush=True)
        del replicas
    log("sweep done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
