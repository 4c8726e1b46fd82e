"""Whether the timed path served the right tokens.

Once the window has closed and the engines are freed, a sample of the
finished requests, drawn from the seed, with the one that served most
tokens in it (and, where sessions moved, up to two that moved), is run
through the plain reference: each prompt with its served tokens, in one
float32 pass.  At every served position the reference's best logit minus
its logit of the token the program served is that token's gap; the widest
gap is compared with the cell's ``logit_gap`` limit.  Greedy decoding
serves the program's own argmax, so a correct bfloat16 program loses only
near-ties to rounding.

The control is the same reference with its matrix products in float8
(``mode="fp8"``): the gap of the token that it puts first at each position.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Sequence

import jax
import numpy as np

from chipbench.serving import ReqRecord


def sample(finished: Sequence[ReqRecord], n: int, seed: int,
           moved_wanted: int) -> List[ReqRecord]:
    if not finished:
        return []
    finished = sorted(finished, key=lambda r: r.arrival.req_id)
    longest = max(finished, key=lambda r: (len(r.request.output),
                                           -r.arrival.req_id))
    rng = np.random.default_rng([seed, 7])
    chosen = [longest]
    moved = [r for r in finished if r.moved and r is not longest]
    for i in rng.permutation(len(moved))[:moved_wanted]:
        chosen.append(moved[i])
    rest = [r for r in finished if all(r is not c for c in chosen)]
    for i in rng.permutation(len(rest))[:max(0, n - len(chosen))]:
        chosen.append(rest[i])
    return chosen


def served_gaps(w: Dict[str, jax.Array], model: Dict, ref: ModuleType,
                records: Sequence[ReqRecord], max_len: int,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Per sampled request: the gap of each served token by the plain
    reference ``ref`` and, with ``control``, of the float8 pass's first
    choice."""
    out = {"program": [], "control": []}
    for r in records:
        seq = list(r.request.prompt) + list(r.request.output)
        if len(seq) > max_len:
            raise ValueError(f"request {r.arrival.req_id}: {len(seq)} tokens")
        tokens = np.zeros(max_len, np.int32)
        targets = np.zeros(max_len, np.int32)
        tokens[:len(seq) - 1] = seq[:-1]
        targets[:len(seq) - 1] = seq[1:]
        rows = jax.device_get(ref.compare(w, tokens, targets, model, control))
        served = slice(len(r.request.prompt) - 1, len(seq) - 1)
        out["program"].append(rows["best"][served] - rows["target"][served])
        if control:
            out["control"].append(rows["best"][served] - rows["control"][served])
    return out


def checks(records: Sequence[ReqRecord], gaps: Dict, limits: Dict[str, float],
           moves_expected: bool) -> Dict[str, Dict]:
    """Each number compared, with its limit; ``ok`` says whether it held."""
    widest = max((float(g.max()) for g in gaps["program"] if g.size),
                 default=float("nan"))
    out = {
        "logit_gap": {"value": widest, "limit": limits["logit_gap"],
                      "rule": "<="},
        "short_outputs": {"value": sum(len(r.request.output) != r.arrival.max_new
                                       for r in records),
                          "limit": 0, "rule": "<="},
        "compared_requests": {"value": len(records), "limit": 1, "rule": ">="},
    }
    if moves_expected:
        out["moved_compared"] = {"value": sum(1 for r in records if r.moved),
                                 "limit": 1, "rule": ">="}
    for c in out.values():
        v = c["value"]
        c["ok"] = bool(np.isfinite(v) and (v <= c["limit"] if c["rule"] == "<="
                                           else v >= c["limit"]))
    return out
