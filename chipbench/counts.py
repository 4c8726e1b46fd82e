"""Operations and bytes that one decode step of a dense decoder needs,
counted from the tokens it processed and the positions each attended,
never from the program's shapes or ``max_len``: the same work reads the
same whatever implements it.

Per token at context c (positions 0..c-1 attended, its own included):
  FLOPs  2 x (matrix parameters of every layer + the output head d x V)
         + 4 x layers x query heads x head size x c   (q.k and p.v)
Per step over tokens with contexts c_i, in bytes of the served type:
  weights once (every parameter; the tied table counts once), keys and
  values read over each token's context, keys and values written for each
  token, float32 logits written for each token.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from chipbench.weights import dims, spec

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
LOGIT_BYTES = 4


def parameter_count(m: Dict) -> int:
    total = 0
    for _, shape, _, _ in spec(m):
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def matmul_params_per_token(m: Dict) -> int:
    """Parameters a token multiplies through: every layer's matrices and
    the output head (the embedding lookup is a gather, not a product)."""
    k = dims(m)
    q, kv = k["nq"] * k["dh"], k["nkv"] * k["dh"]
    per_layer = k["d"] * (q + 2 * kv) + q * k["d"] + 3 * k["d"] * k["ff"]
    return k["L"] * per_layer + k["d"] * k["V"]


def token_flops(m: Dict, context: int) -> float:
    k = dims(m)
    return (2.0 * matmul_params_per_token(m)
            + 4.0 * k["L"] * k["nq"] * k["dh"] * context)


def kv_bytes_per_position(m: Dict) -> int:
    k = dims(m)
    return 2 * k["L"] * k["nkv"] * k["dh"] * DTYPE_BYTES[m["torch_dtype"]]


def step_work(m: Dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over tokens at ``contexts``."""
    contexts = list(contexts)
    if not contexts:
        return 0.0, 0.0
    width = DTYPE_BYTES[m["torch_dtype"]]
    kv = kv_bytes_per_position(m)
    flops = sum(token_flops(m, c) for c in contexts)
    nbytes = (parameter_count(m) * width
              + kv * sum(contexts)                  # read, own position too
              + kv * len(contexts)                  # written
              + LOGIT_BYTES * dims(m)["V"] * len(contexts))
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """Least seconds the chip needs, and which bound sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
