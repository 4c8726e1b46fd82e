"""What the counts of every architecture share: the bytes of the served
types and the least time the chip needs for counted work.  What one
architecture's decode step needs is counted in its module under
``arch/``, from the tokens it processed and the positions each attended.
"""

from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
LOGIT_BYTES = 4


def least_time(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """Least seconds the chip needs, and which bound sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
