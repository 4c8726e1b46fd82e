"""The benchmark's one seam to the system under test: the seeded tensors,
laid out by the cell's architecture module, become the program's parameter
tree, and each replica is a ``ServeEngine`` on its own device."""

from __future__ import annotations

from types import ModuleType
from typing import Dict

import jax

from repro.models import ModelConfig, init_lm
from repro.serve import ServeEngine


def params(arch: ModuleType, w: Dict[str, jax.Array],
           cfg: ModelConfig) -> Dict:
    """The program's parameter tree holding the seeded tensors ``w``, as
    ``arch.program_params`` lays them out.  Raises where the program's own
    tree has another structure or another leaf's shape or type."""
    tree = arch.program_params(w, cfg)
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    if jax.tree.structure(want) != jax.tree.structure(tree):
        raise ValueError(f"the program's parameter tree is "
                         f"{jax.tree.structure(want)}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(tree)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: program wants "
                             f"{a.shape} {a.dtype}, got {b.shape} {b.dtype}")
    return tree


def engine(config: Dict, cfg: ModelConfig, params: Dict, device) -> ServeEngine:
    e = config["engine"]
    # eos_id -1: no token ends a request, so outputs keep their drawn length.
    return ServeEngine(cfg, params, int(e["slots"]), int(e["max_len"]),
                       eos_id=-1, device=device)
