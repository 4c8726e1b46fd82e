"""Seeded weights, by the published tensor names that an architecture
module's ``spec`` gives.

One jitted call makes every tensor on the device from the seed, in the type
the model is served in.  The program and the plain reference are both
handed these tensors; neither makes its own.
"""

from __future__ import annotations

import functools
from types import ModuleType
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (name, shape, std, mean) per tensor.
Spec = Tuple[Tuple[str, Tuple[int, ...], float, float], ...]


def key_data(seed: int) -> np.ndarray:
    """A raw threefry key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, tensors: Spec, dtype: str) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape, std, mean) in enumerate(tensors):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (z * std + mean).astype(dtype)
    return out


def generate(arch: ModuleType, m: Dict, seed: int,
             device) -> Dict[str, jax.Array]:
    """Every tensor of model ``m`` (a config file's ``model``) of the
    architecture ``arch`` on ``device``, in ``m["torch_dtype"]``."""
    key = jax.device_put(key_data(seed), device)
    return _generate(key, arch.spec(m), m["torch_dtype"])
