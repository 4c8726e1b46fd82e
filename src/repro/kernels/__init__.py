"""Pallas TPU kernels for the model's hot spots: decode attention over the
stacked KV cache (`decode_attention.py`) and the Mamba2 chunked scan
(`ssm_scan.py`).

Each kernel is a ``pl.pallas_call`` with explicit VMEM tiling; `ops.py`
holds the entry points the model calls and `ref.py` the pure-jnp oracles
that tests/test_kernels.py checks them against.
"""
