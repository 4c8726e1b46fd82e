"""Sharding rules: leaf-path → PartitionSpec, for params, optimizer states,
KV/SSM caches and batches, over the production mesh axes
(("pod",) "data", "model").

Strategy (baseline; the §Perf loop mutates it via `ShardingStrategy`):

  * batch        → all DP axes ("pod" × "data")
  * TP ("model") → attention heads, FFN hidden, vocab, Mamba/xLSTM channels
  * FSDP ("data")→ the d_model dim of every large matrix (ZeRO-3-style; what
                   makes 110B–1T params fit 16 GB chips)
  * EP ("model") → MoE expert dim (DBRX, Kimi)
  * KV caches    → batch over DP, sequence over "model" (and over all axes
                   when batch==1, e.g. long_500k)

Every rule is divisibility-guarded: an axis that does not divide the dim is
dropped (replicated) rather than erroring, so reduced smoke configs work on
1 device with the same code path.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # typing only — avoids a models↔parallel import cycle
    from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShardingStrategy:
    dp: Tuple[str, ...] = ("data",)   # batch axes (("pod","data") multi-pod)
    tp: Optional[str] = "model"
    fsdp: Optional[str] = "data"      # param d_model dim; None → replicate
    ep: Optional[str] = "model"       # expert dim
    seq: Optional[str] = "model"      # cache sequence axis
    moe: str = "auto_spmd"            # auto_spmd | ep_shardmap (§Perf)
    # Logical-name table consumed by the rules below.

    def axis(self, logical: Optional[str]):
        return {
            None: None,
            "dp": self.dp if len(self.dp) > 1 else (self.dp[0] if self.dp else None),
            "tp": self.tp,
            "fsdp": self.fsdp,
            "ep": self.ep,
            "seq": self.seq,
        }[logical]


def default_strategy(mesh: Mesh) -> ShardingStrategy:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return ShardingStrategy(dp=dp)


# --------------------------------------------------------------- rules ----
# (regex on "/"-joined path, logical spec per dim — right-aligned to shape).
_PARAM_RULES = [
    (r"embed/embedding$",            ("tp", "fsdp")),
    (r"unembed/w$",                  ("fsdp", "tp")),
    (r"(attn|cross|shared_attn/attn)/w[qkv]/w$", ("fsdp", "tp")),
    (r"(attn|cross|shared_attn/attn)/w[qkv]/b$", ("tp",)),
    (r"(attn|cross|shared_attn/attn)/wo/w$",     ("tp", "fsdp")),
    (r"(attn|cross|shared_attn/attn)/wo/b$",     (None,)),
    (r"(ffn|shared_attn/ffn)/w_(gate|up)/w$",    ("fsdp", "tp")),
    (r"(ffn|shared_attn/ffn)/w_(gate|up)/b$",    ("tp",)),
    (r"(ffn|shared_attn/ffn)/w_down/w$",         ("tp", "fsdp")),
    (r"(ffn|shared_attn/ffn)/w_down/b$",         (None,)),
    (r"moe/router/w$",               ("fsdp", None)),
    # Experts sharded (E → ep axis, d_model → fsdp).  NOTE (§Perf kimi
    # iteration 2, REVERTED): ff-over-fsdp with partial-output psums looked
    # 4× cheaper but is WRONG under batch-over-fsdp — the psum mixes
    # different data shards' tokens.  Weight gathers are the correct cost;
    # they amortize by lowering n_microbatch (EP makes activations small).
    (r"moe/experts/w_(gate|up)/w$",  ("ep", "fsdp", None)),
    (r"moe/experts/w_down/w$",       ("ep", None, "fsdp")),
    (r"moe/experts/.*/b$",           ("ep", None)),
    (r"mixer/in_proj/w$",            ("fsdp", "tp")),
    (r"mixer/out_proj/w$",           ("tp", "fsdp")),
    (r"mixer/conv_w$",               (None, "tp")),
    (r"mixer/conv_b$",               ("tp",)),
    (r"mixer/(A_log|D|dt_bias)$",    (None,)),
    (r"mixer/norm_scale$",           ("tp",)),
    (r"mixer/(up|down)_proj/w$",     ("fsdp", "tp")),
    (r"mixer/w[qkv]/w$",             ("tp", None, None)),  # block-diag (nb,bs,bs)
    (r"mixer/w_gates/w$",            (None, "tp")),
    (r"mixer/r_gates$",              (None, None, None, None)),
    (r"mixer/w_up/w$",               (None, "tp")),
    (r"mixer/w_down/w$",             ("tp", "fsdp")),
    (r"norm.*/scale$",               (None,)),
    (r"norm.*/bias$",                (None,)),
    (r"final_norm/scale$",           (None,)),
]
# Down-proj of the mLSTM/sLSTM mixers overlaps "mixer/w_down" rule above.

_CACHE_RULES = [
    (r"attn/(k|v)$",          (None, "dp", "seq", None)),         # B,S,Hkv·Dh (+layer)
    (r"cross/(k|v)$",         (None, "dp", "seq", None, None)),   # B,S,Hkv,Dh (+layer)
    (r"mixer/conv$",          ("dp", None, "tp")),
    (r"mixer/state$",         ("dp", "tp", None, None)),          # B,H,P,N
    (r"mixer/C$",             ("dp", "tp", None, None)),
    (r"mixer/(n|m|c|h)$",     ("dp", "tp", None)),
    (r"index$",               ()),
]


def _right_align(logicals: Sequence, rank: int):
    """Pad logical spec with leading Nones to the leaf's rank (handles the
    stacked (n_full,) layer axis and batch dims transparently)."""
    pad = rank - len(logicals)
    return (None,) * pad + tuple(logicals)


def _guarded(spec_axes, shape, mesh: Mesh) -> P:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for dim, ax in zip(shape, spec_axes):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = int(np.prod([sizes[a] for a in axes]))
        out.append(ax if dim % total == 0 and dim > 0 else None)
    return P(*out)


def _match(path: str, rules, strat: ShardingStrategy, shape, mesh: Mesh) -> Optional[P]:
    for pattern, logicals in rules:
        if re.search(pattern, path):
            axes = tuple(strat.axis(l) for l in _right_align(logicals, len(shape)))
            return _guarded(axes, shape, mesh)
    return None


def _tree_specs(tree, mesh, fn) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out.append(NamedSharding(mesh, fn(pstr, leaf)))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------- frontends --
def param_specs(param_shapes, mesh: Mesh, strat: ShardingStrategy):
    def fn(path, leaf):
        spec = _match(path, _PARAM_RULES, strat, leaf.shape, mesh)
        if spec is None:
            spec = P()  # replicate unknowns (scalars, misc)
        return spec
    return _tree_specs(param_shapes, mesh, fn)


def opt_specs(opt_shapes, param_shapes, mesh: Mesh, strat: ShardingStrategy):
    """Optimizer-state shardings derived from the param rules: same-shape
    moments inherit the param spec; Adafactor factored stats drop the
    factored dim; int8 blocks extend the last dim's spec."""
    pspecs = param_specs(param_shapes, mesh, strat)
    pflat = {  # path → (shape, spec)
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path):
            (leaf.shape, spec.spec)
        for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(param_shapes)[0],
            jax.tree_util.tree_leaves(param_specs(param_shapes, mesh, strat),
                                      is_leaf=lambda x: isinstance(x, NamedSharding)))
    }

    def fn(path, leaf):
        # Strip optimizer wrappers to find the owning param path.
        base = re.sub(r"^(m|v|stats|q)/", "", path)
        base = re.sub(r"/(vr|vc|v|m|mq|ms|vq|vs)$", "", base)
        for ppath, (pshape, pspec) in pflat.items():
            if base == ppath:
                spec = tuple(pspec) + (None,) * (len(leaf.shape) - len(pspec))
                if path.endswith("/vr"):          # shape[:-1]
                    spec = tuple(pspec[:-1]) if len(pspec) else ()
                elif path.endswith("/vc"):        # shape[:-2] + shape[-1:]
                    spec = tuple(pspec[:-2]) + tuple(pspec[-1:]) if len(pspec) >= 2 else ()
                elif path.endswith(("/mq", "/ms", "/vq", "/vs")):
                    spec = tuple(pspec[:-1]) + (pspec[-1], None) if len(pspec) else ()
                spec = spec[: len(leaf.shape)]
                spec = spec + (None,) * (len(leaf.shape) - len(spec))
                return _guarded(spec, leaf.shape, mesh)
        return P()
    return _tree_specs(opt_shapes, mesh, fn)


def state_specs(state_shapes, mesh: Mesh, strat: ShardingStrategy):
    return {
        "params": param_specs(state_shapes["params"], mesh, strat),
        "opt": opt_specs(state_shapes["opt"], state_shapes["params"], mesh, strat),
        "step": NamedSharding(mesh, P()),
    }


def batch_specs(batch_shapes, mesh: Mesh, strat: ShardingStrategy):
    dp = strat.axis("dp")

    def fn(path, leaf):
        if path.endswith("positions") and len(leaf.shape) == 3:
            return _guarded((None, dp, None), leaf.shape, mesh)
        spec = (dp,) + (None,) * (len(leaf.shape) - 1)
        return _guarded(spec, leaf.shape, mesh)
    return _tree_specs(batch_shapes, mesh, fn)


def cache_specs(cache_shapes, mesh: Mesh, strat: ShardingStrategy, batch: int):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = strat.dp
    dp_total = int(np.prod([sizes[a] for a in dp_axes]))
    if batch % dp_total:
        # Single-stream decode (long_500k): spread the sequence dim over
        # everything instead of the batch.
        strat = dataclasses.replace(
            strat, dp=(), seq=tuple(dp_axes) + ((strat.tp,) if strat.tp else ()))

    def fn(path, leaf):
        spec = _match(path, _CACHE_RULES, strat, leaf.shape, mesh)
        return spec if spec is not None else P()
    return _tree_specs(cache_shapes, mesh, fn)
