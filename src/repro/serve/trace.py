"""The serving program's own names in a profiler trace.

``span`` opens a host span (``jax.profiler.TraceAnnotation``), which the
profiler records beside the device's operations; while no profiler runs it
costs one check.  The
engine gives every span ``device=<chip id>``, so that a reduction can key
it to the chip whose work it drives.  ``SCOPES`` are the
``jax.named_scope`` names of the decode program's cache and attention
work: they reach the op-name metadata of its compiled instructions, whose
names the device trace's operations carry.
"""

from __future__ import annotations

import jax

from repro.models.attention import ATTN_SCOPE, KV_SCOPE

#: Host spans of ``ServeEngine.step``: the whole step, then its phases.
#: ``serve.admit`` opens only on steps that give a request a slot.
SPANS = ("serve.step", "serve.admit", "serve.feed", "serve.launch",
         "serve.wait", "serve.emit")

#: Device scopes: everything the KV cache costs inside the layer scan, and
#: the decode attention math.
SCOPES = (KV_SCOPE, ATTN_SCOPE)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **args)
