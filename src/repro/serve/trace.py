"""The serving program's own names in a profiler trace.

``span`` opens a host span (``jax.profiler.TraceAnnotation``), which the
profiler records beside the device's operations; while no profiler runs it
costs one check.  The
engine gives every span ``device=<chip id>``, so that a reduction can key
it to the chip whose work it drives.  ``SCOPES`` are the
``jax.named_scope`` names of the decode program's cache and attention
work, and ``MOVE_SCOPE`` that of the programs that read and write one
slot for a move: they reach the op-name metadata of the compiled
instructions, whose names the device trace's operations carry.
"""

from __future__ import annotations

import jax

from repro.models.attention import ATTN_SCOPE, KV_SCOPE

#: Host spans of ``ServeEngine.step``: the whole step, then its phases.
#: ``serve.admit`` opens only on steps that give a request a slot.
#: ``serve.feed`` builds the next step's inputs and sends them;
#: ``serve.launch`` dispatches it, while the step before is still in
#: flight; ``serve.wait`` then waits for that earlier step and reads its
#: tokens, and ``serve.emit`` gives them to their requests.  On a call
#: with no step in flight, feed and launch cover two steps back to back,
#: and wait reads the first.  ``serve.launch`` also carries ``live`` (the
#: slots fed a token), ``kv_positions`` (the positions decode attention
#: reads: each live slot's length, rounded up to the kernel's copy
#: block), both summed over the steps it dispatches, and ``ahead`` (1
#: when it dispatched while a step was in flight).  ``serve.emit`` also
#: carries ``discarded`` (tokens of the step dropped because their slot
#: had stopped on ``eos_id`` or lost its request since the launch).
#: ``serve.export`` and ``serve.import`` are a session's move
#: (``export_slot``, ``import_slot``); they also carry ``bytes`` (the
#: payload's size) and ``positions`` (the slot's write offset).
SPANS = ("serve.step", "serve.admit", "serve.feed", "serve.launch",
         "serve.wait", "serve.emit", "serve.export", "serve.import")

#: Device scopes: everything the KV cache costs inside the layer scan, and
#: the decode attention math.
SCOPES = (KV_SCOPE, ATTN_SCOPE)

#: Device scope of the slot read and write programs a move runs; apart
#: from ``SCOPES``, which name the decode program's work only.
MOVE_SCOPE = "serve_move"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **args)
