"""Serving: prefill / decode steps and a batched continuous-batching engine.

`make_prefill_step` / `make_decode_step` are the pjit-able pure functions the
dry-run lowers for the prefill_32k / decode_32k / long_500k cells; the
`ServeEngine` drives them for real requests (examples/serve_lm.py).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import kv_block
from repro.models import ModelConfig, forward, init_cache, logits_fn
from repro.models.layers import dtype_of
from repro.models.transformer import encode, read_slot, reset_slot, write_slot
from repro.serve.trace import MOVE_SCOPE, span


def _move_scoped(fn):
    """``fn`` traced under the move's device scope."""
    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope(MOVE_SCOPE):
            return fn(*args)
    return scoped


# Per-slot cache updates run jitted with the cache donated, so admission and
# kv-ship import rewrite one slot in place instead of copying every leaf.
_read_slot = jax.jit(_move_scoped(read_slot))
_reset_slot = jax.jit(reset_slot, donate_argnums=(0,))
_write_slot = jax.jit(_move_scoped(write_slot), donate_argnums=(0,))


def make_prefill_step(cfg: ModelConfig, max_len: int, cross_len: int = 0):
    """(params, batch) -> (cache, last_token_logits).

    batch: {"tokens": (B,S)} (+ encoder_embeds / vision_embeds / positions).
    The cache is allocated inside (zeros) so the lowered program owns it.
    """

    def prefill(params, batch):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        encoder_out = None
        if cfg.n_encoder_layers:
            encoder_out = encode(params, batch["encoder_embeds"], cfg)
        cache = init_cache(cfg, B, max_len, cross_len=cross_len)
        hidden, cache, _ = forward(
            params, tokens, cfg,
            positions=batch.get("positions"),
            cache=cache,
            encoder_out=encoder_out,
            vision_embeds=batch.get("vision_embeds"),
        )
        return cache, logits_fn(params, hidden[:, -1:], cfg)

    return prefill


#: The token a slot that holds no request is fed.
NO_TOKEN = -1


def make_decode_step(cfg: ModelConfig):
    """(params, cache, tokens (B,1)) -> (cache, logits (B,1,V)).

    With a per-slot cache, a slot fed ``NO_TOKEN`` holds no request:
    attention reads none of its positions and its write offset stays;
    its logits mean nothing."""

    def decode(params, cache, tokens):
        live = tokens[:, 0] >= 0 if cache["index"].ndim else None
        hidden, cache, _ = forward(params, jnp.maximum(tokens, 0), cfg,
                                   cache=cache, live=live)
        return cache, logits_fn(params, hidden, cfg)

    return decode


def sample(logits: jnp.ndarray, key, temperature: float = 0.0) -> jnp.ndarray:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


# ------------------------------------------------------------------ engine
@dataclasses.dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_new_tokens: int = 32
    done: bool = False
    output: List[int] = dataclasses.field(default_factory=list)
    # ``time.perf_counter()`` seconds: given a slot, first token emitted.
    t_admit: Optional[float] = dataclasses.field(default=None, compare=False)
    t_first: Optional[float] = dataclasses.field(default=None, compare=False)


class ServeEngine:
    """Slot-based continuous batching over a fixed decode batch.

    Finished sequences free their slot; queued requests are prefilling into
    freed slots (stop-the-world prefill — adequate for the example driver;
    the scheduler-level placement of *engines* is what the paper's technique
    manages, see `core.cluster`).

    Params and cache live on ``device`` (default ``jax.devices()[0]``); the
    decode step donates the cache, so only one copy of it is ever live."""

    def __init__(self, cfg: ModelConfig, params, batch_slots: int, max_len: int,
                 eos_id: int = 0, temperature: float = 0.0, rng_seed: int = 0,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.cache = jax.jit(
            lambda: init_cache(cfg, batch_slots, max_len, per_slot_index=True),
            out_shardings=jax.sharding.SingleDeviceSharding(self.device))()
        # Per-slot write offsets (slot-local KV positions).
        self.offsets = np.zeros(batch_slots, np.int32)
        self._decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        self._slot_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(
                jax.eval_shape(read_slot, self.cache, 0)))
        # Positions decode attention copies at a time (`kv_block`).
        self._kv_block = kv_block(max_len, cfg.n_kv_heads * cfg.d_head
                                  * np.dtype(dtype_of(cfg.compute_dtype)).itemsize)
        self._base_key = jax.random.PRNGKey(rng_seed)
        self.steps = 0

    def _request_key(self, req: Request):
        """Sampling key for ``req``'s next token: derived from (req_id,
        tokens generated so far), never from batch position or step count —
        so a sampled decode replays identically whatever other requests
        share the batch, and a request resumed on another engine (same
        ``rng_seed``) continues the same stream."""
        return jax.random.fold_in(
            jax.random.fold_in(self._base_key, req.req_id), len(req.output))

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    # Slot-level prefill: run the prompt through decode one token at a time
    # into this slot's cache region.  Simple and exactly consistent with
    # decode (per-slot caches share the batched buffers).
    def _admit(self, slot: int, req: Request) -> None:
        self.slots[slot] = req
        self.offsets[slot] = 0
        # Reset the slot's write offset and recurrent states (stale KV is
        # masked by kv_len; SSM/xLSTM states must be zeroed explicitly).
        self.cache = _reset_slot(self.cache, slot)
        req.output = []
        req.t_admit, req.t_first = time.perf_counter(), None

    def _slot_tokens(self) -> np.ndarray:
        toks = np.full((len(self.slots), 1), NO_TOKEN, np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            pos = int(self.offsets[i])
            if pos < len(req.prompt):
                toks[i, 0] = req.prompt[pos]
            else:
                toks[i, 0] = req.output[-1] if req.output else self.eos_id
        return toks

    def step(self) -> Optional[jax.Array]:
        """Admit queued requests into free slots and decode one token for
        every occupied slot.  Returns the step's logits (slots, 1, vocab),
        or None when there was nothing to decode."""
        dev = self.device.id
        with span("serve.step", device=dev):
            free = [i for i, s in enumerate(self.slots) if s is None]
            if free and self.queue:
                with span("serve.admit", device=dev):
                    for i in free[:len(self.queue)]:
                        self._admit(i, self.queue.pop(0))
            if all(s is None for s in self.slots):
                return None
            with span("serve.feed", device=dev):
                host_tokens = self._slot_tokens()
                tokens = jax.device_put(host_tokens, self.device)
            live = host_tokens[:, 0] != NO_TOKEN
            blocks = -(-(self.offsets[live] + 1) // self._kv_block)
            with span("serve.launch", device=dev, live=int(live.sum()),
                      kv_positions=int(blocks.sum()) * self._kv_block):
                self.cache, logits = self._decode(self.params, self.cache,
                                                  tokens)
            self.steps += 1
            with span("serve.wait", device=dev):
                next_tok = self._next_tokens(logits)
            with span("serve.emit", device=dev):
                self._emit(next_tok)
            return logits

    def _next_tokens(self, logits: jax.Array) -> np.ndarray:
        """Each slot's sampled token, on the host: waits for the step."""
        if self.temperature <= 0.0:
            return np.asarray(sample(logits[:, 0], None, 0.0))
        next_tok = np.zeros(len(self.slots), np.int64)
        for i, req in enumerate(self.slots):
            if req is not None:
                next_tok[i] = int(sample(logits[i, 0], self._request_key(req),
                                         self.temperature))
        return next_tok

    def _emit(self, next_tok: np.ndarray) -> None:
        """Advance every live slot; a generating one gets its token, and a
        finished request frees its slot."""
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.offsets[i] += 1
            pos = int(self.offsets[i])
            if pos >= len(req.prompt):  # generating
                req.output.append(int(next_tok[i]))
                if req.t_first is None:
                    req.t_first = time.perf_counter()
                if (len(req.output) >= req.max_new_tokens
                        or int(next_tok[i]) == self.eos_id
                        or pos >= self.max_len - 1):
                    req.done = True
                    self.finished.append(req)
                    self.slots[i] = None

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        while (self.queue or any(self.slots)) and self.steps < max_steps:
            self.step()
        return self.finished

    # ---------------------------------------------------- slot migration --
    # One slot's cache region is a self-contained session state: these two
    # helpers are the engine-level half of the fleet's kv-ship migration
    # strategy (repro.fleet.serving) — export on the source engine, import
    # into any free slot of a destination engine built from the same
    # config/params (on this device or another), and decoding continues
    # bit-identically.
    def export_slot(self, slot: int) -> Dict:
        """Copy out one slot's KV/recurrent state + write offset."""
        offset = int(self.offsets[slot])
        with span("serve.export", device=self.device.id,
                  bytes=self._slot_bytes, positions=offset):
            state = _read_slot(self.cache, slot)
        state["offset"] = offset
        return state

    def import_slot(self, slot: int, state: Dict) -> None:
        """Install an `export_slot` payload into ``slot`` (overwrites it).
        The payload may come from an engine on another device; it is
        copied onto this engine's device first."""
        arrays = {k: v for k, v in state.items() if k != "offset"}
        with span("serve.import", device=self.device.id,
                  bytes=sum(x.nbytes for x in jax.tree.leaves(arrays)),
                  positions=int(state["offset"])):
            self.cache = _write_slot(self.cache, slot,
                                     jax.device_put(arrays, self.device))
        self.offsets[slot] = state["offset"]
