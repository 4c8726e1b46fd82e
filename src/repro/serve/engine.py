"""Serving: prefill / decode steps and a batched continuous-batching engine.

`make_prefill_step` / `make_decode_step` are the pjit-able pure functions the
dry-run lowers for the prefill_32k / decode_32k / long_500k cells; the
`ServeEngine` drives them for real requests (examples/serve_lm.py).

The engine keeps one decode step in flight: each `ServeEngine.step` call
dispatches step n+1 before it reads step n's tokens.  `make_serve_step`
wraps the decode step with the token choice and the sampling, so a slot
that is generating is fed step n's sampled token without it leaving the
device; the host reads each step's tokens once, one step behind.
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


def request_key(base_key, req_id, generated):
    """Sampling key of a request's next token: derived from (req_id,
    tokens generated so far), never from batch position or step count —
    so a sampled decode replays identically whatever other requests
    share the batch, and a request resumed on another engine (same
    ``rng_seed``) continues the same stream."""
    return jax.random.fold_in(jax.random.fold_in(base_key, req_id), generated)


def sample(logits: jnp.ndarray, keys=None,
           temperature: float = 0.0) -> jnp.ndarray:
    """Each row's next token of ``logits`` (rows, vocab): the best at
    temperature 0, else drawn with that row's key (``keys``, one a row)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.vmap(lambda key, row: jax.random.categorical(
        key, row / temperature, axis=-1))(keys, logits)


#: Rows of the array `make_serve_step` is fed each step, one column a slot.
FEED_TOKEN, FEED_FROM_DEVICE, FEED_REQ_ID, FEED_GENERATED = range(4)


def make_serve_step(cfg: ModelConfig, temperature: float = 0.0,
                    rng_seed: int = 0):
    """(params, cache, feed (4,B) int32, prev (B,) int32) ->
    (cache, logits (B,1,V), next (B,) int32): `make_decode_step` with the
    tokens chosen and sampled on the device.

    A slot is fed ``prev[b]`` (the previous step's ``next``) where
    ``feed[FEED_FROM_DEVICE, b]`` is set, else ``feed[FEED_TOKEN, b]``
    (``NO_TOKEN`` for a slot that holds no request).  ``next`` is each
    slot's sampled token, its key folded from ``feed[FEED_REQ_ID]`` and
    ``feed[FEED_GENERATED]`` as `request_key` folds it."""
    decode = make_decode_step(cfg)
    base_key = jax.random.PRNGKey(rng_seed)

    def serve(params, cache, feed, prev):
        tokens = jnp.where(feed[FEED_FROM_DEVICE] > 0, prev, feed[FEED_TOKEN])
        cache, logits = decode(params, cache, tokens[:, None])
        keys = None
        if temperature > 0.0:
            keys = jax.vmap(functools.partial(request_key, base_key))(
                feed[FEED_REQ_ID], feed[FEED_GENERATED])
        return cache, logits, sample(logits[:, 0], keys,
                                     temperature).astype(jnp.int32)

    return serve


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


@dataclasses.dataclass
class _Step:
    """One launched decode step, until its tokens are emitted."""
    feed: np.ndarray                 # (4, B) int32, as `make_serve_step` takes it
    reqs: List[Optional[Request]]    # the request each slot was fed for
    gen: np.ndarray                  # (B,) bool: the slot's token is generated
    pos: np.ndarray                  # (B,) the position each slot writes
    fed: Optional[np.ndarray] = None     # (B,) bool: ``reqs[b]`` is set
    logits: Optional[jax.Array] = None
    tokens: Optional[jax.Array] = None   # (B,) int32, on the device
    host: Optional[np.ndarray] = None    # ``tokens`` once read


class ServeEngine:
    """Slot-based continuous batching over a fixed decode batch.

    Finished sequences free their slot; queued requests take freed slots
    and feed their prompts through the decode step, one token a step
    beside the slots that generate (the scheduler-level placement of
    *engines* is what the paper's technique manages, see `core.cluster`).

    One step stays in flight: `step` dispatches the next decode before it
    reads the tokens of the one before, and a generating slot is fed its
    previous token on the device.  `export_slot` first brings its slot
    level with the device, and `run_until_done` returns with nothing in
    flight.

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
        # Per-slot write offsets (slot-local KV positions) of every step
        # launched, the one in flight included.
        self.offsets = np.zeros(batch_slots, np.int32)
        self._decode = jax.jit(make_serve_step(cfg, temperature, rng_seed),
                               donate_argnums=(1,))
        self._no_tokens = jax.device_put(np.zeros(batch_slots, np.int32),
                                         self.device)
        self._flight: Optional[_Step] = None
        self._slot_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(
                jax.eval_shape(read_slot, self.cache, 0)))
        # Positions decode attention copies at a time (`kv_block`).
        self._kv_block = kv_block(max_len, cfg.n_kv_heads * cfg.d_head
                                  * np.dtype(dtype_of(cfg.compute_dtype)).itemsize)
        self.steps = 0

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

    def _ends(self, req: Request, generated: int, written: int) -> bool:
        """Whether ``req`` stops by length with ``generated`` tokens and
        ``written`` positions: known before the last token is read."""
        return (generated >= req.max_new_tokens
                or written >= self.max_len - 1)

    def _plan(self, prev: Optional[_Step]) -> Optional[_Step]:
        """The step after ``prev``: what each slot is fed, with the fed
        slots' offsets advanced; None when no slot is fed.

        A slot whose token of ``prev`` is a generated one, not yet read, is
        fed it on the device, unless the request ends with that token by
        length.  Every other slot that holds a request is fed from the
        host: a prompt token, or its last token when no step in flight
        holds it (a slot just imported, or read by `export_slot`)."""
        n = len(self.slots)
        feed = np.zeros((4, n), np.int32)
        feed[FEED_TOKEN] = NO_TOKEN
        step = _Step(feed, [None] * n, np.zeros(n, bool), self.offsets.copy())
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if req.done:              # ended while a move read its last token
                self.slots[i] = None
                continue
            p = int(step.pos[i])
            pending = prev is not None and prev.reqs[i] is req and prev.gen[i]
            generated = len(req.output) + pending
            if pending and self._ends(req, generated, p):
                continue
            if p < len(req.prompt):
                feed[FEED_TOKEN, i] = req.prompt[p]
            elif pending:
                feed[FEED_FROM_DEVICE, i] = 1
            else:
                feed[FEED_TOKEN, i] = req.output[-1] if req.output else self.eos_id
            feed[FEED_REQ_ID, i], feed[FEED_GENERATED, i] = req.req_id, generated
            step.reqs[i], step.gen[i] = req, p >= len(req.prompt) - 1
        step.fed = np.array([r is not None for r in step.reqs])
        if not step.fed.any():
            return None
        self.offsets[step.fed] += 1
        return step

    @staticmethod
    def _read(step: _Step) -> np.ndarray:
        """``step``'s tokens on the host: waits for the step, once."""
        if step.host is None:
            step.host = np.asarray(step.tokens)
        return step.host

    def step(self) -> Optional[jax.Array]:
        """Admit queued requests into free slots, launch the next decode
        step, then read and emit the step launched before it.  Returns the
        emitted step's logits (slots, 1, vocab), or None when nothing was
        held or in flight.

        Each call emits exactly one step's tokens.  With a step already in
        flight (n), the call launches n+1, then waits for n; with none, it
        launches n and n+1 back to back and waits for n.  A slot that ends
        by length at n is not fed at n+1; one that stops on ``eos_id`` is
        learned at n's emit, and its token of n+1 is discarded.  Tokens go
        to the request each slot held when the step was launched."""
        dev = self.device.id
        with span("serve.step", device=dev):
            free = [i for i, s in enumerate(self.slots) if s is None]
            if free and self.queue:
                with span("serve.admit", device=dev):
                    for i in free[:len(self.queue)]:
                        self._admit(i, self.queue.pop(0))
            prev = self._flight
            if prev is None and all(s is None for s in self.slots):
                return None
            with span("serve.feed", device=dev):
                launch = []
                nxt = self._plan(prev)
                if nxt is not None:
                    launch.append(nxt)
                    if prev is None:
                        after = self._plan(nxt)
                        if after is not None:
                            launch.append(after)
                feeds = jax.device_put([s.feed for s in launch], self.device)
            blocks = sum(int((-(-(s.pos[s.fed] + 1) // self._kv_block)).sum())
                         for s in launch)
            with span("serve.launch", device=dev,
                      live=sum(int(s.fed.sum()) for s in launch),
                      kv_positions=blocks * self._kv_block,
                      ahead=int(prev is not None and bool(launch))):
                last = prev
                for s, feed in zip(launch, feeds):
                    self.cache, s.logits, s.tokens = self._decode(
                        self.params, self.cache, feed,
                        last.tokens if last is not None else self._no_tokens)
                    last = s
            steps = ([prev] if prev is not None else []) + launch
            if not steps:
                return None
            done = steps[0]
            self._flight = steps[1] if len(steps) > 1 else None
            with span("serve.wait", device=dev):
                tokens = self._read(done)
            with span("serve.emit", device=dev) as emit:
                emit.set_metadata(discarded=self._emit(done, tokens))
            self.steps += 1
            return done.logits

    def _give(self, slot: int, req: Request, token: int, written: int) -> None:
        """Append ``req``'s next token; a finished request frees ``slot``."""
        req.output.append(token)
        if req.t_first is None:
            req.t_first = time.perf_counter()
        if token == self.eos_id or self._ends(req, len(req.output), written):
            req.done = True
            self.finished.append(req)
            self.slots[slot] = None

    def _emit(self, step: _Step, tokens: np.ndarray) -> int:
        """Give each generated token of ``step`` to its request; returns
        the tokens discarded because their slot had stopped or moved."""
        discarded = 0
        for i, req in enumerate(step.reqs):
            if req is None or not step.gen[i]:
                continue
            if req.done or self.slots[i] is not req:
                discarded += 1
                continue
            self._give(i, req, int(tokens[i]), int(step.pos[i]) + 1)
        return discarded

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        """Step until nothing is queued, held or in flight, or for
        ``max_steps``; a step still in flight is then read and emitted, so
        every request holds the tokens of every step launched for it."""
        while ((self.queue or any(s is not None for s in self.slots)
                or self._flight is not None) and self.steps < max_steps):
            self.step()
        last, self._flight = self._flight, None
        if last is not None:
            self._emit(last, self._read(last))
            self.steps += 1
        return self.finished

    # ---------------------------------------------------- slot migration --
    # One slot's cache region is a self-contained session state: these two
    # helpers are the engine-level half of the fleet's kv-ship migration
    # strategy (repro.fleet.serving) — export on the source engine, import
    # into any free slot of a destination engine built from the same
    # config/params (on this device or another), and decoding continues
    # bit-identically.
    def export_slot(self, slot: int) -> Dict:
        """Copy out one slot's KV/recurrent state + write offset.

        The slot is first brought level with the device: if the step in
        flight generates a token for the request the slot holds, the call
        waits for that step and gives the token to the request, so that
        the offset and the state agree with the tokens it holds; that
        step's emit then skips the slot."""
        offset = int(self.offsets[slot])
        with span("serve.export", device=self.device.id,
                  bytes=self._slot_bytes, positions=offset):
            step, req = self._flight, self.slots[slot]
            if (step is not None and req is not None and not req.done
                    and step.reqs[slot] is req and step.gen[slot]):
                step.reqs[slot] = None
                self._give(slot, req, int(self._read(step)[slot]), offset)
            state = _read_slot(self.cache, slot)
        state["offset"] = offset
        return state

    def import_slot(self, slot: int, state: Dict) -> None:
        """Install an `export_slot` payload into ``slot`` (overwrites it).
        The payload may come from an engine on another device; it is
        copied onto this engine's device first.  No step in flight holds
        the session, so its next step feeds its last token from the
        host."""
        arrays = {k: v for k, v in state.items() if k != "offset"}
        with span("serve.import", device=self.device.id,
                  bytes=sum(x.nbytes for x in jax.tree.leaves(arrays)),
                  positions=int(state["offset"])):
            self.cache = _write_slot(self.cache, slot,
                                     jax.device_put(arrays, self.device))
        self.offsets[slot] = state["offset"]
