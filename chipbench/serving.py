"""The window: open-loop arrivals into the program's own entries.

Each replica is one ``ServeEngine`` on its own device.  Arrivals are
submitted when due (``ServeEngine.submit``) and every replica is stepped
(``ServeEngine.step``); sessions move between replicas through
``export_slot`` -> ``import_slot`` at step boundaries.  The harness only
reads host clocks around those calls and the engines' own state (slots,
outputs, write offsets).

One replica without moves runs in the calling thread.  Several run one
loop thread each, as a deployment runs one server loop per chip; the
calling thread then submits, routes each arrival to the replica with the
fewest live plus queued requests, and moves a session every
``move_every_s``: from replica (move index mod replicas), the session there
with the most generated tokens, to the other replica with the most free
slots.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.serve import Request, ServeEngine

from chipbench.generator import Arrival

clock = time.perf_counter


@dataclasses.dataclass
class ReqRecord:
    arrival: Arrival
    request: Request
    due: float                       # absolute, on ``clock``
    submit: Optional[float] = None
    admit: Optional[float] = None    # start of the step that gave it a slot
    tokens: List[float] = dataclasses.field(default_factory=list)
    replica: Optional[int] = None
    moved: int = 0


@dataclasses.dataclass(frozen=True)
class StepRecord:
    replica: int
    t0: float
    t1: float
    contexts: tuple                  # positions attended by each token fed


@dataclasses.dataclass
class MoveRecord:
    src: int
    dst: int
    req_id: int
    t_begin: float                   # export_slot called
    t_ready: float                   # destination cache ready
    last_src_token: float
    token_index: int                 # index of the first token on dst
    shipped_bytes: int
    live_positions: int


@dataclasses.dataclass
class Recorder:
    requests: Dict[int, ReqRecord] = dataclasses.field(default_factory=dict)
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    moves: List[MoveRecord] = dataclasses.field(default_factory=list)
    skipped_moves: int = 0


class Annotator:
    """``jax.profiler.TraceAnnotation`` around the program's entries in a
    traced run, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str, **stats):
        if self.on:
            return jax.profiler.TraceAnnotation(name, **stats)
        return contextlib.nullcontext()


class Replica:
    def __init__(self, index: int, engine: ServeEngine, rec: Recorder,
                 note: Annotator):
        self.index, self.engine, self.rec, self.note = index, engine, rec, note
        self.lock = threading.Lock()
        self.hold = threading.Event()     # a move waits for this replica

    def load(self) -> int:
        e = self.engine
        return sum(s is not None for s in e.slots) + len(e.queue)

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.engine.slots) if s is None]

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(s is not None for s in e.slots)

    def step(self) -> None:
        e = self.engine
        before = list(e.slots)
        t0 = clock()
        with self.note("engine.step", replica=self.index):
            e.step()
        t1 = clock()
        with self.note("harness.record", replica=self.index):
            live = [i for i, (b, a) in enumerate(zip(before, e.slots))
                    if b is not None or a is not None]
            self.rec.steps.append(StepRecord(
                self.index, t0, t1, tuple(int(e.offsets[i]) for i in live)))
            for i in live:
                req = before[i] if before[i] is not None else e.slots[i]
                r = self.rec.requests[req.req_id]
                if before[i] is None:
                    r.admit, r.replica = t0, self.index
                r.tokens.extend([t1] * (len(req.output) - len(r.tokens)))


def warm_up(replica: Replica) -> None:
    """Compile and run every program the window drives on this replica's
    device: admission, the decode step, sampling, and a slot's export and
    import."""
    e = replica.engine
    e.submit(Request(-1, [1, 2], max_new_tokens=2))
    e.run_until_done()
    e.import_slot(0, e.export_slot(0))
    jax.block_until_ready(e.cache)
    e.finished.clear()


@dataclasses.dataclass
class Window:
    traffic_start: float
    start: float
    end: float
    stop: float = 0.0                # when the run stopped stepping


class Runner:
    def __init__(self, replicas: Sequence[Replica], arrivals: Sequence[Arrival],
                 mix: Dict, seconds: float, rec: Recorder,
                 edges: Sequence[Tuple[str, float]] = (),
                 on_edge: Callable[[str], None] = lambda edge: None):
        """``on_edge(label)`` is called once the run passes each of
        ``edges``, (label, seconds after the traffic starts), in order."""
        self.replicas = list(replicas)
        self.arrivals = list(arrivals)
        self.mix, self.seconds, self.rec = mix, float(seconds), rec
        self.edges = sorted(edges, key=lambda e: e[1])
        self.on_edge = on_edge
        self.move_every = float(mix.get("move_every_s", 0))
        self.note = replicas[0].note
        self._before_drain = sum(a.phase != "drain" for a in self.arrivals)
        self._due_in_window: List[ReqRecord] = []
        self._next = 0
        self._edges = 0
        self._moves = 0

    # -- bookkeeping shared by both loops
    def _submit_due(self, now: float) -> None:
        while (self._next < len(self.arrivals)
               and self.win.traffic_start + self.arrivals[self._next].due <= now):
            a = self.arrivals[self._next]
            self._next += 1
            req = Request(a.req_id, list(a.prompt), max_new_tokens=a.max_new)
            r = ReqRecord(a, req, self.win.traffic_start + a.due)
            self.rec.requests[a.req_id] = r
            if a.phase == "window":
                self._due_in_window.append(r)
            target = min(self.replicas, key=lambda rep: (rep.load(), rep.index))
            r.submit, r.replica = clock(), target.index
            target.engine.submit(req)

    def _edge(self, now: float) -> None:
        while (self._edges < len(self.edges) and now >= self.win.traffic_start
               + self.edges[self._edges][1]):
            self._edges += 1
            self.on_edge(self.edges[self._edges - 1][0])

    def _finished(self, now: float) -> bool:
        if now >= self.win.end + float(self.mix["drain_s"]):
            return True
        if (now < self.win.end or self._edges < len(self.edges)
                or self._next < self._before_drain):
            return False
        return all(r.tokens for r in self._due_in_window)

    def _next_due(self) -> float:
        if self._next < len(self.arrivals):
            return self.win.traffic_start + self.arrivals[self._next].due
        return float("inf")

    def run(self) -> Window:
        t = clock()
        warm = float(self.mix["warm_s"])
        self.win = Window(t, t + warm, t + warm + self.seconds)
        if len(self.replicas) == 1 and not self.move_every:
            self._inline()
        else:
            self._threads()
        self.win.stop = clock()
        for label, _ in self.edges[self._edges:]:
            self.on_edge(label)
        return self.win

    # -- one replica in this thread
    def _inline(self) -> None:
        rep = self.replicas[0]
        while True:
            now = clock()
            self._edge(now)
            with self.note("harness.arrivals"):
                self._submit_due(now)
            if self._finished(now):
                return
            if rep.busy():
                rep.step()
            else:
                with self.note("harness.idle"):
                    time.sleep(max(0.0, min(self._next_due() - now, 0.002)))

    # -- one loop thread per replica
    def _threads(self) -> None:
        stop = threading.Event()
        errors: List[BaseException] = []

        def loop(rep: Replica) -> None:
            try:
                while not stop.is_set():
                    if rep.hold.is_set():
                        time.sleep(0.0002)
                        continue
                    with rep.lock:
                        stepped = rep.busy()
                        if stepped:
                            rep.step()
                    if not stepped:
                        with self.note("harness.idle", replica=rep.index):
                            time.sleep(0.001)
            except BaseException as exc:      # reported by the main thread
                errors.append(exc)
                stop.set()

        threads = [threading.Thread(target=loop, args=(rep,), daemon=True,
                                    name=f"replica{rep.index}")
                   for rep in self.replicas]
        for th in threads:
            th.start()
        try:
            next_move = self.win.traffic_start + self.move_every
            while not stop.is_set():
                now = clock()
                self._edge(now)
                with self.note("harness.arrivals"):
                    self._submit_due(now)
                if self._finished(now):
                    break
                if self.move_every and now >= next_move:
                    self.move(self._moves)
                    self._moves += 1
                    next_move += self.move_every
                time.sleep(0.0005)
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=60)
        if errors:
            raise errors[0]
        if any(th.is_alive() for th in threads):
            raise RuntimeError("a replica's loop thread did not stop")

    def move(self, k: int) -> bool:
        """Move one live session off replica ``k mod replicas``; False when
        there is none to move or no free slot to take it."""
        src = self.replicas[k % len(self.replicas)]
        others = [r for r in self.replicas if r is not src]
        dst = max(others, key=lambda r: (len(r.free_slots()), -r.index))
        pair = sorted((src, dst), key=lambda r: r.index)
        for r in pair:
            r.hold.set()
        try:
            with pair[0].lock, pair[1].lock:
                return self._move_locked(src, dst)
        finally:
            for r in pair:
                r.hold.clear()

    def _move_locked(self, src: Replica, dst: Replica) -> bool:
        s, d = src.engine, dst.engine
        live = [i for i, req in enumerate(s.slots)
                if req is not None and req.output]
        free = dst.free_slots()
        if not live or not free:
            self.rec.skipped_moves += 1
            return False
        i = max(live, key=lambda i: (len(s.slots[i].output), -i))
        j = free[0]
        req = s.slots[i]
        r = self.rec.requests[req.req_id]
        t_begin = clock()
        with self.note("move.export", replica=src.index):
            state = s.export_slot(i)
        with self.note("move.import", replica=dst.index):
            d.import_slot(j, state)
            jax.block_until_ready(d.cache)
        t_ready = clock()
        s.slots[i] = None
        d.slots[j] = req
        shipped = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(state) if hasattr(x, "dtype"))
        self.rec.moves.append(MoveRecord(
            src.index, dst.index, req.req_id, t_begin, t_ready, r.tokens[-1],
            len(r.tokens), shipped, int(state["offset"])))
        r.moved += 1
        r.replica = dst.index
        return True
