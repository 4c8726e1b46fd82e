"""Bring-up smoke run on a TPU: the served model and both live-migration
paths, once each, at the published widths of qwen1.5-0.5b (24 layers,
d_model 1024, 16/16 heads, d_ff 2816, vocab 151936, bf16).  Weights and
data come from ``--seed``; nothing is downloaded.

Phases (one chip, the default):

  device      jax.devices()[0] must be a TPU; there is no CPU fallback
  serve       ServeEngine, 16 slots x 2048 tokens, 32 seeded requests
              (prompts of 32-128 tokens, 32 new tokens each) run to the end
  logits      one request's decode-path logits against a float32 full
              forward of the same tokens at highest matmul precision
  kv-ship     export_slot mid-decode, import_slot into a second engine;
              its continuation equals the unmoved one token for token
  train-move  Trainer steps, then LiveElasticBackend save -> reshard ->
              resume through execute_move, and one more step; the restored
              state equals the saved one leaf for leaf

``--chips 4`` runs only the paths that cross chips, each with what it is
compared with: kv-ship from an engine on device 0 to one on device 1, and a
training move from a (4,1) to a (2,1) mesh against a one-chip run.

Every phase raises on failure.  The last line of stdout is the JSON result,
printed only when every phase passed:

    python chip_smoke.py [--chips 4] [--seed 0]

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
``.jax_cache/`` beside this file; checkpoints go to ``.smoke_ckpt/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.cluster import JobSpec, PodSpec, build_fleet_topology  # noqa: E402
from repro.core.migration import Move  # noqa: E402
from repro.core.placement import PlacementEngine  # noqa: E402
from repro.fleet.elastic_bridge import LiveElasticBackend, execute_move  # noqa: E402
from repro.models import ModelConfig, forward, init_lm, logits_fn  # noqa: E402
from repro.parallel.sharding import default_strategy  # noqa: E402
from repro.runtime.elastic import MeshPlan  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.train import make_optimizer  # noqa: E402
from repro.train.trainer import TrainerConfig, make_synthetic_trainer  # noqa: E402

ARCH = "qwen1.5-0.5b"
CACHE_DIR = ROOT / ".jax_cache"
CKPT_DIR = ROOT / ".smoke_ckpt"

# Engine logits (bf16 params, activations and KV cache) against the float32
# reference, as the largest per-position relative L2 error.  bf16 keeps 8
# significant bits (unit roundoff 2^-9); 24 layers of rounded residual adds,
# norms and matmul outputs leave a few per cent, so 0.05 admits that and
# little more.  The same comparison with the weights cut to 4 significant
# bits (16x bf16's roundoff: a computation below the configured precision)
# must fail it, or the tolerance is too loose to mean anything.
LOGITS_TOL = 0.05
CONTROL_MANTISSA_BITS = 3
# Loss on four chips against one chip, relative: the sharded step sums the
# same f32 products in another order, and bf16 parameter updates carry
# that difference forward one rounding (2^-8) at a time.
LOSS_RTOL = 5e-3


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """How much work each phase does (the defaults are the chip run)."""

    slots: int = 16
    max_len: int = 2048
    n_requests: int = 32
    prompt_len: Sequence[int] = (32, 128)
    new_tokens: int = 32
    train_batch: int = 8
    train_seq: int = 512
    train_steps: int = 3
    loss_chunk: int = 128


# ------------------------------------------------------------------ helpers
def configure_compile_cache() -> str:
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; otherwise keep the
    cache at one fixed path in the checkout, so a later run finds it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def init_params(cfg: ModelConfig, seed: int):
    # Eager: one small program per distinct leaf shape.  Jitted whole, the
    # 24 layers' random draws unroll into one program that took 86 s to
    # compile on the chip's host.
    return init_lm(jax.random.PRNGKey(seed), cfg)


def make_requests(cfg: ModelConfig, size: SmokeSize, seed: int, n: int,
                  first_id: int = 0) -> List[Request]:
    """Seeded prompts of ``size.prompt_len`` tokens (ids 1..vocab-1)."""
    rng = np.random.default_rng(seed)
    lo, hi = size.prompt_len
    reqs = []
    for i in range(n):
        prompt = rng.integers(1, cfg.vocab_size, int(rng.integers(lo, hi + 1)))
        reqs.append(Request(first_id + i, prompt.tolist(),
                            max_new_tokens=size.new_tokens))
    return reqs


def max_rel_err(got, want) -> float:
    """Largest per-row relative L2 error ``||got - want|| / ||want||``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


@functools.partial(jax.jit, static_argnums=2)
def _full_forward_logits(params, tokens, cfg: ModelConfig):
    return logits_fn(params, forward(params, tokens, cfg)[0], cfg)


def reference_logits(params, tokens: Sequence[int], cfg: ModelConfig):
    """Full causal forward of one sequence in float32 at highest matmul
    precision: no cache, no kernels, no batching.  (T, vocab)."""
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32",
                                logit_dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return _full_forward_logits(p32, jnp.asarray([tokens], jnp.int32),
                                    cfg32)[0]


def _log(phase: str, **kv) -> None:
    print(f"{phase}: " + ", ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# ------------------------------------------------------------------- phases
def phase_device(chips: int) -> Dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    _log("device", **info)
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {info['platform']!r} devices")
    if len(devs) < chips:
        raise RuntimeError(f"need {chips} chips, JAX found {len(devs)}")
    return info


def phase_serve(engine: ServeEngine, size: SmokeSize, seed: int) -> Dict:
    reqs = make_requests(engine.cfg, size, seed, size.n_requests)
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    engine.step()                      # first step: compiles admit + decode
    first_step_s = time.perf_counter() - t0
    engine.run_until_done()
    jax.block_until_ready(engine.cache)
    wall_s = time.perf_counter() - t0
    done = {r.req_id for r in engine.finished}
    if done != {r.req_id for r in reqs}:
        raise AssertionError(f"serve: {len(done)}/{len(reqs)} requests finished")
    short = [r.req_id for r in reqs if len(r.output) != size.new_tokens]
    if short:
        raise AssertionError(f"serve: requests {short} stopped early")
    out = {"requests": len(reqs), "new_tokens": size.new_tokens * len(reqs),
           "prompt_tokens": sum(len(r.prompt) for r in reqs),
           "steps": engine.steps, "wall_s": wall_s,
           "first_step_s": first_step_s}
    _log("serve", **out)
    return out


def phase_logits(engine: ServeEngine, size: SmokeSize, seed: int) -> Dict:
    """Decode one request alone (its slot's logits at every step, prompt
    and generated tokens alike) and compare with the float32 reference."""
    if any(s is not None for s in engine.slots) or engine.queue:
        raise RuntimeError("logits phase needs an idle engine")
    req = make_requests(engine.cfg, size, seed + 1, 1, first_id=10_000)[0]
    engine.submit(req)
    rows = []
    while not req.done:
        logits = engine.step()
        rows.append(logits[0, 0])      # the only request sits in slot 0
    got = jnp.stack(rows)
    fed = req.prompt + req.output[:-1]
    want = reference_logits(engine.params, fed, engine.cfg)
    err = max_rel_err(got, want)
    coarse = jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=CONTROL_MANTISSA_BITS),
        engine.params)
    control = max_rel_err(got, reference_logits(coarse, fed, engine.cfg))
    out = {"positions": len(fed), "max_rel_err": err, "tol": LOGITS_TOL,
           "control_err": control}
    _log("logits", **out)
    if not np.isfinite(err) or err > LOGITS_TOL:
        raise AssertionError(f"logits: max rel err {err} > {LOGITS_TOL}")
    if control <= LOGITS_TOL:
        raise AssertionError(f"logits: {CONTROL_MANTISSA_BITS}-bit weights "
                             f"pass the tolerance ({control}); it is too loose")
    return out


def phase_kv_ship(src: ServeEngine, dst: ServeEngine, size: SmokeSize,
                  seed: int) -> Dict:
    """Fill ``src``, move one session to ``dst`` (same slot) halfway
    through its generation, and decode both to the end: the moved session
    must continue exactly as the one that stayed.  The session with the
    longest prompt finishes last, so both engines stop stepping its slot
    at the same token and the slot states can be compared too."""
    if any(s is not None for s in src.slots) or src.queue:
        raise RuntimeError("kv-ship phase needs an idle source engine")
    reqs = make_requests(src.cfg, size, seed + 4, len(src.slots),
                         first_id=30_000)
    for r in reqs:
        src.submit(r)
    stay = max(reqs, key=lambda r: len(r.prompt))
    while len(stay.output) < size.new_tokens // 2:
        src.step()
    slot = next(i for i, r in enumerate(src.slots) if r is stay)
    t0 = time.perf_counter()
    state = src.export_slot(slot)
    dst.import_slot(slot, state)
    jax.block_until_ready(dst.cache)
    move_s = time.perf_counter() - t0
    moved = Request(stay.req_id, stay.prompt, stay.max_new_tokens,
                    output=list(stay.output))
    dst.slots[slot] = moved
    at = len(moved.output)
    src.run_until_done()
    dst.run_until_done()
    if moved.output != stay.output:
        raise AssertionError(f"kv-ship: continuation differs after token "
                             f"{at}: {moved.output[at:]} vs {stay.output[at:]}")
    a, b = src.export_slot(slot), dst.export_slot(slot)
    same = all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    if not same:
        raise AssertionError("kv-ship: slot state differs after decoding on")
    out = {"src": str(src.device), "dst": str(dst.device), "moved_at": at,
           "tokens_after": len(moved.output) - at, "move_s": move_s}
    _log("kv-ship", **out)
    return out


def _train(cfg: ModelConfig, size: SmokeSize, seed: int, steps: int,
           plan: MeshPlan, devices, state=None, start_step: int = 0):
    """``Trainer`` steps ``start_step..steps-1`` on ``plan``'s mesh.  Every
    run shares one learning-rate schedule, so moved and unmoved jobs take
    the same steps."""
    mesh = plan.build(devices)
    tcfg = TrainerConfig(steps=steps, log_every=10 ** 9,
                         loss_chunk=size.loss_chunk, seed=seed)
    optimizer = make_optimizer(cfg.optimizer, lr=1e-3, warmup=1,
                               total_steps=size.train_steps + 1)
    trainer = make_synthetic_trainer(cfg, tcfg, size.train_batch,
                                     size.train_seq, mesh=mesh,
                                     strategy=default_strategy(mesh),
                                     optimizer=optimizer)
    state = trainer.run(state=state, start_step=start_step)
    return trainer, state


def _move(src_chips: int, dst_chips: int):
    """A planner move of one training job between two pods."""
    pods = [PodSpec("src", src_chips, 1.0), PodSpec("dst", dst_chips, 1.0)]
    engine = PlacementEngine(build_fleet_topology(pods), all_sites=True)
    req = JobSpec(0, ARCH, "train", chips=dst_chips, step_time_s=1.0,
                  step_slo_s=2.0).request()
    old = next(c for c in engine.enumerate_feasible(req)
               if c.node.site_id == "src")
    engine.commit(req, old)
    new = next(c for c in engine.enumerate_feasible(req)
               if c.node.site_id == "dst")
    return req, Move(0, old, new, 1.0)


def train_losses(cfg: ModelConfig, size: SmokeSize, seed: int) -> List[float]:
    """Losses of ``train_steps + 1`` unmoved steps on one chip."""
    plan = MeshPlan((1, 1), ("data", "model"))
    trainer, _ = _train(cfg, size, seed, size.train_steps + 1, plan,
                        jax.devices()[:1])
    return [m["loss"] for m in trainer.metrics_log]


def phase_train_move(cfg: ModelConfig, size: SmokeSize, seed: int,
                     ckpt_dir: Path, src_chips: int = 1,
                     dst_chips: int = 1) -> Dict:
    """Train on a (src_chips, 1) mesh, move the job through the live
    backend onto (dst_chips, 1), check the restored state leaf for leaf,
    and take one more step there."""
    t0 = time.perf_counter()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    ckpt_dir.mkdir(parents=True)
    try:
        devices = jax.devices()[:src_chips]
        plan = MeshPlan((src_chips, 1), ("data", "model"))
        trainer, state = _train(cfg, size, seed, size.train_steps, plan,
                                devices)
        backend = LiveElasticBackend()
        backend.register_job(0, str(ckpt_dir), cfg, trainer.optimizer, plan,
                             devices=devices)
        backend.update_state(0, state, step=size.train_steps)
        req, mv = _move(src_chips, dst_chips)
        phases = execute_move(backend, req, mv)
        resumed = backend.resumed[0]
        if resumed.step != size.train_steps:
            raise AssertionError(f"train-move: resumed at {resumed.step}")
        if resumed.plan.shape != (dst_chips, 1):
            raise AssertionError(f"train-move: mesh {resumed.plan.shape}")
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(state),
                                jax.tree.leaves(resumed.state)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(f"train-move: restored leaf "
                                     f"{jax.tree_util.keystr(path)} differs")
        del state                      # the restored copy carries on
        after, _ = _train(cfg, size, seed, size.train_steps + 1,
                          resumed.plan, devices, state=resumed.state,
                          start_step=resumed.step)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = ([m["loss"] for m in trainer.metrics_log]
              + [m["loss"] for m in after.metrics_log])
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"train-move: losses {losses}")
    out = {"mesh": f"{plan.shape}->{resumed.plan.shape}", "losses": losses,
           "snapshot_s": phases.snapshot_s, "restore_s": phases.restore_s,
           "ckpt_mbits": phases.mbits, "seconds": time.perf_counter() - t0}
    _log("train-move", **out)
    return out


# --------------------------------------------------------------------- main
def serve_phases(cfg: ModelConfig, size: SmokeSize, seed: int,
                 devices: Sequence) -> None:
    """Serving phases on engines built from seeded weights.  With two
    distinct devices only kv-ship runs, from the first to the second; with
    the same device twice, every serving phase runs on it."""
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    jax.block_until_ready(params)
    _log("init", params=sum(x.size for x in jax.tree.leaves(params)),
         seconds=time.perf_counter() - t0)

    def engine(dev):
        return ServeEngine(cfg, params, size.slots, size.max_len, eos_id=-1,
                           device=dev)

    one_device = devices[0] == devices[1]
    a = engine(devices[0])
    if one_device:
        phase_serve(a, size, seed)
        phase_logits(a, size, seed)
    phase_kv_ship(a, engine(devices[1]), size, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    device = phase_device(args.chips)
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    size = SmokeSize()
    if args.chips == 1:
        serve_phases(cfg, size, args.seed, jax.devices()[:1] * 2)
        phase_train_move(cfg, size, args.seed, CKPT_DIR)
    else:
        serve_phases(cfg, size, args.seed, jax.devices()[:2])
        want = train_losses(cfg, size, args.seed)
        got = phase_train_move(cfg, size, args.seed, CKPT_DIR,
                               src_chips=4, dst_chips=2)["losses"]
        _log("loss-vs-one-chip", one_chip=want, four_to_two=got,
             rtol=LOSS_RTOL)
        np.testing.assert_allclose(got, want, rtol=LOSS_RTOL)
    _log("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
