"""Training/serving substrate tests: optimizers, loss behavior, data
determinism, serve engine with continuous batching."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import ByteTokenizer, DataConfig, Prefetcher, SyntheticLM
from repro.models import init_lm, reduced
from repro.serve import Request, ServeEngine
from repro.train import (
    adafactor,
    adam8bit,
    adamw,
    cosine_schedule,
    init_state,
    make_optimizer,
    make_train_step,
)
from repro.train.trainer import TrainerConfig, make_synthetic_trainer

KEY = jax.random.PRNGKey(0)


class TestOptimizers:
    def _quad_problem(self, opt, steps=200):
        """Minimize ||x - t||² for a (8,256) matrix param."""
        t = jax.random.normal(KEY, (8, 256))
        params = {"w": {"x": jnp.zeros((8, 256))}}

        def loss_fn(p):
            return jnp.mean(jnp.square(p["w"]["x"] - t))

        state = opt.init(params)
        step = jax.jit(lambda p, s: opt.update(jax.grad(loss_fn)(p), s, p))
        for _ in range(steps):
            params, state = step(params, state)
        return float(loss_fn(params))

    @pytest.mark.parametrize("name", ["adamw", "adafactor", "adam8bit"])
    def test_converges_on_quadratic(self, name):
        opt = make_optimizer(name, lr=0.05, warmup=5, total_steps=200,
                             **({"weight_decay": 0.0} if name != "adafactor" else {}))
        final = self._quad_problem(opt)
        assert final < 0.02, f"{name} stalled at {final}"

    def test_cosine_schedule(self):
        lr = cosine_schedule(1e-3, warmup=10, total=100)
        assert float(lr(jnp.array(0))) < 1e-3 * 0.2
        assert float(lr(jnp.array(10))) == pytest.approx(1e-3, rel=0.02)
        assert float(lr(jnp.array(100))) == pytest.approx(1e-4, rel=0.05)

    def test_adafactor_factored_state_is_small(self):
        opt = make_optimizer("adafactor")
        params = {"w": jnp.zeros((1024, 4096))}
        st = opt.init(params)
        n_state = sum(x.size for x in jax.tree.leaves(st["stats"]))
        assert n_state < params["w"].size * 0.01  # ≪ full second moment

    def test_adam8bit_state_bytes(self):
        opt = make_optimizer("adam8bit")
        params = {"w": jnp.zeros((512, 512))}
        st = opt.init(params)
        nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st["q"]))
        full = params["w"].size * 8  # fp32 m+v
        assert nbytes < full * 0.35


class TestTrainingLoss:
    def test_loss_decreases_on_learnable_data(self):
        cfg = reduced(get_config("granite-3-2b"), vocab_size=64)
        tcfg = TrainerConfig(steps=30, log_every=1000, ckpt_dir=None)
        trainer = make_synthetic_trainer(cfg, tcfg, global_batch=8, seq_len=64)
        trainer.run()
        first = np.mean([m["loss"] for m in trainer.metrics_log[:5]])
        last = np.mean([m["loss"] for m in trainer.metrics_log[-5:]])
        assert last < first - 0.2, f"no learning: {first:.3f} → {last:.3f}"

    @pytest.mark.slow
    def test_microbatched_grads_match_full(self):
        cfg = reduced(get_config("granite-3-2b"))
        opt = make_optimizer("adamw", lr=1e-3)
        step1 = jax.jit(make_train_step(cfg, opt, n_microbatch=1))
        step4 = jax.jit(make_train_step(cfg, opt, n_microbatch=4))
        state = init_state(KEY, cfg, opt)
        batch = {
            "inputs": jax.random.randint(KEY, (8, 32), 0, cfg.vocab_size),
            "targets": jax.random.randint(KEY, (8, 32), 0, cfg.vocab_size),
        }
        s1, m1 = step1(state, batch)
        s2, m2 = step4(init_state(KEY, cfg, opt), batch)
        np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                                   rtol=1e-3)
        l1 = jax.tree.leaves(s1["params"])[0]
        l2 = jax.tree.leaves(s2["params"])[0]
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32), atol=1e-5)

    def test_loss_chunking_equivalence(self):
        cfg = reduced(get_config("granite-3-2b"))
        from repro.models import lm_loss
        params = init_lm(KEY, cfg)
        batch = {
            "inputs": jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size),
            "targets": jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size),
        }
        l1, _ = lm_loss(params, batch, cfg, loss_chunk=0)
        l2, _ = lm_loss(params, batch, cfg, loss_chunk=16)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


class TestData:
    def test_deterministic_and_step_indexed(self):
        cfg = DataConfig(vocab_size=100, global_batch=4, seq_len=16, seed=7)
        src = SyntheticLM(cfg)
        a = src.batch_at(5)
        b = src.batch_at(5)
        np.testing.assert_array_equal(a["inputs"], b["inputs"])
        c = src.batch_at(6)
        assert not np.array_equal(a["inputs"], c["inputs"])

    def test_host_sharding_disjoint(self):
        full = DataConfig(vocab_size=100, global_batch=8, seq_len=16, seed=1)
        h0 = SyntheticLM(dataclasses.replace(full, n_hosts=2, host_index=0))
        h1 = SyntheticLM(dataclasses.replace(full, n_hosts=2, host_index=1))
        b0, b1 = h0.batch_at(0), h1.batch_at(0)
        assert b0["inputs"].shape[0] == 4
        assert not np.array_equal(b0["inputs"], b1["inputs"])

    def test_prefetcher(self):
        cfg = DataConfig(vocab_size=100, global_batch=2, seq_len=8)
        it = Prefetcher(SyntheticLM(cfg), depth=2)
        batches = [next(it) for _ in range(5)]
        assert len(batches) == 5
        it.close()

    def test_byte_tokenizer_roundtrip(self):
        tok = ByteTokenizer()
        s = "hello, 世界!"
        assert tok.decode(tok.encode(s)) == s


class TestServeEngine:
    def test_continuous_batching_completes_all(self):
        cfg = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
        params = init_lm(KEY, cfg)
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, eos_id=-1)
        reqs = [Request(i, prompt=[1 + i, 2, 3], max_new_tokens=5) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_done(max_steps=500)
        assert len(done) == 5
        assert all(len(r.output) == 5 for r in done)

    def test_slot_isolation(self):
        """A request's output must not depend on what shares the batch."""
        cfg = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
        params = init_lm(KEY, cfg)

        def run(prompts):
            eng = ServeEngine(cfg, params, batch_slots=len(prompts),
                              max_len=32, eos_id=-1)
            for i, p in enumerate(prompts):
                eng.submit(Request(i, prompt=p, max_new_tokens=4))
            done = {r.req_id: r.output for r in eng.run_until_done(500)}
            return done

        solo = run([[5, 6, 7]])[0]
        paired = run([[5, 6, 7], [9, 10, 11, 12]])[0]
        assert solo == paired

    def test_params_and_cache_live_on_the_engine_device(self):
        cfg = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
        dev = jax.devices()[-1]
        eng = ServeEngine(cfg, init_lm(KEY, cfg), batch_slots=2, max_len=32,
                          eos_id=-1, device=dev)
        eng.submit(Request(0, prompt=[1, 2], max_new_tokens=3))
        eng.run_until_done(100)
        for leaf in jax.tree.leaves((eng.params, eng.cache)):
            assert leaf.devices() == {dev}


def _eager_reset_slot(cache, slot):
    """The eager per-leaf update `reset_slot` was before it was jitted."""
    out = dict(cache)
    out["index"] = cache["index"].at[slot].set(0)
    out["blocks"] = jax.tree.map(lambda x: x.at[:, slot].set(0), cache["blocks"])
    out["tail"] = jax.tree.map(lambda x: x.at[slot].set(0), cache["tail"])
    if "shared" in cache:
        out["shared"] = jax.tree.map(lambda x: x.at[:, slot].set(0), cache["shared"])
    if "tail_shared" in cache:
        out["tail_shared"] = jax.tree.map(lambda x: x.at[slot].set(0),
                                          cache["tail_shared"])
    return out


def _eager_import_slot(cache, slot, state):
    """The eager per-leaf update `import_slot` was before it was jitted."""
    c = dict(cache)
    c["index"] = cache["index"].at[slot].set(state["index"])
    c["blocks"] = jax.tree.map(lambda x, v: x.at[:, slot].set(v),
                               cache["blocks"], state["blocks"])
    c["tail"] = jax.tree.map(lambda x, v: x.at[slot].set(v),
                             cache["tail"], state["tail"])
    if "shared" in cache:
        c["shared"] = jax.tree.map(lambda x, v: x.at[:, slot].set(v),
                                   cache["shared"], state["shared"])
    if "tail_shared" in cache:
        c["tail_shared"] = jax.tree.map(lambda x, v: x.at[slot].set(v),
                                        cache["tail_shared"], state["tail_shared"])
    return c


class TestSlotUpdates:
    """The jitted, cache-donating slot updates the engine uses give the
    same caches as the eager per-leaf updates they replaced."""

    @staticmethod
    def _random_engine(arch, seed):
        cfg = reduced(get_config(arch), vocab_size=64)
        eng = ServeEngine(cfg, init_lm(KEY, cfg), batch_slots=3, max_len=16)
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
        eng.cache = jax.tree.map(
            lambda x: (jax.random.randint(next(keys), x.shape, 0, 16, x.dtype)
                       if x.dtype == jnp.int32
                       else jax.random.normal(next(keys), x.shape, x.dtype)),
            eng.cache)
        return eng

    @staticmethod
    def _assert_same(a, b):
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-7b", "xlstm-1.3b"])
    def test_reset_slot_matches_eager(self, arch):
        eng = self._random_engine(arch, 1)
        want = _eager_reset_slot(eng.cache, 1)
        eng._admit(1, Request(0, prompt=[1]))
        self._assert_same(eng.cache, want)

    @pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-7b", "xlstm-1.3b"])
    def test_export_import_slot_match_eager(self, arch):
        src, dst = self._random_engine(arch, 2), self._random_engine(arch, 3)
        src.offsets[0] = 7
        state = src.export_slot(0)
        assert state["offset"] == 7
        want_state = {"index": src.cache["index"][0],
                      "blocks": jax.tree.map(lambda x: x[:, 0], src.cache["blocks"]),
                      "tail": jax.tree.map(lambda x: x[0], src.cache["tail"])}
        for key in ("blocks", "tail", "index"):
            self._assert_same(state[key], want_state[key])
        want = _eager_import_slot(dst.cache, 2, state)
        dst.import_slot(2, state)
        self._assert_same(dst.cache, want)
        assert dst.offsets[2] == 7
