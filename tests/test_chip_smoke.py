"""`chip_smoke.py` on the CPU: its phases on a reduced qwen1.5-0.5b in bf16,
its refusal to run without a TPU, and its cross-device paths on four
virtual CPU devices."""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import reduced
from repro.serve import ServeEngine

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
chip_smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

CFG = reduced(get_config(chip_smoke.ARCH), param_dtype="bfloat16",
              compute_dtype="bfloat16")
SIZE = chip_smoke.SmokeSize(slots=4, max_len=128, n_requests=6,
                            prompt_len=(4, 12), new_tokens=8, train_batch=4,
                            train_seq=32, train_steps=2, loss_chunk=0)


@pytest.fixture(scope="module")
def params():
    return chip_smoke.init_params(CFG, 0)


def _engine(params, device=None):
    return ServeEngine(CFG, params, SIZE.slots, SIZE.max_len, eos_id=-1,
                       device=device)


def _run_script(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


class TestPhases:
    def test_serve_finishes_every_request(self, params):
        out = chip_smoke.phase_serve(_engine(params), SIZE, 0)
        assert out["requests"] == SIZE.n_requests
        assert out["new_tokens"] == SIZE.n_requests * SIZE.new_tokens

    def test_logits_match_float32_reference(self, params):
        out = chip_smoke.phase_logits(_engine(params), SIZE, 0)
        assert out["max_rel_err"] <= chip_smoke.LOGITS_TOL < out["control_err"]

    def test_kv_ship_continues_token_for_token(self, params):
        out = chip_smoke.phase_kv_ship(_engine(params), _engine(params),
                                       SIZE, 0)
        # the export also reads the token in flight when the move came
        assert out["moved_at"] == SIZE.new_tokens // 2 + 1
        assert out["tokens_after"] == SIZE.new_tokens - out["moved_at"]

    def test_train_move_restores_saved_state(self, tmp_path):
        ckpt = tmp_path / "ckpt"
        out = chip_smoke.phase_train_move(CFG, SIZE, 0, ckpt)
        assert out["mesh"] == "(1, 1)->(1, 1)"
        assert len(out["losses"]) == SIZE.train_steps + 1
        assert not ckpt.exists()                       # cleaned up
        # The moved job's losses are the unmoved job's.
        np.testing.assert_allclose(out["losses"],
                                   chip_smoke.train_losses(CFG, SIZE, 0),
                                   rtol=chip_smoke.LOSS_RTOL)

    def test_device_phase_refuses_cpu(self):
        with pytest.raises(RuntimeError, match="no TPU"):
            chip_smoke.phase_device(1)


class TestScript:
    def test_exits_nonzero_without_tpu(self):
        proc = _run_script(["chip_smoke.py"], ROOT)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout

    def test_fails_alone_in_a_directory(self, tmp_path):
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        proc = _run_script(["chip_smoke.py"], tmp_path,
                           {"PYTHONPATH": ""})
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout

    def test_compile_cache_dir(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            assert chip_smoke.configure_compile_cache() == "/elsewhere"
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            got = chip_smoke.configure_compile_cache()
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


_FOUR_DEVICES = textwrap.dedent("""
    import importlib.util, sys
    from pathlib import Path
    import jax, numpy as np
    spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
    cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    from repro.configs import get_config
    from repro.models import reduced
    cfg = reduced(get_config(cs.ARCH), param_dtype="bfloat16",
                  compute_dtype="bfloat16")
    size = cs.SmokeSize(slots=4, max_len=128, n_requests=6, prompt_len=(4, 12),
                        new_tokens=8, train_batch=4, train_seq=32,
                        train_steps=2, loss_chunk=0)
    assert len(jax.devices()) == 4
    cs.serve_phases(cfg, size, 0, jax.devices()[:2])
    want = cs.train_losses(cfg, size, 0)
    got = cs.phase_train_move(cfg, size, 0, Path(sys.argv[1]), src_chips=4,
                              dst_chips=2)
    assert got["mesh"] == "(4, 1)->(2, 1)", got["mesh"]
    np.testing.assert_allclose(got["losses"], want, rtol=cs.LOSS_RTOL)
    print("FOUR_DEVICES_OK")
""")


def test_cross_device_paths_on_four_virtual_devices(tmp_path):
    """The ``--chips 4`` paths (kv-ship from device 0 to device 1, a
    (4,1) -> (2,1) training move against a one-device run) on four CPU
    devices, in a child process so the device count does not leak."""
    proc = _run_script(
        ["-c", _FOUR_DEVICES, str(tmp_path / "ckpt")], ROOT,
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FOUR_DEVICES_OK" in proc.stdout
    assert "src=TFRT_CPU_0, dst=TFRT_CPU_1" in proc.stdout
