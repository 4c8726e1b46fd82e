"""The move cell's path on the CPU: sessions moved between replicas by
kv-ship are compared after their move, a move whose exchange is left out
makes the run incorrect, and four replicas on four virtual devices run
a whole cell in a child process."""

import json
import os
import subprocess
import sys
import textwrap

import jax

from chipbench import run, spec
from conftest import ROOT, make_root
from repro.serve.engine import ServeEngine

SEED = 2 ** 31 + 23


def _run(tmp_path, replicas=2, traced=False):
    cell = spec.load_cell("tiny.move",
                          make_root(tmp_path, "tiny.move", replicas, rate=8.0))
    devices = jax.devices()[:1] * replicas          # replicas share the CPU
    return run.run_cell(cell, SEED, 3.0, traced, devices)


def test_moved_sessions_are_compared(tmp_path):
    out = _run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["checks"]["moved_compared"]["value"] >= 1
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "move_gap_ms",
                                   "setup_s"}


def test_move_without_its_exchange_is_not_correct(tmp_path, monkeypatch):
    def import_nothing(self, slot, state):
        self.offsets[slot] = state["offset"]       # the payload never lands
    monkeypatch.setattr(ServeEngine, "import_slot", import_nothing)
    out = _run(tmp_path)
    assert out["checks"]["moved_compared"]["value"] >= 1
    assert not out["correct"]


_FOUR = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src", sys.argv[2] + "/tests/chipbench"]
    import jax
    from conftest import make_root
    from chipbench import run, spec
    assert len(jax.devices()) == 4
    root = make_root(Path(sys.argv[1]), "tiny.move", 4, rate=12.0)
    out = run.run_cell(spec.load_cell("tiny.move", root), 2 ** 31 + 29, 3.0,
                       True, jax.devices())
    print(json.dumps(out))
""")


def test_four_replicas_on_four_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR, str(tmp_path),
                           str(ROOT)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert {"idle_share.move", "move_copy_ms.move",
            "move_ship_ratio.move"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
