"""Helpers for the benchmark's own tests: a benchmark root built from new
files only, holding a tiny cell that runs on the CPU."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

# A cell small enough for the CPU: a two-layer decoder at bf16 with the
# published configurations' structure (qkv bias or not, grouped heads).
TINY_MODEL = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 256, "vocab_size": 512,
    "hidden_act": "silu", "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "qkv_bias": True, "torch_dtype": "bfloat16",
}
TINY_MIX = {
    "arrivals": {"kind": "poisson"},
    "prompt_len": {"kind": "lognormal", "mean": 8, "sigma": 0.5, "min": 2,
                   "max": 24},
    "output_len": {"kind": "lognormal", "mean": 40, "sigma": 0.5, "min": 4,
                   "max": 96},
    "warm_s": 1.0, "drain_s": 4.0, "replicas": 1, "move_every_s": 0,
}
# The move cell's metrics, as the entries a four-chip move cell brings to
# BENCHMARK.json (their readers are in chipbench/metrics/).
MOVE_METRICS = {
    "end_to_end": [{"name": "move_gap_ms", "unit": "ms", "better": "lower",
                    "bound": 0.1, "source": "host_clock", "workloads": []}],
    "per_layer": [
        {"name": "idle_share.move", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "itl_p95_ms",
         "workloads": []},
        {"name": "move_copy_ms.move", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "migration", "moves": "move_gap_ms",
         "workloads": []},
        {"name": "move_ship_ratio.move", "unit": "ratio", "better": "lower",
         "source": "program_counter", "layer": "migration",
         "moves": "move_gap_ms", "workloads": []}],
}
# Widest served-token gap of the tiny bf16 program against the float32
# reference reads under 0.01 on the CPU; the float8 control reads 0.15 or
# more.  0.05 lies between with room on both sides.
TINY_LIMIT = 0.05


def make_root(tmp_path: Path, cell: str = "tiny.chat", replicas: int = 1,
              rate: float = 6.0, model=None, sample: int = 8) -> Path:
    """A benchmark root holding only new files: BENCHMARK.json with one
    cell, its configuration, mix and cell file, and copies of the metric
    readers, architecture modules and plain references."""
    root = tmp_path / "bench"
    data = root / "chipbench"
    for kind in ("metrics", "arch", "reference"):
        shutil.copytree(ROOT / "chipbench" / kind, data / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": cell, "config": "tiny", "traffic": "mix",
                           "chips": replicas, "why": "CPU test"}]
    moving = replicas > 1
    for key in ("end_to_end", "per_layer"):
        if moving:
            bench[key] += MOVE_METRICS[key]
        for m in bench[key]:
            if "workloads" in m:          # a chat cell's or a move cell's
                chat = m["name"].endswith(".chat")
                m["workloads"] = [cell] if chat != moving else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for kind, name, body in (
            ("configs", "tiny", {"registry": "qwen1.5-0.5b",
                                 "reference": "dense_decoder",
                                 "model": dict(model or TINY_MODEL),
                                 "engine": {"slots": 4, "max_len": 128}}),
            ("traffic", "mix", dict(TINY_MIX, replicas=replicas,
                                    move_every_s=0.25 if moving else 0)),
            ("workloads", cell, {"rate_per_s": rate, "sample": sample,
                                 "limits": {"logit_gap": TINY_LIMIT}})):
        (data / kind).mkdir(parents=True, exist_ok=True)
        (data / kind / f"{name}.json").write_text(json.dumps(body))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
