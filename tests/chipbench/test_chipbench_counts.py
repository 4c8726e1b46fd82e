"""FLOP and byte counts against hand counts and against the numbers the
counts read before they moved into the architecture module, and the table
of peaks."""

import json

import pytest

from chipbench import counts, peaks
from chipbench.arch import dense_decoder as dense
from conftest import ROOT

# d 8, 2 layers, 2 query heads of 4, 1 kv head, ff 16, vocab 10, bf16.
SMALL = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
         "tie_word_embeddings": True, "qkv_bias": True,
         "torch_dtype": "bfloat16"}


def test_parameter_count_by_hand():
    per_layer = (8 * 8 + 8 * 4 + 8 * 4      # q, k, v projections
                 + 8 + 4 + 4                # their biases
                 + 8 * 8                    # o projection
                 + 3 * 8 * 16               # gate, up, down
                 + 2 * 8)                   # two norms
    assert dense.parameter_count(SMALL) == 2 * per_layer + 10 * 8 + 8


def test_token_flops_by_hand():
    matmul = 2 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16) + 8 * 10
    assert dense.matmul_params_per_token(SMALL) == matmul
    # q.k and p.v: 2 FLOPs each per head dim, per head, per position.
    assert dense.token_flops(SMALL, 5) == 2 * matmul + 4 * 2 * 2 * 4 * 5


def test_step_bytes_by_hand():
    kv = 2 * 2 * 1 * 4 * 2                   # k and v, layers, heads, dim, bf16
    assert dense.kv_bytes_per_position(SMALL) == kv
    flops, nbytes = dense.step_work(SMALL, [3, 7])
    assert flops == dense.token_flops(SMALL, 3) + dense.token_flops(SMALL, 7)
    want = (dense.parameter_count(SMALL) * 2 + kv * (3 + 7) + kv * 2
            + 4 * 10 * 2)
    assert nbytes == want
    assert dense.step_work(SMALL, []) == (0.0, 0.0)


def test_session_bytes_by_hand():
    assert dense.session_bytes(SMALL, 3) == 3 * 2 * 2 * 1 * 4 * 2
    assert dense.session_bytes(SMALL, 0) == 0


# The configurations at their published sizes: parameters, (FLOPs, bytes)
# of a step over tokens at contexts 1, 70, 285 and 2048, and one session's
# bytes at 300 positions, as counted before the counts moved into the
# architecture module.
FULL = {
    "granite-3-2b": (2533531648, (21054668800.0, 5265113136.0), 24576000),
    "qwen1.5-0.5b": (463987712, (3947233280.0, 1167122432.0), 29491200),
}


@pytest.mark.parametrize("name", sorted(FULL))
def test_full_size_counts_read_as_before(name):
    m = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                   .read_text())["model"]
    params, work, session = FULL[name]
    assert dense.parameter_count(m) == params
    assert dense.step_work(m, [1, 70, 285, 2048]) == work
    assert dense.session_bytes(m, 300) == session


def test_least_time_names_its_bound():
    peak = peaks.peaks("TPU v5 lite")
    t, bound = counts.least_time(1e9, 819e9, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
    t, bound = counts.least_time(197e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
