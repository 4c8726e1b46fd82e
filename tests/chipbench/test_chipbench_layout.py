"""``BENCHMARK.json`` against the rules for a benchmark file, and a cell, a
configuration, a mix and a metric added as new files only."""

import json
import re

import jax
import pytest

from chipbench import arch, run, spec
from conftest import ROOT, make_root
from repro.configs import get_config

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "chipbench.run"]
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


def test_run_seconds_fit_a_full_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries():
    names = set()
    for key, fields in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
                        ("per_layer", {"name", "unit", "better", "source", "layer",
                                       "moves"})):
        for entry in BENCH[key]:
            assert set(entry) - {"workloads"} == fields, entry
            assert NAME.match(entry["name"]), entry["name"]
            assert (key, entry["name"]) not in names
            names.add((key, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in (
                    "lower", "higher")
            for text in ("why", "layer", "source"):
                if text in entry:
                    assert 1 <= len(entry[text]) <= 200
                    assert "\n" not in entry[text] and "\t" not in entry[text]


def test_end_to_end_bounds_and_sources():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    configs = {c["name"] for c in BENCH["configs"]}
    assert configs == {w["config"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        cell = spec.load_cell(w["name"])
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in BENCH["per_layer"]:
            if w["name"] in m.get("workloads", []):
                assert m["moves"] in e2e


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.metric_reader(ROOT, m["name"]).read)
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_names_its_file(entry):
    path = ROOT / entry["file"]
    assert path.parent == ROOT / "chipbench" / "configs"
    config = json.loads(path.read_text())
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]


@pytest.mark.parametrize("path", sorted(
    (ROOT / "chipbench" / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file_is_what_the_program_runs(path):
    config = json.loads(path.read_text())
    for key in config["reduced"]:          # departures, never widths
        assert not re.search(r"(size|_dim|_rank|heads|experts)", key)
        assert key in config["published"]
    model_config = arch.module(config["reference"]).model_config
    assert model_config(config) == get_config(config["registry"])


def test_a_cell_config_mix_and_metric_added_as_new_files_run(tmp_path):
    """Everything the tiny cell needs lives in a temporary root; a metric
    added there as one file and one entry is reported, and an architecture
    added there as one module and one reference, named by the
    configuration, is the one the cell runs."""
    root = make_root(tmp_path, "fresh.cell")
    data = root / "chipbench"
    for kind in ("arch", "reference"):
        (data / kind / "fresh_decoder.py").write_text(
            (ROOT / "chipbench" / kind / "dense_decoder.py").read_text())
    path = data / "configs" / "tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    reference="fresh_decoder")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "served_tokens", "unit": "tokens",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["fresh.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "metrics" / "served_tokens.py").write_text(
        "def read(run):\n"
        "    return sum(len(r.tokens) for r in run.rec.requests.values())\n")
    cell = spec.load_cell("fresh.cell", root)
    assert cell.config["model"]["hidden_size"] == 128
    for kind, mod in (("arch", cell.arch), ("reference", cell.reference)):
        assert mod.__file__ == str(data / kind / "fresh_decoder.py")
    out = run.run_cell(cell, 5, 1.5, False, jax.devices()[:1])
    assert out["correct"]
    assert out["metrics"]["served_tokens"]["value"] > 0
    assert out["metrics"]["served_tokens"]["unit"] == "tokens"


def test_cell_file_that_disagrees_with_the_benchmark_is_refused(tmp_path):
    root = make_root(tmp_path)
    path = root / "chipbench" / "workloads" / "tiny.chat.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), chips=4)))
    with pytest.raises(ValueError, match="chips"):
        spec.load_cell("tiny.chat", root)


@pytest.mark.parametrize("kind", ["arch", "reference"])
def test_architecture_without_a_module_is_refused_at_load(tmp_path, kind):
    root = make_root(tmp_path)
    path = root / "chipbench" / "configs" / "tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    reference="no_such_arch")))
    if kind == "reference":          # the architecture module alone is there
        (root / "chipbench" / "arch" / "no_such_arch.py").write_text(
            (ROOT / "chipbench" / "arch" / "dense_decoder.py").read_text())
    want = root / "chipbench" / kind / "no_such_arch.py"
    with pytest.raises(FileNotFoundError, match=str(want)):
        spec.load_cell("tiny.chat", root)


def test_every_architecture_module_gives_what_the_harness_calls():
    for path in sorted((ROOT / "chipbench" / "arch").glob("[!_]*.py")):
        mod = arch.module(path.stem)
        for name in ("spec", "model_config", "program_params",
                     "parameter_count", "step_work", "session_bytes"):
            assert callable(getattr(mod, name)), (path.stem, name)
        assert (ROOT / "chipbench" / "reference" / path.name).is_file()
