"""BENCHMARK.json against the contract, and discovery by name alone."""
import json
import re
import shutil

import pytest

from chipbench_tiny import ROOT
from benchmarks.chip import spec

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = re.compile(r"(hidden_size|intermediate|latent|state|proj|head_dim|"
                    r"_dim$|_rank$|expan|experts_per|n_embd|n_inner)")


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for e in BENCH[section]:
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


def test_metrics_have_units_bounds_and_readers():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports_enough(workload):
    cell = spec.load_cell(workload)
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:        # a per-layer metric moves one the cell has
        assert m["moves"] in e2e
    assert cell.limits["limits"]
    assert cell.chips in (1, 4)
    family = cell.family()
    for name in ("PROGRAM_FAMILY", "layout", "init_stds", "forward",
                 "row_logits", "train_work", "prefill_flops", "decode_work"):
        assert hasattr(family, name), name


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_source_and_departures(entry):
    cfg = spec.load_json(ROOT / entry["file"])
    assert cfg["source"] == entry["source"] and cfg["departures"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    for key, was in cfg["published"].items():
        assert cfg[key] != was            # a listed key really changed
    assert not any(WIDTHS.search(k) for k in entry["reduced"])
    m = cfg["model"]
    if m["family"] == "dense":     # the dense block's widths, as published
        assert m["head_dim"] * m["num_heads"] == m["d_model"]


def test_a_new_traffic_file_is_found_without_edits(tmp_path):
    """A later PR adds a mix by adding a file and an entry; nothing that is
    there changes."""
    copy = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmarks" / "chip", copy / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "internlm2-1.8b-6l.train-seq2k", "config": "internlm2-1.8b-6l",
        "traffic": "train-seq2k", "chips": 1, "why": "longer rows"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    t = json.loads((ROOT / "benchmarks/chip/traffic/train-seq1k.json")
                   .read_text())
    t.update(seq_len=2048, batch=20)
    (copy / "benchmarks/chip/traffic/train-seq2k.json").write_text(
        json.dumps(t))
    (copy / "benchmarks/chip/limits/internlm2-1.8b-6l.train-seq2k.json") \
        .write_text(json.dumps({"limits": {"loss_gap": 1.0}}))
    cell = spec.load_cell("internlm2-1.8b-6l.train-seq2k", root=copy)
    assert cell.traffic["seq_len"] == 2048 and cell.kind == "train"
    assert cell.config["name"] == "internlm2-1.8b-6l"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}


def test_a_new_metric_reader_is_found_by_name(tmp_path):
    copy = tmp_path / "repo"
    (copy / "benchmarks" / "chip" / "metrics").mkdir(parents=True)
    (copy / "benchmarks/chip/metrics/steps.per_s.py").write_text(
        "def read(run):\n    return run.steps / run.window_s\n")
    read = spec.load_reader("steps.per_s", root=copy)

    class Run:
        steps, window_s = 10, 4.0

    assert read(Run()) == 2.5


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")
