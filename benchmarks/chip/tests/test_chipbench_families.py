"""A model family is a file of its own (``families/<family>.py``): the dense
family gives the numbers and weights the harness gave before it had the
seam, a new family runs the cells from files under its root alone, and no
shared module names a family's parameters or widths."""
import copy
import hashlib
import json
import shutil

import numpy as np
import pytest

from chipbench_tiny import OFFLINE, ROOT, TRAIN, run, tiny_cell
from test_chipbench_faults_serve import LIMIT as SERVE_LIMIT
from test_chipbench_faults_train import LIMIT as TRAIN_LIMIT
from benchmarks.chip import spec, weights

DENSE = spec.load_family("dense")
# the words of the dense block's layout and equations
DENSE_WORDS = ("blocks/mlp", "wi_gate", "w_q", "d_ff")

# sha256 (first 16 hex digits) of each leaf's bytes, as the harness made
# them before the family seam, for the tiny train cell from seed 2**31 + 5
TINY_INIT = {
    "blocks/attn/w_k": "c44ff94938e4bb8b",
    "blocks/attn/w_o": "ae609f7c1c0a25b0",
    "blocks/attn/w_q": "524f6ca7641964b1",
    "blocks/attn/w_v": "e0747e795bea84be",
    "blocks/ln1": "02722f124d0f1736",
    "blocks/ln2": "02722f124d0f1736",
    "blocks/mlp/wi_gate": "2d5a65c6b4aa50fe",
    "blocks/mlp/wi_up": "f80076dc3e6c2f5d",
    "blocks/mlp/wo": "d7af19475e5a4e0b",
    "embed": "39dbb1cc1169cf8a",
    "final_norm": "2f20cd03c9cd392a",
    "head": "c248b80651398399",
}


def _model(config: str) -> dict:
    return spec.load_json(spec.HERE / "configs" / f"{config}.json")["model"]


def test_training_counts_are_the_harness_numbers():
    """internlm2-1.8b-6l at 40 rows of 1024 tokens, as train-seq1k runs it:
    the numbers the train cell recorded before the seam."""
    assert DENSE.train_work(_model("internlm2-1.8b-6l"), 40, 1024) == {
        "attn_call_flops": 171966464000.0,
        "attn_call_bytes": 505937920.0,
        "model_flops_per_token": 2624790528.0}


@pytest.mark.parametrize("n,flops", [
    (1, 3399155712.0), (37, 125899702272.0), (1020, 3569314037760.0),
    (2048, 7373586432000.0)])
def test_prefill_counts_are_the_harness_numbers(n, flops):
    """internlm2-1.8b's prefill of an n-token prompt: 2 x 1699479552
    weights per token and the causal attention of 24 layers."""
    assert DENSE.prefill_flops(_model("internlm2-1.8b"), n) == flops


@pytest.mark.parametrize("n,first,end,want", [
    (1020, 1, 1, (0.0, 0.0, 0.0)),
    (1020, 1, 5, (804126720.0, 402849792.0, 14399963136.0)),
    (37, 3, 11, (68419584.0, 35782656.0, 27260092416.0)),
    (2048, 250, 256, (2713780224.0, 1358069760.0, 23107534848.0))])
def test_decode_counts_are_the_harness_numbers(n, first, end, want):
    got = DENSE.decode_work(_model("internlm2-1.8b"), n, first, end)
    assert (got["decode_attn_flops"], got["decode_attn_bytes"],
            got["decode_model_flops"]) == want


def test_init_is_the_harness_weights_bit_for_bit():
    c = tiny_cell(TRAIN)
    init = weights.make_init(DENSE, c.config["model"], c.config["init"])
    flat = weights.flatten(init(weights.seed_key(2 ** 31 + 5)))
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in flat.items()}
    assert got == TINY_INIT


def test_shared_modules_name_no_family():
    shared = sorted(spec.HERE.glob("*.py")) + sorted(
        (spec.HERE / "metrics").glob("*.py"))
    for path in shared:
        text = path.read_text()
        assert not [w for w in DENSE_WORDS if w in text], path.name


def _files(root) -> dict:
    return {str(p.relative_to(root)): (p.stat().st_mtime_ns, p.stat().st_size)
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts
            and not any(s.startswith(".") for s in p.relative_to(root).parts)}


@pytest.fixture
def plugin_root(tmp_path, monkeypatch):
    """A fresh checkout that holds a family defined only there (the dense
    file under another name), a tiny configuration of each kind naming it,
    their mixes and limits, and the readers of their metrics."""
    monkeypatch.setenv("REPRO_KV_PAGES", "0")
    root = tmp_path / "repo"
    here = root / spec.HERE.relative_to(spec.ROOT)
    for d in ("configs", "traffic", "limits", "families", "metrics"):
        (here / d).mkdir(parents=True)
    shutil.copy(spec.HERE / "families" / "dense.py",
                here / "families" / "dense_copy.py")
    for name in ("setup_s", "train_tokens_per_s", "output_tokens_per_s"):
        shutil.copy(spec.HERE / "metrics" / f"{name}.py", here / "metrics")
    bench = {"configs": [], "workloads": [], "per_layer": [],
             "end_to_end": [{"name": "setup_s", "unit": "s"}]}
    for cell, metric, limits in (
            (tiny_cell(TRAIN), "train_tokens_per_s", TRAIN_LIMIT),
            (tiny_cell(OFFLINE), "output_tokens_per_s", SERVE_LIMIT)):
        cfg = copy.deepcopy(cell.config)
        cfg["name"] = name = f"tiny-copy-{cell.kind}"
        cfg["model"]["family"] = "dense_copy"
        (here / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        (here / "traffic" / f"{name}.json").write_text(
            json.dumps(cell.traffic))
        (here / "limits" / f"{name}.run.json").write_text(
            json.dumps({"limits": limits}))
        bench["configs"].append({"name": name, "file": str(
            (here / "configs" / f"{name}.json").relative_to(root))})
        bench["workloads"].append({"name": f"{name}.run", "config": name,
                                   "traffic": name, "chips": 1})
        bench["end_to_end"].append({"name": metric, "unit": "tokens/s",
                                    "workloads": [f"{name}.run"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("kind,metric", [("train", "train_tokens_per_s"),
                                         ("serve", "output_tokens_per_s")])
def test_a_new_family_runs_from_its_files_alone(plugin_root, kind, metric):
    with pytest.raises(FileNotFoundError):
        spec.load_family("dense_copy")        # not in the checkout
    before = _files(spec.HERE)
    cell = spec.load_cell(f"tiny-copy-{kind}.run", root=plugin_root)
    assert cell.config["model"]["family"] == "dense_copy"
    out = run(cell, seed=3)
    assert out["correct"], out["checks"]
    assert out["metrics"][metric]["value"] > 0
    assert _files(spec.HERE) == before
    assert (plugin_root / "benchmarks/chip/families/dense_copy.py").is_file()
