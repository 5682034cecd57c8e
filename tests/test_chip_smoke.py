"""chip_smoke.py's phases at smoke widths on the CPU (Pallas kernels in
interpret mode), and its refusal to run, or to report success, without a
TPU."""
import importlib.util
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_smoke_widths(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    out = smoke.serve_phase(smoke.CompileClock(), smoke=True, slots=2,
                            prompt_len=32, gen=8, chunk=4, requests=3,
                            page_size=8)
    assert out["contiguous"] > 0 and out["paged"] > 0
    assert set(out["kernels"]) == {"prefill", "decode", "paged decode"}
    assert all(e <= smoke.KERNEL_RTOL for e in out["kernels"].values())
    for ps in (0, 8):
        for what in ("prefill", "decode"):
            err = out[f"logits_ps{ps}"][what]
            assert 0 <= err["pallas_jnp"] <= err["jnp_f32"]


def test_train_phase_smoke_widths(smoke):
    out = smoke.train_phase(smoke.CompileClock(), smoke=True, steps=2,
                            batch=4, seq=32)
    assert len(out["losses"]) == 2


def test_dp_check_sees_a_missing_update(smoke):
    rng = np.random.default_rng(0)
    w0 = [rng.standard_normal((64, 32)), rng.standard_normal(16)]
    step = [rng.choice([-1e-3, 1e-3], w.shape) for w in w0]

    def flipped(share):
        """The update with a share of its signs flipped, as AdamW's first
        steps flip the near-zero gradient entries that rounding moves."""
        return [np.where(rng.random(s.shape) < share, -s, s) for s in step]

    def run(update, loss0=5.0, gnorm0=2.0):
        return {"losses": [loss0, 4.9], "grad_norms": [gnorm0, 1.8],
                "params": [w + u for w, u in zip(w0, update)]}

    ref = run(step)
    sound = run(flipped(0.08), loss0=5.0001)           # gap about 0.57
    smoke.check_dp(smoke.dp_agreement(ref, sound, w0), "sound")
    for bad in (run([0 * s for s in step]),            # lr 0: no update
                run([-s for s in step]),               # wrong direction
                run(flipped(0.25)),                    # a partial gradient
                run([0.9 * s for s in step]),          # a shrunken update
                run(step, gnorm0=3.0)):                # other gradient
        with pytest.raises(AssertionError):
            smoke.check_dp(smoke.dp_agreement(ref, bad, w0), "bad")


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_cpu(smoke, argv, capsys):
    assert smoke.main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out
