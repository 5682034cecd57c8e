"""A layer of held experts (``moe.held_moe_ffn``): the shares of a layer add
up to the whole layer, no routed pair is ever dropped, and a token's result
does not depend on its batch mates; and YaRN's rotary table at Mellum2's
published numbers."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.layers import yarn_rotary
from repro.models.moe import held_moe_ffn, moe_ffn

E, K, D, F = 8, 4, 32, 16


def _cfg(held=2, offset=0, **kw):
    return ModelConfig(name="tiny-moe", family="moe", num_layers=1,
                       d_model=D, num_heads=4, num_kv_heads=2, d_ff=F,
                       vocab_size=64, num_experts=E, experts_per_token=K,
                       experts_held=held, expert_offset=offset, **kw)


def _params(seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    return {"router": n(D, E), "wi_gate": n(E, D, F), "wi_up": n(E, D, F),
            "wo": n(E, F, D)}


def _share(p, lo, hi):
    return {"router": p["router"], **{k: v[lo:hi] for k, v in p.items()
                                      if k != "router"}}


def _whole_layer(p, x):
    """The uncut layer in float64 numpy: every expert over every token,
    weighted by its renormalised top-k gate (0 where not chosen)."""
    x = np.asarray(x, np.float64).reshape(-1, D)
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :K]
    gates = np.take_along_axis(probs, top, -1)
    gates /= gates.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for e in range(E):
        g = x @ np.asarray(p["wi_gate"][e], np.float64)
        u = x @ np.asarray(p["wi_up"][e], np.float64)
        ye = (g / (1 + np.exp(-g)) * u) @ np.asarray(p["wo"][e], np.float64)
        y += ye * np.where(top == e, gates, 0).sum(-1, keepdims=True)
    return y


def _x(seed=1, B=2, S=24):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)


def test_four_shares_add_up_to_the_whole_layer():
    p, x = _params(), _x()
    parts = [held_moe_ffn(_cfg(2, off), _share(p, off, off + 2), x)
             for off in (0, 2, 4, 6)]
    total = sum(np.asarray(y, np.float64) for y, _ in parts)
    want = _whole_layer(p, x).reshape(x.shape)
    assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()
    # each pair is routed to exactly one share: K per token in all
    assert sum(int(st["moe_rows"]) for _, st in parts) == x.shape[0] * \
        x.shape[1] * K


def test_one_share_is_its_experts_part():
    p, x = _params(), _x()
    y, st = held_moe_ffn(_cfg(2, 4), _share(p, 4, 6), x)
    rest = [held_moe_ffn(_cfg(2, off), _share(p, off, off + 2), x)[0]
            for off in (0, 2, 6)]
    want = _whole_layer(p, x).reshape(x.shape) - sum(
        np.asarray(r, np.float64) for r in rest)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    sizes_mean = float(st["moe_rows"]) / 2
    assert 1.0 <= float(st["moe_load_max"]) <= 2.0
    assert sizes_mean > 0


def test_every_token_on_one_held_expert_loses_nothing():
    """A router that sends every token to held expert 2 first: the capacity
    path drops most of them, the held path none."""
    p, x = _params(), _x()
    router = np.asarray(p["router"]).copy()
    router[:, 2] += 40.0 * np.sign(np.asarray(x).mean((0, 1)))
    p["router"] = jnp.asarray(router)
    cfg = _cfg(2, 2)
    y, st = held_moe_ffn(cfg, _share(p, 2, 4), x)
    T = x.shape[0] * x.shape[1]
    assert int(st["moe_rows"]) >= T               # expert 2 took every token
    rest = [held_moe_ffn(_cfg(2, off), _share(p, off, off + 2), x)[0]
            for off in (0, 4, 6)]
    want = _whole_layer(p, x).reshape(x.shape) - sum(
        np.asarray(r, np.float64) for r in rest)
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    # the capacity-bounded layer, every expert held, drops tokens here
    dropped = moe_ffn(cfg.replace(experts_held=0, capacity_factor=1.0), p, x)
    whole = _whole_layer(p, x).reshape(x.shape)
    assert np.abs(np.asarray(dropped) - whole).max() > 1e-2


def test_a_tokens_result_does_not_depend_on_its_batch_mates():
    p = _params()
    x = _x(seed=1, B=3)
    y, _ = held_moe_ffn(_cfg(2, 2), _share(p, 2, 4), x)
    other = np.asarray(_x(seed=5, B=3)).copy()
    other[1] = np.asarray(x[1])            # the same row among new mates
    y2, _ = held_moe_ffn(_cfg(2, 2), _share(p, 2, 4), jnp.asarray(other))
    np.testing.assert_allclose(np.asarray(y2[1]), np.asarray(y[1]),
                               rtol=1e-6, atol=1e-7)


def test_gradients_flow_only_through_written_rows():
    """The buffer's unwritten rows (NaN in interpret mode) reach neither
    the output nor any gradient."""
    p, x = _params(), _x()
    cfg = _cfg(2, 2)

    def loss(p, x):
        return jnp.sum(held_moe_ffn(cfg, p, x)[0] ** 2)

    grads = jax.grad(loss, argnums=(0, 1))(_share(p, 2, 4), x)
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(grads[0]["router"]).sum()) > 0


def test_a_share_that_no_token_chose_gives_zero():
    """Held experts that no token routes to: the buffer holds no written
    row, the output is exactly zero and every gradient is finite, the
    experts' own zero."""
    p, x = _params(), jnp.abs(_x())
    # positive tokens score every expert above zero but experts 2 and 3
    router = np.abs(np.asarray(p["router"]))
    router[:, 2:4] *= -1
    p["router"] = jnp.asarray(router)
    cfg = _cfg(2, 2)
    y, st = held_moe_ffn(cfg, _share(p, 2, 4), x)
    assert int(st["moe_rows"]) == 0
    assert np.all(np.asarray(y) == 0)

    def loss(p, x):
        return jnp.sum(held_moe_ffn(cfg, p, x)[0] ** 2 + x)

    grads = jax.grad(loss, argnums=(0, 1))(_share(p, 2, 4), x)
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
    assert all(float(jnp.abs(grads[0][k]).max()) == 0
               for k in ("wi_gate", "wi_up", "wo"))


# -- YaRN ---------------------------------------------------------------------
def _yarn_written_out(D=128, base=500000.0, factor=16.0, orig=8192,
                      beta_fast=32.0, beta_slow=1.0):
    """YaRN's inverse frequencies, by its definition: dimension i keeps the
    base frequency base^(-2i/D) below the dimension that turns beta_fast
    times over the original context, takes it over ``factor`` above the one
    that turns beta_slow times, and is blended linearly between them."""
    def dim(rot):
        return D * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(dim(beta_fast)), 0), min(
        math.ceil(dim(beta_slow)), D - 1)
    out = []
    for i in range(D // 2):
        f = base ** (-2 * i / D)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / factor * ramp)
    return np.array(out), (low, high)


def test_yarn_table_at_mellum2s_numbers():
    inv, scale = yarn_rotary(128, 500000.0, 16.0, 8192, 32.0, 1.0,
                             1.2772588722239782)
    want, (low, high) = _yarn_written_out()
    assert (low, high) == (18, 35)      # of the 64 frequency dims
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert inv[0] == pytest.approx(1.0) and inv[-1] == pytest.approx(
        want[-1], rel=2e-6)
    assert scale == 1.2772588722239782
    # the published attention factor is YaRN's default, 0.1 ln(16) + 1
    assert yarn_rotary(128, 500000.0, 16.0, 8192, 32.0, 1.0)[1] == \
        pytest.approx(1.2772588722239782, rel=1e-12)


# -- the normal train path -------------------------------------------------
def _mellum_like():
    return ModelConfig(
        name="tiny-mellum", family="moe", num_layers=4, d_model=D,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=F, vocab_size=64,
        num_experts=E, experts_per_token=K, experts_held=2,
        layer_pattern=("sliding", "sliding", "sliding", "full"),
        sliding_window=8, rope_theta=500000.0, yarn_factor=16.0,
        yarn_original_max_positions=16)


def test_serving_a_stack_of_layer_kinds_says_it_is_not_built():
    from repro.models import build_model, init_params

    model = build_model(_mellum_like())
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="layer kinds"):
        model.prefill(params, {"tokens": tokens})


def test_the_trainer_steps_a_held_expert_stack_and_logs_its_counters(
        monkeypatch, capsys):
    """``launch.train`` on a stack of windowed and full (YaRN) layers of
    held experts: the guarded step returns the counters and the loop logs
    them; every layer routes its K pairs a token over all experts, so the
    held share of 2 of 8 takes about a quarter of them."""
    from repro.launch import train as train_lib

    cfg = _mellum_like()
    monkeypatch.setattr(train_lib, "get_config", lambda arch, smoke: cfg)
    out = train_lib.train("tiny-mellum", steps=2, batch=2, seq=32,
                          log_every=1, use_mesh=False)
    assert np.isfinite(out["final_loss"])
    logged = [l for l in capsys.readouterr().out.splitlines()
              if l.startswith("step")]
    assert len(logged) == 2 and all("moe_rows" in l and "moe_load_max" in l
                                    for l in logged)
    rows = float(logged[0].split("moe_rows")[1].split()[0])
    assert 0.1 < rows / (4 * 2 * 32 * K) < 0.4
