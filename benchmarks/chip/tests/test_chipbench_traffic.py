"""The one generator: reproducible per seed, the same work for every seed."""
import numpy as np
import pytest

from chipbench_tiny import chat_traffic, serving_cell
from benchmarks.chip import traffic

SEEDS = [0, 7, 2 ** 31 + 11, 2 ** 33 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_schedule_is_reproducible_per_seed(seed):
    t = chat_traffic()
    a = traffic.schedule(t, seed=seed, seconds=51, vocab=1000)
    b = traffic.schedule(t, seed=seed, seconds=51, vocab=1000)
    assert [(x.due_s, x.max_new) for x in a] == [(x.due_s, x.max_new)
                                                 for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_seeds_share_the_work_in_another_order():
    t = chat_traffic()
    a, b = (traffic.schedule(t, seed=s, seconds=51, vocab=1000)
            for s in (1, 2))
    assert sorted(len(x.tokens) for x in a) == sorted(len(x.tokens)
                                                      for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.tokens) for x in a] != [len(x.tokens) for x in b]
    gaps = [np.sort(np.diff([x.due_s for x in s])) for s in (a, b)]
    assert len(gaps[0]) == len(gaps[1])


def test_poisson_schedule_fills_the_window_at_its_rate():
    t = chat_traffic()
    rate = t["arrivals"]["rate_per_s"]
    s = traffic.schedule(t, seed=3, seconds=51, vocab=1000)
    due = np.array([x.due_s for x in s])
    assert len(s) == round(rate * 51)
    assert due[0] == 0 and due[-1] < 51 and np.all(np.diff(due) > 0)
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(x.tokens) <= hi for x in s)
    lo, hi = t["output_tokens"]["min"], t["output_tokens"]["max"]
    assert all(lo <= x.max_new <= hi for x in s)
    assert all(1 <= x.tokens.min() and x.tokens.max() < 1000 for x in s)


def test_batch_schedule_is_all_due_at_once():
    t = serving_cell().traffic
    s = traffic.schedule(t, seed=3, seconds=51, vocab=1000)
    assert len(s) == t["arrivals"]["requests"]
    assert {x.due_s for x in s} == {0.0}
    b = t["arrivals"]["block"]
    blocks = [sorted((len(x.tokens), x.max_new) for x in s[i:i + b])
              for i in range(0, len(s), b)]
    lens = [(sorted(p for p, _ in k), sorted(o for _, o in k))
            for k in blocks]
    assert all(x == lens[0] for x in lens)   # the same work in every block
    assert blocks[0] != blocks[1]            # paired in another order


def test_log_normal_quantiles_keep_to_their_range():
    d = {"dist": "log_normal", "median": 129, "sigma": 1.0, "min": 2,
         "max": 512}
    q = traffic.quantile_lengths(d, 1000)
    assert q.min() >= 2 and q.max() <= 512 and np.all(np.diff(q) >= 0)
    # the median of the log-normal taken on [2, 512]: below 129, since
    # the upper tail is cut off
    assert 110 <= np.median(q) < 129
    wide = dict(d, min=1, max=10 ** 7)
    assert abs(np.median(traffic.quantile_lengths(wide, 1001)) - 129) <= 1


def test_log_uniform_quantiles():
    q = traffic.quantile_lengths({"dist": "log_uniform", "min": 32,
                                  "max": 512}, 4)
    want = np.rint(np.exp(np.log(32) + (np.arange(4) + 0.5) / 4
                          * np.log(16)))
    assert np.array_equal(q, want)


def test_check_sample_holds_the_longest_and_is_seeded():
    fin = {u: (u * 7) % 23 + 1 for u in range(40)}
    a = traffic.check_sample(fin, 5, seed=9)
    assert a == traffic.check_sample(fin, 5, seed=9)
    assert len(a) == 5 and a[0] == max(fin, key=lambda u: (fin[u], -u))
    assert traffic.check_sample({}, 5, seed=9) == []
