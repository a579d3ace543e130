"""The traffic generator: each mix is a pure function of its seed; other
seeds get other token ids and the same arrivals and sizes, spread evenly
over the span."""
import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))


def _mix(name):
    import json
    return json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(_mix(name), 2**31 + 17, 30, 49152)
    b = traffic.schedule(_mix(name), 2**31 + 17, 30, 49152)
    assert [(q.due_s, q.max_new_tokens) for q in a] == \
        [(q.due_s, q.max_new_tokens) for q in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_tokens_same_work(name):
    mix = _mix(name)
    a = traffic.schedule(mix, 11, 30, 49152)
    b = traffic.schedule(mix, 2**31 + 12, 30, 49152)
    assert [(q.due_s, q.prompt.size, q.max_new_tokens, q.window)
            for q in a] == [(q.due_s, q.prompt.size, q.max_new_tokens,
                             q.window) for q in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    for q in a:
        assert mix["prompt"]["min"] <= q.prompt.size <= mix["prompt"]["max"]
        assert (mix["output"]["min"] <= q.max_new_tokens
                <= mix["output"]["max"])
        assert q.prompt.min() >= 1 and q.prompt.max() < 49152


def test_schedule_covers_preroll_and_window():
    mix = _mix(MIXES[0])
    s = traffic.schedule(mix, 5, 30, 1000)
    (_, pre, n_pre), (start, span, n_win) = traffic.segments(mix, 30)
    rate = mix["arrivals"]["rate_per_s"]
    assert n_win == round(rate * 30) and len(s) == n_pre + n_win
    window = [q for q in s if q.window]
    assert len(window) == n_win
    assert all(q.due_s <= pre for q in s if not q.window)
    assert all(start < q.due_s <= start + span + 1e-9 for q in window)
    assert window[-1].due_s == pytest.approx(start + span)
    assert [q.rid for q in s] == list(range(len(s)))


def test_lengths_follow_the_distribution():
    spec_ = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32,
             "max": 3072}
    v = traffic._lengths(spec_, 1001)
    assert np.median(v) == 512
    assert v.min() >= 32 and v.max() <= 3072
    u = traffic._lengths({"dist": "uniform", "min": 16, "max": 64}, 100)
    assert 39 <= u.mean() <= 41
    with pytest.raises(ValueError):
        traffic._lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


@pytest.mark.parametrize("alpha,tol", [(traffic.ALPHA_GAP, 0.04),
                                       (traffic.ALPHA_PROMPT, 0.05),
                                       (traffic.ALPHA_OUTPUT, 0.1)])
def test_order_is_a_permutation_spread_over_the_span(alpha, tol):
    for n in range(30, 90):
        values = np.arange(n, dtype=float)
        v = traffic._spread(values, alpha)
        assert sorted(v) == list(values)
        # the first half of the requests holds about half of the total
        assert abs(v[:n // 2].sum() / v.sum() - (n // 2) / n) < tol
