"""The traffic generator: deterministic by seed, the same work for every
seed, and the declared distributions."""
import numpy as np
import pytest

from bench.lib import gen, spec


def _open(name, seed, seconds=45.0):
    return gen.open_loop(spec.traffic(name), seconds, seed, 50304)


@pytest.mark.parametrize("name", ["tenants_zipf", "chat_steady"])
def test_open_loop_is_deterministic_by_seed(name):
    a, b = _open(name, 2**33 + 1), _open(name, 2**33 + 1)
    assert [(p.due, p.max_new, p.task) for p in a] == \
        [(p.due, p.max_new, p.task) for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    c = _open(name, 2**33 + 2)
    assert [p.due for p in a] != [p.due for p in c]


@pytest.mark.parametrize("name", ["tenants_zipf", "chat_steady"])
def test_open_loop_same_work_for_every_seed(name):
    a, b = _open(name, 11), _open(name, 2**31 + 5)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new,
                lambda p: p.task):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = lambda r: sorted(np.round(np.diff([0.0] + [p.due for p in r]),  # noqa: E731
                                     9))
    assert gaps(a) == gaps(b)


def test_open_loop_rate_and_window():
    mix = spec.traffic("tenants_zipf")
    reqs = gen.open_loop(mix, 45.0, 3, 50304)
    assert len(reqs) == round(mix["rate"] * 45.0)
    due = np.array([p.due for p in reqs])
    assert (np.diff(due) > 0).all() and 0 < due[0] and due[-1] < 45.0


def test_lognormal_lengths_match_declared():
    mix = spec.traffic("tenants_zipf")
    n = 4000
    lens = gen.lengths(mix["prompt"], gen.quantiles(n))
    assert abs(np.median(lens) - mix["prompt"]["median"]) <= 1
    assert lens.min() >= mix["prompt"]["lo"] and lens.max() <= mix["prompt"]["hi"]
    # below the clip, log-lengths spread by sigma
    inner = np.log(lens[(lens > mix["prompt"]["lo"]) & (lens < mix["prompt"]["hi"])])
    q16, q84 = np.percentile(np.log(lens), [16, 84])
    assert abs((q84 - q16) / 2 - mix["prompt"]["sigma"]) < 0.02
    assert len(inner) > 0.9 * n


def test_uniform_lengths_match_declared():
    mix = spec.traffic("long_decode")
    lens = gen.lengths(mix["prompt"], gen.quantiles(1000))
    assert lens.min() >= 1024 and lens.max() <= 1728
    assert abs(lens.mean() - (1024 + 1728) / 2) < 1


def test_zipf_tenants_match_declared():
    spec_ = spec.traffic("tenants_zipf")["tasks"]
    n = 20000
    t = gen.tenants(spec_, gen.quantiles(n))
    ranks = np.arange(1, spec_["n"] + 1) ** -spec_["s"]
    pmf = ranks / ranks.sum()
    freq = np.bincount(t, minlength=spec_["n"]) / n
    assert np.abs(freq[:10] - pmf[:10]).max() < 2.0 / n * 10
    assert t.min() == 0 and t.max() < spec_["n"]


def test_closed_rounds_deal_one_set_per_round():
    mix = spec.traffic("long_decode")
    for k in (1, 2):
        a = gen.closed_round(mix, 5, k, 50304)
        b = gen.closed_round(mix, 2**33, k, 50304)
        # the same sizes to the same clients, other token ids
        assert [p.max_new for p in a] == [p.max_new for p in b]
        assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]
        assert not np.array_equal(a[0].prompt, b[0].prompt)
        assert len(set(p.max_new for p in a)) > 1
        assert [p.uid for p in a] == [k * mix["clients"] + j
                                      for j in range(mix["clients"])]
    first = gen.closed_round(mix, 5, 0, 50304)
    full = gen.lengths(mix["output"], gen.quantiles(mix["clients"]))
    # priming cuts every first output, to a different share per client
    assert all(1 <= p.max_new <= mix["output"]["hi"] for p in first)
    assert sum(p.max_new for p in first) < 0.6 * full.sum()
