"""Two seeds of one traffic file offer the same multiset of lengths and
gaps, in another order — the property the bounds rest on."""

import glob
import os

import numpy as np
import pytest

from benchmarks import traffic

FILES = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(os.path.dirname(traffic.__file__), "traffic", "*.json")))


def drawn(t: traffic.Traffic, cycles: int):
    specs = [t.next() for _ in range(cycles * t.cycle)]
    return ([(s.prompt.shape[0], s.max_new, s.sampled) for s in specs],
            [s.gap_s for s in specs])


@pytest.mark.parametrize("name", FILES)
def test_same_multiset_every_seed_and_cycle(name):
    params = traffic.load(name)
    a = traffic.Traffic(params, 7, vocab=32000)
    b = traffic.Traffic(params, 2 ** 31 + 12345, vocab=32000)
    assert a.multiset() == b.multiset()
    la, ga = drawn(a, 3)
    lb, gb = drawn(b, 3)
    k = a.cycle
    for c in range(3):          # every cycle, not only the whole stream
        assert sorted(la[c * k:(c + 1) * k]) == a.multiset()["pairs"]
        assert sorted(lb[c * k:(c + 1) * k]) == a.multiset()["pairs"]
        assert np.allclose(sorted(ga[c * k:(c + 1) * k]),
                           sorted(gb[c * k:(c + 1) * k]))
    assert la != lb             # the seed does decide the order
    if params["order"] == "rotate":
        # one periodic trace, entered at a phase the seed picks
        assert la[:k] == la[k:2 * k] and ga[:k] == ga[k:2 * k]
        both = list(zip(lb, gb))
        i = both.index((la[0], ga[0]))
        assert (both + both)[i:i + k] == list(zip(la, ga))[:k]
    else:
        assert la[:k] != la[k:2 * k]    # a fresh permutation every cycle
    if params["loop"] == "open":
        assert np.isclose(np.mean(ga), 1.0 / params["rate_per_s"])


@pytest.mark.parametrize("name", FILES)
def test_lengths_follow_the_stated_law(name):
    params = traffic.load(name)
    t = traffic.Traffic(params, 0, vocab=32000)
    prompts = sorted(p for p, _, _ in t.pairs)
    outs = sorted(o for _, o, _ in t.pairs)
    assert params["prompt"]["lo"] <= prompts[0] <= prompts[-1] \
        <= params["prompt"]["hi"]
    assert params["output"]["lo"] <= outs[0] <= outs[-1] \
        <= params["output"]["hi"]
    # a log-uniform grid: the median is the geometric mean of the ends
    for law, vals in ((params["prompt"], prompts), (params["output"], outs)):
        if law["law"] == "log_uniform":
            geo = (law["lo"] * law["hi"]) ** 0.5
            assert abs(np.median(vals) / geo - 1) < 0.08
    assert sum(s for _, _, s in t.pairs) == len(range(1, t.cycle, 3))


def test_same_seed_same_stream():
    params = traffic.load(FILES[0])
    a = traffic.Traffic(params, 99, vocab=1000)
    b = traffic.Traffic(params, 99, vocab=1000)
    for _ in range(40):
        x, y = a.next(), b.next()
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.sampled, x.sampler_seed, x.gap_s) == \
            (y.max_new, y.sampled, y.sampler_seed, y.gap_s)
