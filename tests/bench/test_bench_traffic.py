"""Traffic generators are deterministic per seed, and every seed gets the
same work."""

import numpy as np

from bench import core

ZM = {"generator": "zipf_markov", "seq_len": 32, "n_states": 16,
      "fanout": 8}
CHAT = {"generator": "open_loop", "rate_per_s": 5.0,
        "prompt_len": {"median": 256, "sigma": 0.75, "min": 32, "max": 768},
        "output_len": {"median": 128, "sigma": 0.75, "min": 16, "max": 256}}


def gen(name):
    return core.load_module("traffic", name)


def test_zipf_markov_deterministic_rows_differ():
    a = gen("zipf_markov").make(ZM, 7, 4, 1000)
    b = gen("zipf_markov").make(ZM, 7, 4, 1000)
    c = gen("zipf_markov").make(ZM, 8, 4, 1000)
    for i in range(3):
        assert np.array_equal(a(i)["inputs"], b(i)["inputs"])
        assert a(i)["inputs"].shape == (4, 32)
        assert np.array_equal(a(i)["inputs"][:, 1:],
                              a(i)["targets"][:, :-1])
    assert not np.array_equal(a(0)["inputs"], c(0)["inputs"])
    rows = np.concatenate([a(i)["inputs"] for i in range(3)])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert rows.max() < 1000 and rows.min() >= 0


def test_open_loop_deterministic_same_work_every_seed():
    g = gen("open_loop")
    a = g.make(CHAT, 5, 30.0, 1000)
    b = g.make(CHAT, 5, 30.0, 1000)
    c = g.make(CHAT, 6, 30.0, 1000)
    assert len(a) == len(c) == 150
    assert all(x["due"] == y["due"] and np.array_equal(x["prompt"],
                                                        y["prompt"])
               for x, y in zip(a, b))
    assert [x["due"] for x in a] != [x["due"] for x in c]
    for key in ("max_new",):
        assert sorted(x[key] for x in a) == sorted(x[key] for x in c)
    assert sorted(len(x["prompt"]) for x in a) == \
        sorted(len(x["prompt"]) for x in c)
    dues = [x["due"] for x in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
    lens = [len(x["prompt"]) for x in a]
    assert min(lens) >= 32 and max(lens) <= 768
    assert abs(np.median(lens) - 256) <= 8


def test_open_loop_arrivals_are_poisson_from_one_set_of_gaps():
    """Every seed draws its gaps from one set of exponential quantiles, in
    an order the seed shuffles freely, so short gaps bunch into bursts as
    in a Poisson process (no chunk spreads them out)."""
    g = gen("open_loop")

    n = 150
    quantiles = np.array([-np.log(1.0 - (i + 0.5) / n) for i in range(n)])
    step = 30.0 / quantiles.sum()

    def gaps(seed):
        d = np.array([x["due"] for x in g.make(CHAT, seed, 30.0, 1000)])
        return np.diff(d)
    a, b = gaps(5), gaps(6)
    for x in (a, b):
        assert np.abs(x[:, None] / step - quantiles[None]).min(1).max() < 1e-9
    assert not np.allclose(a, b)
    low = a < np.quantile(a, 0.25)
    assert max(int(low[i:i + 4].sum()) for i in range(len(a) - 3)) >= 3
    counts = np.histogram(np.cumsum(a), bins=29, range=(0, 29))[0]
    assert counts.var() / counts.mean() > 0.5


def test_seed32_takes_large_seeds():
    s = [core.seed32(x) for x in (0, 2**31 + 5, 2**40 + 5, 2**64 + 5)]
    assert len(set(s)) == 4 and all(0 <= x < 2**32 for x in s)
    assert core.seed32(2**31 + 5) == core.seed32(2**31 + 5)
    assert core.seed32(9, 1) != core.seed32(9, 2)
