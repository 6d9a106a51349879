"""The generator copy replays byte-identically for one seed, differs for
another, and honours the mix's clips and burst."""
import numpy as np

from benchmark.lib import workload as wl
from benchmark.tests import toy

MIX = toy.load("traffic", "chat_sat")


def test_replay_is_byte_identical_and_seeds_differ():
    a = wl.build_schedule(MIX, 7, 50304, 1024, 20.0)
    b = wl.build_schedule(MIX, 7, 50304, 1024, 20.0)
    c = wl.build_schedule(MIX, 8, 50304, 1024, 20.0)
    assert len(a) > MIX["seed_burst"]["count"]
    assert wl.schedule_digest(a) == wl.schedule_digest(b)
    assert wl.schedule_digest(a) != wl.schedule_digest(c)


def test_a_longer_horizon_only_appends():
    a = wl.build_schedule(MIX, 3, 50304, 1024, 10.0)
    b = wl.build_schedule(MIX, 3, 50304, 1024, 20.0)
    assert wl.schedule_digest(b[:len(a)]) == wl.schedule_digest(a)


def test_vector_draws_equal_scalar_draws():
    s = wl.Stream(11, "chat_sat/prompt_tok")
    base = 5 << 20
    block = s.randint_block(base, 300, 1, 50304)
    assert block.tolist() == [s.randint(base | j, 1, 50304)
                              for j in range(300)]
    assert block.min() >= 1 and block.max() < 50304


def test_lengths_burst_and_rate():
    reqs = wl.build_schedule(MIX, 1, 50304, 1024, 200.0)
    ten = MIX["tenants"][0]
    n_burst = MIX["seed_burst"]["count"]
    assert [r.t_due for r in reqs[:n_burst]] == [0.0] * n_burst
    for r in reqs:
        assert ten["prompt"]["lo"] <= r.prompt.size <= ten["prompt"]["hi"]
        assert 1 <= r.new_tokens <= ten["new"]["hi"]
        assert r.prompt.size + r.new_tokens <= 1024
    for r in reqs[n_burst:]:
        assert r.new_tokens >= ten["new"]["lo"]
    # the burst's outputs are scaled down, so shorter on average
    assert (np.mean([r.new_tokens for r in reqs[:n_burst]])
            < np.mean([r.new_tokens for r in reqs[n_burst:]]))
    dues = [r.t_due for r in reqs]
    assert dues == sorted(dues)
    rate = (len(reqs) - n_burst) / 200.0
    assert abs(rate - MIX["arrival"]["rate"]) < 0.15 * MIX["arrival"]["rate"]


def test_windows_arrival_with_a_silent_window():
    mix = dict(MIX, arrival={"kind": "windows",
                             "windows": [[5, 4.0], [5, 0.0], [5, 4.0]]})
    mix.pop("seed_burst")
    reqs = wl.build_schedule(mix, 2, 1024, 1024, 15.0)
    assert reqs and not [r for r in reqs if 5.0 <= r.t_due < 10.0]
