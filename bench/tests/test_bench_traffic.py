"""The traffic generator: deterministic per seed, every prompt on the
mix's ladder, bursts present, and the same work for every seed."""

import collections
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import traffic  # noqa: E402

MIX = traffic.load_mix("chat-bursty")
BIG = 2 ** 31 + 12345


def sched(seed, rate=3.0, seconds=30.0):
    return traffic.schedule(MIX, rate_rps=rate, seconds=seconds,
                            vocab=32000, seed=seed)


def test_same_seed_same_schedule():
    a, b = sched(BIG), sched(BIG)
    assert [(x.uid, x.due_s, x.max_new_tokens) for x in a] == \
        [(x.uid, x.due_s, x.max_new_tokens) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_work_not_the_order():
    a, b = sched(BIG), sched(BIG + 1)
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_prompts_on_the_ladder_and_in_bounds():
    ladder = set(MIX["prompt"]["ladder"])
    for it in sched(7):
        assert len(it.prompt) in ladder
        assert MIX["output"]["min"] <= it.max_new_tokens \
            <= MIX["output"]["max"]
        assert it.prompt.dtype == np.int32 and it.prompt.max() < 32000
    assert traffic.prompt_shapes(MIX, 90) == sorted(
        {len(x.prompt) for x in sched(7)})


def test_rate_and_window():
    items = sched(3, rate=3.0, seconds=30.0)
    assert len(items) == 90
    due = [x.due_s for x in items]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0


def test_bursts_present():
    arr = MIX["arrivals"]
    items = sched(11, rate=3.0, seconds=30.0)
    due = np.array([x.due_s for x in items])
    # a burst puts burst_size arrivals within burst_span_s
    windows = collections.Counter()
    for t in due:
        windows[t] = int(((due >= t) & (due < t + arr["burst_span_s"]
                                         + 1e-9)).sum())
    n_full = sum(1 for v in windows.values() if v >= arr["burst_size"])
    assert n_full >= round(arr["burst_share"] * 90 / arr["burst_size"])


def test_generator_does_not_import_jax():
    code = ("import sys; sys.path.insert(0, %r); import bench.traffic, "
            "bench.costs; assert 'jax' not in sys.modules" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_each_burst_carries_every_stratum():
    lengths = traffic.lognormal_lengths(MIX["prompt"], 90)
    members = [list(range(b * 8, b * 8 + 8)) for b in range(5)]
    rng = np.random.default_rng(3)
    dealt = traffic._deal(lengths, members, rng)
    assert sorted(dealt) == sorted(lengths)
    ranked = sorted(lengths)
    for burst in members:
        got = sorted(dealt[i] for i in burst)
        for s, x in enumerate(got):
            assert ranked[s * 90 // 8] <= x <= ranked[(s + 1) * 90 // 8 - 1]
