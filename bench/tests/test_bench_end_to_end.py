"""The end-to-end metrics from hand-made timelines: output tokens inside
the window, and the tails of TTFT and of the gaps between tokens, named
``ttft_p<q>_ms`` and ``itl_p<q>_ms``."""

import types

import pytest

from bench import harness


def timelines():
    """Two requests due at 0 and 1 s; the first gets its first token at
    0.5 s and three more at 1, 1.5 and 3 s; the second never starts."""
    items = [types.SimpleNamespace(uid=0, due_s=0.0),
             types.SimpleNamespace(uid=1, due_s=1.0)]
    results = [types.SimpleNamespace(
        uid=0, metrics=types.SimpleNamespace(first_token_s=0.5))]
    times = {0: [1.0, 1.5, 3.0], 1: []}
    return items, results, times


def test_tails_by_name():
    out, n_ttft, n_gaps = harness.end_to_end(
        *timelines(), 2.0, 7.0, names=("ttft_p50_ms", "itl_p50_ms",
                                       "itl_p100_ms"))
    assert (n_ttft, n_gaps) == (2, 3)
    # three of the four tokens are emitted by the window's close at 2 s
    assert out["output_tok_s"] == pytest.approx(3 / 2.0)
    assert out["setup_s"] == 7.0
    # the request that never started waits for the window and the drain
    assert out["ttft_p50_ms"] == pytest.approx(
        1e3 * (0.5 + (2.0 + harness.DRAIN_S - 1.0)) / 2)
    # gaps 0.5, 0.5 and 1.5 s
    assert out["itl_p50_ms"] == pytest.approx(500.0)
    assert out["itl_p100_ms"] == pytest.approx(1500.0)


@pytest.mark.parametrize("name", ["ttft_p90", "itl_mean_ms", "queue_p50_ms"])
def test_unknown_metric_is_refused(name):
    with pytest.raises(KeyError):
        harness.end_to_end(*timelines(), 2.0, 7.0, names=(name,))


def test_every_metric_of_the_benchmark_has_a_reader():
    """Each end-to-end metric that ``BENCHMARK.json`` names is one the
    harness computes, and each per-layer metric finds its reader (a metric
    split by what it moves reads with its base reader)."""
    import json

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["end_to_end"]]
    out, _, _ = harness.end_to_end(
        *timelines(), 2.0, 7.0,
        names=[n for n in names if harness.TAIL.match(n)])
    assert set(names) <= set(out)
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    assert harness.metric_reader("idle_share.itl_p99").__code__.co_filename \
        == harness.metric_reader("idle_share").__code__.co_filename
