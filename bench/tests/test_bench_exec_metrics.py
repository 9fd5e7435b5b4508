"""The readers of the program's named executables (``jit_serve_<name>``)
on hand-built traces, what they read from a program that names none, and
the readers that were there before, which the names and the program's own
spans leave as they were."""

import pytest

from bench import costs, executables, harness
from bench import trace as tr
from bench.tests import smoke

DEV = "/device:TPU:0"
MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return tr.Event(name, int(start_ms * MS), int(dur_ms * MS))


#: how the parent program's executables read (every callable a lambda)
OLD = {"prefill": "jit__lambda(11)", "write": "jit__write_slot(12)",
       "decode": "jit__lambda(13)", "sample": "jit_sample_batch(14)"}
#: and how they read once named by ``repro.tracing.executable``
NEW = {"prefill": "jit_serve_prefill(21)", "write": "jit_serve_write(22)",
       "decode": "jit_serve_decode(23)", "sample": "jit_serve_sample(24)"}


def record(names):
    """Three traced ticks: tick 0 admits a 64-token prompt (prefill 3 ms,
    write 0.5 ms) and decodes (4 ms); tick 1 decodes (5 ms); tick 2 admits
    a 32-token prompt (prefill 2 ms, write 0.5 ms) and decodes nothing.
    Each decode is followed by a 0.2 ms sample."""
    spans = [ev("bench.tick", 0, 10), ev("bench.wait", 10, 5),
             ev("bench.tick", 15, 8), ev("bench.tick", 25, 4)]
    modules = [ev(names["prefill"], 0.5, 3), ev(names["write"], 3.6, 0.5),
               ev(names["decode"], 4.2, 4), ev(names["sample"], 8.3, 0.2),
               ev(names["decode"], 16, 5), ev(names["sample"], 21.1, 0.2),
               ev(names["prefill"], 25.5, 2), ev(names["write"], 27.6, 0.5)]
    ops = [ev(f"%fusion.{i} = bf16[4] fusion(bf16[4] %p)", e.start / MS,
              e.dur / MS) for i, e in enumerate(modules)]
    ticks = [harness.Tick(0.0, 0.010, [64], [65, 30], True),
             harness.Tick(0.015, 0.023, [], [66, 31], True),
             harness.Tick(0.025, 0.029, [32], [], True)]
    return harness.Run(spec=smoke.spec("mamba2-chat"), seconds=1.0,
                       n_slots=4, ticks=ticks,
                       peak=costs.peaks("TPU v5 lite"),
                       trace=tr.Trace({DEV: ops}, {DEV: modules}, spans),
                       device=DEV)


def test_decode_exec_ms_is_the_mean_over_decoding_ticks():
    # 4 and 5 ms in the two ticks that decode; the sampler is not decode
    assert harness.metric_reader("decode_exec_ms")(record(NEW)) \
        == pytest.approx(4.5)
    assert harness.metric_reader("decode_exec_ms.itl_p99")(record(NEW)) \
        == pytest.approx(4.5)


def test_prefill_exec_ms_per_ktok_counts_prefill_and_its_write():
    # (3 + 0.5) + (2 + 0.5) ms for 96 prompt tokens
    assert harness.metric_reader("prefill_exec_ms_per_ktok")(record(NEW)) \
        == pytest.approx(6.0 / 0.096)


@pytest.mark.parametrize("metric", ["decode_exec_ms",
                                    "prefill_exec_ms_per_ktok"])
def test_a_program_without_named_executables_reads_nothing(metric):
    assert harness.metric_reader(metric)(record(OLD)) is None
    r = record(NEW)
    r.trace = None
    assert harness.metric_reader(metric)(r) is None


def test_without_the_program_prefix_nothing_is_read(monkeypatch):
    """Run over a program that has no ``repro.tracing``, the readers give
    nothing and raise nothing."""
    monkeypatch.setattr(executables, "module_prefix", lambda: None)
    for metric in ("decode_exec_ms", "prefill_exec_ms_per_ktok"):
        assert harness.metric_reader(metric)(record(NEW)) is None


@pytest.mark.parametrize("metric", [
    "idle_share", "host_idle_ms_per_tick", "decode_step_ms",
    "prefill_ms_per_ktok", "decode_step_mfu", "slot_occupancy", "mfu"])
def test_the_names_leave_every_earlier_reader_as_it_was(metric):
    read = harness.metric_reader(metric)
    before, after = read(record(OLD)), read(record(NEW))
    assert before is not None
    assert after == before


def test_the_program_names_what_the_readers_look_for():
    """The lowered decode and prefill of the cell's engine are the modules
    the readers match."""
    import jax.numpy as jnp

    spec = smoke.spec("mamba2-chat")
    model, params = harness.build(spec, 0)
    engine = harness.make_engine(spec, model, params)
    prefix = executables.module_prefix()
    toks = jnp.zeros((spec.cell["n_slots"], 1), jnp.int32)
    text = engine._decode.lower(engine.params, engine.cache, toks).as_text()
    assert f"module @{prefix}decode " in text
    text = engine._prefill.lower(
        engine.params, {"tokens": jnp.zeros((1, 16), jnp.int32)}).as_text()
    assert f"module @{prefix}prefill " in text


def test_program_spans_stay_out_of_the_benchmark_spans(tmp_path):
    """A trace in which the engine's own ``serve.*`` spans nest inside the
    benchmark's ``bench.tick`` loads with only the ``bench.*`` spans, one
    per tick."""
    import jax

    from repro.serve.request import Request

    spec = smoke.spec("mamba2-chat")
    model, params = harness.build(spec, 0)
    engine = harness.make_engine(spec, model, params)
    harness.warm_up(engine, [16], spec.model["vocab"])
    engine.start_run()
    engine.submit(Request(uid=0, prompt=tuple(range(16)), max_new_tokens=3))
    results, n = [], 0
    with jax.profiler.trace(str(tmp_path)):
        while not engine.scheduler.done:
            with jax.profiler.TraceAnnotation("bench.tick"):
                engine.tick(results)
            n += 1
    trace = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    assert [s.name for s in trace.spans] == ["bench.tick"] * n
