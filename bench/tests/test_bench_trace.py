"""The trace-to-metrics reduction on a synthesised trace: busy time as the
union of device operations, idle gaps labelled by the host span they fall
in, kernel time by name, and the per-layer readers that build on them."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import costs, harness  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.tests import smoke  # noqa: E402

DEV = "/device:TPU:0"
MS = 1_000_000
#: how the profiler names a Mosaic kernel's custom call: after the function
#: that launches it, with the instruction's text
PAGED = ("%paged_attention.11 = f32[16,32,1,64] custom-call(s32[16,32] %c), "
         'custom_call_target="tpu_custom_call"')
#: a projection kernel's custom call
DOT = ("%dot_moa.9 = f32[16,2048]{1,0:T(8,128)S(1)} custom-call("
       "bf16[16,8192]{1,0:T(8,128)(2,1)S(1)} %a, "
       "bf16[8192,2048]{1,0:T(8,128)(2,1)} %b), "
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       "{bf16[16,8192]{1,0}, bf16[8192,2048]{1,0}}")


def ev(name, start_ms, dur_ms):
    return tr.Event(name, int(start_ms * MS), int(dur_ms * MS))


def synthetic():
    """Two ticks: tick 1 admits a 64-token prompt and decodes, tick 2 only
    decodes. A wait span lies between them."""
    spans = [ev("bench.tick", 0, 10), ev("bench.wait", 10, 5),
             ev("bench.tick", 15, 6)]
    modules = [ev("jit_prefill", 1, 3), ev("jit_decode", 5, 4),
               ev("jit_decode", 16, 4)]
    ops = [ev("%fusion.1 = bf16[4] fusion(bf16[4] %p)", 1, 3),
           ev(PAGED, 5, 1),
           ev("%fusion.2 = bf16[4] fusion(bf16[4] %q)", 5.5, 3.5),
           ev(PAGED, 16, 1),
           ev(DOT, 17, 3),
           ev("%while.3 = (s32[]) while((s32[]) %t), body=%b", 16, 4)]
    return tr.Trace({DEV: ops}, {DEV: modules}, spans)


def test_busy_union_and_idle_gaps():
    t = synthetic()
    busy = tr.busy_intervals(t, DEV)
    assert busy == [(1 * MS, 4 * MS), (5 * MS, 9 * MS), (16 * MS, 20 * MS)]
    b = tr.Busy(busy)
    assert b.within(0, 21 * MS) == 11 * MS
    assert b.within(2 * MS, 6 * MS) == 3 * MS
    assert tr.covered(busy, 0, 21 * MS) == 11 * MS
    gaps = tr.idle_gaps(busy, 0, 21 * MS)
    assert gaps == [(0, MS), (4 * MS, 5 * MS), (9 * MS, 16 * MS),
                    (20 * MS, 21 * MS)]
    longest = tr.labelled_gaps(gaps, t.spans, n=2)
    assert longest[0] == ["bench.tick", pytest.approx(7e-3)]
    assert tr.span_at(t.spans, 12 * MS) == "bench.wait"


def test_kernel_time_by_name():
    t = synthetic()
    paged = tr.kernel_events(t, DEV, "paged_attention")
    assert sum(e.dur for e in paged) == 2 * MS
    assert sum(e.dur for e in tr.kernel_events(t, DEV, "dot_moa")) == 3 * MS
    assert tr.kernel_events(t, DEV, "fusion") == []
    # the while loop holds the kernels: its own time is not an operation's
    assert tr.top_ops(t, DEV, 2) == [["fusion.2", pytest.approx(3.5e-3)],
                                     ["fusion.1", pytest.approx(3e-3)]]


def record():
    spec = smoke.spec("zamba2-chat")
    ticks = [harness.Tick(0.0, 0.010, [64], [65, 30], True),
             harness.Tick(0.015, 0.021, [], [66, 31], True)]
    peak = costs.peaks("TPU v5 lite")
    r = harness.Run(spec=spec, seconds=1.0, n_slots=4, ticks=ticks,
                    peak=peak,
                    trace=synthetic(), device=DEV)
    return r


def test_readers_on_the_synthetic_trace():
    r = record()
    read = {m: harness.metric_reader(m)(r) for m in (
        "idle_share", "host_idle_ms_per_tick", "decode_step_ms",
        "prefill_ms_per_ktok", "paged_attn_roofline", "slot_occupancy")}
    # 11 ms busy in the 21 ms from the first span to the last
    assert read["idle_share"] == pytest.approx(100 * (1 - 11 / 21))
    # tick 1: 10 ms span, 7 ms busy; tick 2: 6 ms span, 4 ms busy
    assert read["host_idle_ms_per_tick"] == pytest.approx((3 + 2) / 2)
    # jit_decode runs in the tick that admits nothing: 4 ms per tick
    assert read["decode_step_ms"] == pytest.approx(4.0)
    # jit_prefill: 3 ms for 64 prompt tokens
    assert read["prefill_ms_per_ktok"] == pytest.approx(3 / 0.064)
    assert read["slot_occupancy"] == pytest.approx(50.0)
    cfg = r.spec.model
    apps = costs.n_shared_applications(cfg)
    least = sum(apps * costs.least_time_s(
        *costs.paged_attention_call(cfg, t.decoded, 16), r.peak)
        for t in r.ticks)
    assert read["paged_attn_roofline"] == pytest.approx(
        100 * least / 2e-3)



def test_readers_read_nothing_without_a_trace():
    r = record()
    r.trace = None
    for m in ("idle_share", "decode_step_ms", "paged_attn_roofline",
              "decode_step_mfu"):
        assert harness.metric_reader(m)(r) is None


def test_spans_that_do_not_match_ticks_read_nothing():
    r = record()
    r.ticks = r.ticks[:1]
    assert harness.metric_reader("decode_step_ms")(r) is None


def test_prefill_placed_in_a_quiet_tick_stays_prefill():
    """The trace's clocks can put a prefill's executable into the next
    tick, one that admitted nothing: it still counts as prefill, while a
    decode executable that only some ticks run (one live-block bucket)
    still counts as decode."""
    r = record()
    spans = [ev("bench.tick", 10 * i, 9) for i in range(8)]
    modules = [ev("jit_decode_a" if i < 5 else "jit_decode_b",
                  10 * i + 5, 2) for i in range(8)]
    modules += [ev("jit_prefill", 10 * i + 1, 3) for i in (0, 2)]
    modules.append(ev("jit_prefill", 51, 3))        # tick 4's, in tick 5
    r.trace = tr.Trace({DEV: []}, {DEV: modules}, spans)
    r.ticks = [harness.Tick(0.01 * i, 0.01 * i + 0.009,
                            [64] if i in (0, 2, 4) else [], [65], True)
               for i in range(8)]
    assert harness.metric_reader("decode_step_ms")(r) == pytest.approx(2.0)
    assert harness.metric_reader("prefill_ms_per_ktok")(r) == \
        pytest.approx(9 / 0.192)
