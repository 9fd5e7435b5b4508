"""One run of one benchmark cell: set-up, a measured window of open-loop
traffic through ``ServeEngine.tick``, the end-to-end or per-layer metrics,
and the comparison with the plain reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is found by name: ``bench/configs/<config>.json`` (with the reference
module it names under ``bench/reference/``), ``bench/traffic/<mix>.json``,
``bench/cells/<workload>.json`` and ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import bisect
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CLOCK = time.monotonic
#: seconds a request due in the window may take after the window closes
DRAIN_S = 60.0
#: the traced part of a ``--trace 1`` window: its last this many seconds
#: (a whole window's trace is too large to read back within a run's time;
#: the trace is written out after the window, where its stall harms no
#: request's timing)
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Spec:
    """A cell and everything it names."""

    workload: dict
    config: dict        # bench/configs/<config>.json
    cell: dict          # bench/cells/<workload>.json
    mix: dict           # bench/traffic/<mix>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Spec(
        workload=w,
        config=json.loads((root / conf["file"]).read_text()),
        cell=json.loads((BENCH / "cells" / f"{workload}.json").read_text()),
        mix=traffic.load_mix(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def reference_module(spec: Spec):
    return importlib.import_module(
        f"bench.reference.{spec.config['reference']}")


def metric_reader(name: str) -> Callable:
    """The reader ``bench/metrics/<name>.py``; a metric split by the
    end-to-end metric it moves (``<reader>.<suffix>``) without a file of its
    own reads with ``<reader>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def seed_key(seed: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax

    path = str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    # the program's own entry points take their cache from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def accelerator(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform not in ("tpu", "gpu") or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


# -- the system under test ---------------------------------------------------

def build(spec: Spec, seed: int):
    """The served model with the benchmark's own weights for ``seed``."""
    import dataclasses as dc

    import jax

    from repro.configs.registry import get_config
    from repro.models.api import build_model

    model_cfg = dict(spec.model)
    cfg = dc.replace(get_config(spec.config["arch"]), **model_cfg)
    model = build_model(cfg)
    ref = reference_module(spec)
    params = jax.block_until_ready(
        jax.jit(lambda k: ref.init(k, model_cfg))(seed_key(seed)))
    want = jax.eval_shape(model.init, seed_key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's weights do not fit the served "
                           "model's parameter layout")
    return model, params


def make_engine(spec: Spec, model, params):
    from repro.serve import ServeEngine

    c = spec.cell
    kw = dict(paged=True, block_size=c["block_size"],
              n_blocks=c.get("n_blocks")) if c["paged"] else {}
    return ServeEngine(model, params, n_slots=c["n_slots"],
                       max_len=c["max_len"], clock=CLOCK, **kw)


def warm_up(engine, prompt_lens, vocab: int) -> None:
    """Compile what the window will run: every decode bucket and the
    sampler (the engine's own warm-up), and a prefill at every prompt
    length of the traffic, through the same ticks the window drives."""
    from repro.serve.request import Request

    engine.start_run(warmup=True)
    results: list = []
    rng = np.random.default_rng(0)
    for i, n in enumerate(prompt_lens):
        engine.submit(Request(uid=-1 - i, max_new_tokens=2,
                              prompt=tuple(int(t) for t in
                                           rng.integers(0, vocab, n))))
        while not engine.scheduler.done:
            engine.tick(results)


@dataclasses.dataclass
class Tick:
    start: float                    # host clock, seconds after window start
    end: float
    admitted: List[int]             # prompt lengths prefilled in this tick
    decoded: List[int]              # attended length of each decoded slot
    traced: bool


@dataclasses.dataclass
class Run:
    """What one window recorded: what the per-layer readers read."""

    spec: Spec
    seconds: float
    n_slots: int
    ticks: List[Tick]
    peak: dict                      # the chip's published peaks
    trace: Optional[object] = None  # bench.trace.Trace of the traced part
    device: Optional[str] = None    # its device plane

    def traced_ticks(self):
        """``(tick, span)`` for every tick the profiler saw, or None when
        the run was not traced or its spans do not match its ticks."""
        if self.trace is None:
            return None
        ticks = [t for t in self.ticks if t.traced]
        spans = [s for s in self.trace.spans if s.name == "bench.tick"]
        if not ticks or len(ticks) != len(spans):
            log(f"{len(ticks)} ticks traced but {len(spans)} tick spans in "
                "the trace: the readers of ticks read nothing")
            return None
        return list(zip(ticks, spans))

    def busy(self):
        """The device's busy intervals, with prefix sums."""
        from bench import trace as tr

        if self._busy is None:
            self._busy = tr.Busy(tr.busy_intervals(self.trace, self.device))
        return self._busy

    def device_events(self, kind: str = "modules"):
        """For every traced tick, ``(tick, events)``: the device's
        executables (``kind="modules"``) or operations (``"ops"``) that
        began inside its span. A tick ends by pulling its tokens to the
        host, so the work it launched has finished inside it."""
        pairs = self.traced_ticks()
        if not pairs:
            return None
        evs = getattr(self.trace, kind).get(self.device, [])
        starts = [e.start for e in evs]
        out = []
        for tick, span in pairs:
            i = bisect.bisect_left(starts, span.start)
            j = bisect.bisect_left(starts, span.end)
            out.append((tick, evs[i:j]))
        return out

    def step_time(self):
        """``(decode_ns, prefill_ns, ticks)`` of the traced ticks: the
        decode step's device time as the median of the executables' time in
        the ticks that admit nothing, and prefill's as all the executables'
        time less that median for every decoding tick. Medians and totals
        keep a prefill event that the trace's clocks put into a
        neighbouring tick from counting as decode; per tick, the decode
        step is one executable of several (one per live-block bucket)."""
        ticks = self.device_events()
        if not ticks:
            return None
        quiet = [sum(e.dur for e in evs) for t, evs in ticks
                 if t.decoded and not t.admitted]
        if not quiet:
            return None
        decode = statistics.median(quiet)
        total = sum(e.dur for _, evs in ticks for e in evs)
        n_decode = sum(1 for t, _ in ticks if t.decoded)
        return decode, total - decode * n_decode, [t for t, _ in ticks]

    _busy: Optional[object] = None


def serve_window(spec: Spec, engine, items, seconds: float, *,
                 trace_dir: Optional[str] = None):
    """Offer ``items`` open loop for ``seconds``, then let what is in flight
    finish (at most :data:`DRAIN_S`). Returns ``(results, per-request
    timelines, ticks, lateness)``."""
    import jax

    from repro.serve.request import Request

    sched = engine.scheduler
    prompt_len = {it.uid: len(it.prompt) for it in items}
    times: Dict[int, List[float]] = {it.uid: [] for it in items}
    ticks: List[Tick] = []
    results: list = []
    lateness: List[float] = []
    tracing = False
    nxt = 0
    t0 = CLOCK()
    engine.start_run(t_origin=t0)

    def submit_due(now):
        nonlocal nxt
        while nxt < len(items) and items[nxt].due_s <= now - t0:
            it = items[nxt]
            engine.submit(Request(uid=it.uid, prompt=tuple(it.prompt.tolist()),
                                  max_new_tokens=it.max_new_tokens,
                                  arrival_s=it.due_s))
            lateness.append(now - t0 - it.due_s)
            nxt += 1

    def tick():
        n_res = len(results)
        n_adm = len(sched.admission_log)
        ts = CLOCK()
        with jax.profiler.TraceAnnotation("bench.tick"):
            engine.tick(results)
        te = CLOCK()
        admitted = [prompt_len[u] for u, _, _ in sched.admission_log[n_adm:]]
        decoded = [r.uid for r in sched.active.values()]
        decoded += [r.uid for r in results[n_res:]]
        ctx = []
        for uid in decoded:
            times[uid].append(te - t0)
            ctx.append(prompt_len[uid] + len(times[uid]))
        ticks.append(Tick(ts - t0, te - t0, admitted, ctx, tracing))

    end = t0 + seconds
    while True:
        now = CLOCK()
        if now >= end:
            break
        if trace_dir is not None and not tracing \
                and now >= end - min(TRACE_SECONDS, seconds / 2):
            jax.profiler.start_trace(trace_dir)
            tracing = True
        submit_due(now)
        if sched.done:
            wake = t0 + items[nxt].due_s if nxt < len(items) else end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(wake, end) - CLOCK()))
            continue
        tick()
    if tracing:
        jax.profiler.stop_trace()
        tracing = False
    submit_due(CLOCK())
    while not sched.done and CLOCK() < end + DRAIN_S:
        tick()
    ff = getattr(engine, "_fast_forward_s", 0.0)
    if ff:
        raise RuntimeError(f"the engine fast-forwarded its clock by {ff} s: "
                           "requests would be timed on a clock that skipped")
    return results, times, ticks, lateness


# -- end-to-end metrics -------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (``numpy.percentile``'s default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


#: a tail by name: ``ttft_p<q>_ms`` over every request due in the window,
#: ``itl_p<q>_ms`` over every gap between consecutive tokens of a request
TAIL = re.compile(r"^(ttft|itl)_p([0-9]+)_ms$")
#: the tails every run logs on standard error
LOGGED = ("ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p90_ms",
          "itl_p95_ms", "itl_p99_ms")


def end_to_end(items, results, times, seconds: float, setup_s: float,
               names=LOGGED):
    """``output_tok_s``, ``setup_s`` and the tails that ``names`` asks for
    (see :data:`TAIL`); the result line carries those ``BENCHMARK.json``
    lists, the standard error all of them.

    A request with no first token counts as waiting until the run gave up
    (the window plus the drain): it misses every limit."""
    done = {r.uid: r for r in results}
    out_tokens = 0
    samples = {"ttft": [], "itl": []}
    for it in items:
        r = done.get(it.uid)
        first = r.metrics.first_token_s if r is not None else None
        stamps = ([first] if first is not None else []) + times[it.uid]
        out_tokens += sum(1 for t in stamps if t <= seconds)
        samples["ttft"].append(
            (first if first is not None else seconds + DRAIN_S) - it.due_s)
        samples["itl"].extend(np.diff(stamps).tolist())
    out = {"output_tok_s": out_tokens / seconds, "setup_s": setup_s}
    for name in names:
        m = TAIL.match(name)
        if m is None:
            raise KeyError(f"no end-to-end metric {name!r}")
        values = samples[m.group(1)]
        out[name] = percentile(values, int(m.group(2))) * 1e3 \
            if values else 0.0
    return out, len(samples["ttft"]), len(samples["itl"])


# -- correctness --------------------------------------------------------------

def check_sample(items, results, cell: dict, seed: int):
    """The finished requests whose served tokens are compared: the one with
    the most output, then others drawn from the seed, until at least
    ``check.min_requests`` requests and ``check.tokens`` tokens are in, or
    ``check.max_requests`` requests."""
    by_uid = {it.uid: it for it in items}
    done = sorted(results, key=lambda r: r.uid)
    if not done:
        return []
    first = max(done, key=lambda r: (r.tokens.size, -r.uid))
    rest = [r for r in done if r.uid != first.uid]
    order = np.random.default_rng(seed).permutation(len(rest))
    chosen, n_tok = [first], first.tokens.size
    chk = cell["check"]
    for i in order:
        if len(chosen) >= chk["max_requests"] or (
                n_tok >= chk["tokens"]
                and len(chosen) >= chk["min_requests"]):
            break
        chosen.append(rest[i])
        n_tok += rest[i].tokens.size
    return [(by_uid[r.uid].prompt, np.asarray(r.tokens)) for r in chosen]


def reference_gaps(spec: Spec, params, sample, *, control=None):
    """For every compared token: how far the float32 reference's logit of
    the served token lies below its best (``control="int8"``: of the token
    that the reference computed in int8 puts first instead). Runs in
    batches of
    ``check.batch`` sequences padded to ``max_len``."""
    import jax
    import jax.numpy as jnp

    from bench.reference import common

    ref = reference_module(spec)
    cfg = spec.model
    S = spec.cell["max_len"]
    T = spec.mix["output"]["max"]
    R = spec.cell["check"]["batch"]

    @jax.jit
    def gaps(params, tokens, positions, served):
        h = ref.hidden(params, tokens, cfg)
        logits = common.logits_at(h, ref.unembedding(params), positions)
        if control:
            h8 = ref.hidden(params, tokens, cfg, quant=control)
            served = jnp.argmax(common.logits_at(
                h8, ref.unembedding(params), positions, quant=control), -1)
        return common.gaps(logits, served)

    out = []
    for b in range(0, len(sample), R):
        part = sample[b:b + R]
        tokens = np.zeros((R, S), np.int32)
        pos = np.zeros((R, T), np.int32)
        served = np.zeros((R, T), np.int32)
        for j, (prompt, toks) in enumerate(part):
            seq = np.concatenate([prompt, toks[:-1]])
            tokens[j, :seq.size] = seq
            n = toks.size
            pos[j, :n] = prompt.size - 1 + np.arange(n)
            served[j, :n] = toks
        g = np.asarray(gaps(params, tokens, pos, served))
        out.extend(g[j, :toks.size] for j, (_, toks) in enumerate(part))
    return out


# -- one run -------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, t_start: Optional[float] = None,
        require_chip: bool = True, spec: Optional[Spec] = None,
        engine_hook=None) -> dict:
    """One run of the cell; returns the result line's object.

    ``require_chip``, ``spec`` and ``engine_hook`` are for the tests, which
    run a small cell on the CPU and break the served path underneath to see
    ``correct`` turn false."""
    t_start = CLOCK() if t_start is None else t_start
    spec = spec or load_spec(workload, root)
    import jax

    if require_chip:
        devices = accelerator(spec.workload["chips"])
    else:
        devices = jax.devices()[:1]
    dev = devices[0]
    if require_chip:
        use_compile_cache(root)
    from bench import costs

    peak = costs.peaks(dev.device_kind) if require_chip else None
    items = traffic.schedule(spec.mix, rate_rps=spec.cell["rate_rps"],
                             seconds=seconds, vocab=spec.model["vocab"],
                             seed=seed)
    model, params = build(spec, seed)
    engine = make_engine(spec, model, params)
    if engine_hook is not None:
        engine_hook(engine)
    warm_up(engine, sorted({len(it.prompt) for it in items}),
            spec.model["vocab"])
    setup_s = CLOCK() - t_start
    log(f"{spec.name}: {len(items)} requests due in {seconds} s; set-up "
        f"{setup_s:.2f} s on {dev.device_kind}")

    compiles = _count_compiles()
    trace_dir = None
    if trace:
        trace_dir = str(root / "bench_out" / "trace" / f"{spec.name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    results, times, ticks, lateness = serve_window(
        spec, engine, items, seconds, trace_dir=trace_dir)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"compilations inside the window and drain: {compiles[0]}")
    log(f"generator lateness: p50 {percentile(lateness, 50) * 1e3:.3f} ms, "
        f"p99 {percentile(lateness, 99) * 1e3:.3f} ms, "
        f"max {max(lateness) * 1e3:.3f} ms over {len(lateness)} requests")
    tails = {m["name"] for m in spec.end_to_end if TAIL.match(m["name"])}
    e2e, n_ttft, n_gaps = end_to_end(items, results, times, seconds,
                                     setup_s, sorted(tails | set(LOGGED)))
    log(f"{len(results)}/{len(items)} requests finished; {n_ttft} TTFT "
        f"samples, {n_gaps} inter-token gaps, {len(ticks)} ticks")
    log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in
                                   sorted(e2e.items())))

    record = Run(spec=spec, seconds=seconds, n_slots=spec.cell["n_slots"],
                 ticks=ticks, peak=peak)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace:
        from bench import trace as tr

        path = tr.find_xplane(trace_dir)
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        record.trace = tr.load_xplane(path)
        record.device = _device_plane(record.trace, dev)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = tr.busy_intervals(record.trace, record.device)
        lo, hi = _traced_span(record)
        device.update(busy_s=tr.covered(busy, lo, hi) * 1e-9,
                      window_s=(hi - lo) * 1e-9)
        breakdown = {
            "device_ops": tr.top_ops(record.trace, record.device),
            "idle_gaps": tr.labelled_gaps(tr.idle_gaps(busy, lo, hi),
                                          record.trace.spans)}

    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        value = metric_reader(m["name"])(record) if trace \
            else e2e[m["name"]]
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the reference runs once the program's state is gone
    failed = len(items) - len(results)
    del engine
    gc.collect()
    sample = check_sample(items, results, spec.cell, seed)
    t_ref = CLOCK()
    gaps = reference_gaps(spec, params, sample)
    widest = float(max(np.max(g) for g in gaps)) if gaps else math.inf
    n_cmp = int(sum(g.size for g in gaps))
    log(f"reference: {len(sample)} requests, {n_cmp} served tokens compared "
        f"in {CLOCK() - t_ref:.2f} s; {int(sum((g > 0).sum() for g in gaps))}"
        " differ from the reference's best")
    limit = spec.cell["check"]["max_logit_gap"]
    want = {it.uid: it.max_new_tokens for it in items}
    short = sum(1 for r in results if r.tokens.size != want[r.uid])
    checks = {
        "logit_gap": {"value": widest, "limit": limit},
        "unfinished": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
    }
    correct = widest <= limit and failed == 0 and short == 0 and n_cmp > 0
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    out = {"correct": bool(correct), "attempted": len(items),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def _count_compiles():
    """A counter of the XLA compilations from now on (a persistent-cache
    hit is not one)."""
    import jax

    seen = [0]

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def _device_plane(trace, dev) -> str:
    name = f"/device:{dev.platform.upper()}:{dev.id}"
    if name in trace.devices:
        return name
    if not trace.devices:
        raise RuntimeError("the trace holds no device events")
    return trace.devices[0]


def _traced_span(record: Run):
    """The traced window: from the first to the last benchmark span."""
    spans = record.trace.spans
    return spans[0].start, max(s.end for s in spans)
