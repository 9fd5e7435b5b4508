"""The one traffic generator: turns a mix's parameters into a schedule.

A mix is a JSON file ``bench/traffic/<name>.json``; the cell gives the mean
offered rate. Arrivals are open loop: a Poisson stream plus bursts of
``burst_size`` requests within ``burst_span_s``, the bursts carrying
``burst_share`` of the requests and starting at Poisson times. Prompt and output lengths are lognormal
(``median``, ``sigma``), clipped to ``[min, max]``; prompts are rounded up to
the mix's ``ladder`` so that every prefill shape is known before the window.

Every seed gets the same work: the lengths are the distribution's quantiles
at ``(i + 1/2) / n`` and the gaps between arrivals the exponential
distribution's, so that only their order and the token ids come from the
seed; and each burst gets one length from every stratum, so that bursts
carry alike work. Two seeds then differ in arrangement, not in amount of
work.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Item:
    uid: int
    due_s: float        # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the lognormal's quantiles, clipped, on the ladder."""
    unit = statistics.NormalDist()
    out = []
    for q in _quantiles(n):
        x = spec["median"] * math.exp(spec["sigma"] * unit.inv_cdf(q))
        x = int(min(max(round(x), spec["min"]), spec["max"]))
        ladder = spec.get("ladder")
        if ladder:
            x = ladder[min(bisect.bisect_left(ladder, x), len(ladder) - 1)]
        out.append(x)
    return out


def _gaps(n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps at their quantiles, scaled to sum to
    ``span * n / (n + 1)`` so that the ``n`` arrivals fall inside ``span``."""
    if n == 0:
        return np.zeros(0)
    g = np.array([-math.log1p(-q) for q in _quantiles(n)])
    return g * (span * n / (n + 1) / g.sum())


def counts(mix: dict, rate_rps: float, seconds: float):
    """(Poisson requests, bursts) in a window of ``seconds``: the mean rate
    times the window, of which ``burst_share`` comes in bursts."""
    arr = mix["arrivals"]
    total = int(round(rate_rps * seconds))
    size = arr.get("burst_size", 0)
    n_bursts = int(round(arr.get("burst_share", 0.0) * total / size)) \
        if size else 0
    return total - n_bursts * size, n_bursts
def _deal(lengths, members, rng) -> List[int]:
    """Give each burst one length from every ``1/size`` stratum of the
    sorted ``lengths`` (in a random order within the burst), and the rest
    to the Poisson requests in a random order: every burst then carries
    about the same work, whatever the seed."""
    size = len(members[0]) if members else 1
    n = len(lengths)
    ranked = sorted(lengths)
    strata = [list(rng.permutation(ranked[s * n // size:(s + 1) * n // size]))
              for s in range(size)]
    out = [0] * n
    for burst in members:
        for s, i in zip(rng.permutation(size), burst):
            out[i] = int(strata[s].pop())
    rest = list(rng.permutation([x for st in strata for x in st]))
    for i in range(n):
        if not any(i in b for b in members):
            out[i] = int(rest.pop())
    return out


def schedule(mix: dict, *, rate_rps: float, seconds: float, vocab: int,
             seed: int) -> List[Item]:
    """The requests due in a window of ``seconds``, sorted by due time."""
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    n_poisson, n_bursts = counts(mix, rate_rps, seconds)
    due = [(float(t), -1) for t in
           np.cumsum(rng.permutation(_gaps(n_poisson, seconds)))]
    if n_bursts:
        size, span = arr["burst_size"], arr["burst_span_s"]
        starts = np.cumsum(rng.permutation(_gaps(n_bursts, seconds - span)))
        offsets = [span * q for q in _quantiles(size)]
        due += [(float(s + o), b) for b, s in enumerate(starts)
                for o in offsets]
    due.sort()
    n = len(due)
    members = [[i for i, (_, b) in enumerate(due) if b == j]
               for j in range(n_bursts)]
    prompts = _deal(lognormal_lengths(mix["prompt"], n), members, rng)
    outputs = _deal(lognormal_lengths(mix["output"], n), members, rng)
    return [Item(uid=i, due_s=due[i][0],
                 prompt=rng.integers(0, vocab, prompts[i], dtype=np.int32),
                 max_new_tokens=outputs[i])
            for i in range(n)]


def prompt_shapes(mix: dict, n: int) -> List[int]:
    """Every prompt length a window of ``n`` requests holds, ascending."""
    return sorted(set(lognormal_lengths(mix["prompt"], n)))
