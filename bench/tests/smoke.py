"""A small cell for the CPU tests: the two configurations at smoke widths
under a short bursty mix, built as a ``harness.Spec`` without files."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

#: a seed beyond 32 bits, as a benchmark run's may be, and a short window
SEED = 2 ** 32 + 2 ** 31 + 3
SECONDS = 1.5

MODELS = {
    "zamba2-chat": {
        "n_layers": 3, "d_model": 64, "vocab": 257, "n_heads": 4,
        "n_kv_heads": 4, "head_dim": 32, "rope_theta": 10000.0, "d_ff": 128,
        "d_state": 16, "headdim": 16, "n_groups": 1, "d_conv": 4,
        "expand": 2, "ssd_chunk": 8, "attn_every": 1,
        "tie_embeddings": False, "param_dtype": "float32",
        "compute_dtype": "bfloat16", "kv_cache_dtype": "bfloat16"},
    "mamba2-chat": {
        "n_layers": 2, "d_model": 64, "vocab": 257, "d_state": 16,
        "headdim": 16, "n_groups": 1, "d_conv": 4, "expand": 2,
        "ssd_chunk": 8, "tie_embeddings": True, "param_dtype": "float32",
        "compute_dtype": "bfloat16"},
}

#: widths at which int8 rounding already reads above bf16's on the CPU (at
#: 64 wide a row's int8 step is as fine as bf16's rounding over the layers)
WIDER = {
    "zamba2-chat": dict(n_layers=6, d_model=256, vocab=2048, n_heads=4,
                        n_kv_heads=4, head_dim=64, d_ff=1024, d_state=32,
                        headdim=32, attn_every=3, ssd_chunk=16),
    "mamba2-chat": dict(n_layers=8, d_model=256, vocab=2048, d_state=32,
                        headdim=32, ssd_chunk=16),
}

MIX = {"arrivals": {"poisson": True, "burst_size": 4, "burst_span_s": 0.05,
                    "burst_share": 0.5},
       "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64,
                  "ladder": [16, 32, 64]},
       "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 48}}


def spec(workload: str, **model) -> "harness.Spec":
    """The cell ``workload`` of ``BENCHMARK.json`` at smoke size."""
    s = harness.load_spec(workload)
    s.config = dict(s.config, model=dict(MODELS[workload], **model))
    s.cell = dict(s.cell, n_slots=4, max_len=128, n_blocks=24,
                  rate_rps=12.0,
                  check=dict(s.cell["check"], batch=2, max_logit_gap=0.12))
    s.mix = copy.deepcopy(MIX)
    return s
