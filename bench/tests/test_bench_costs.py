"""The benchmark's operation and byte counts against hand counts at the
zamba2-1.2b and mamba2-370m shapes, and the table of peaks."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import costs  # noqa: E402


def config(name):
    path = os.path.join(ROOT, "bench", "configs", f"{name}.json")
    with open(path) as f:
        return json.load(f)["model"]


ZAMBA2 = config("zamba2-1.2b")
MAMBA2 = config("mamba2-370m")


def test_paged_attention_call_zamba2():
    # two live slots attending 17 and 1 positions: 2 + 1 pages of 16
    flops, nbytes = costs.paged_attention_call(ZAMBA2, [17, 1], 16)
    assert flops == 4 * 32 * 128 * 18
    assert nbytes == 3 * 16 * 32 * 128 * 2 * 2 + 2 * 2 * 32 * 128 * 2


def test_shared_block_projections_zamba2():
    shapes = costs.shared_block_projections(ZAMBA2)
    # q, k, v 2048 -> 32 heads of 128, o back to 2048; the SwiGLU's three
    assert sum(k * n for k, n in shapes) == 4 * 2048 * 4096 + 3 * 2048 * 8192
    assert costs.n_shared_applications(ZAMBA2) == 6
    assert costs.n_shared_applications(MAMBA2) == 0


def test_matmul_params_per_token():
    # zamba2: 38 mixers of 2048 x (2*4096 + 2*64 + 64) + 4096 x 2048,
    # and 6 applications of the shared block's 83,886,080 weights
    assert costs.matmul_params_per_token(ZAMBA2) == \
        38 * 25_559_040 + 6 * 83_886_080
    # mamba2: 48 mixers of 1024 x (2*2048 + 2*128 + 32) + 2048 x 1024
    assert costs.matmul_params_per_token(MAMBA2) == 48 * 6_586_368


def test_token_flops_mamba2():
    got = costs.token_flops(MAMBA2, 0, logits=True)
    ssm = 2 * 4 * (2048 + 256) + 4 * 32 * 64 * 128 + 2 * 32 * 128
    assert got == 2 * 316_145_664 + 48 * ssm + 2 * 1024 * 50280


def test_token_flops_zamba2_attention_grows_with_position():
    a = costs.token_flops(ZAMBA2, 99, logits=False)
    b = costs.token_flops(ZAMBA2, 100, logits=False)
    assert b - a == 6 * 4 * 32 * 128


@pytest.mark.parametrize("cfg", [ZAMBA2, MAMBA2], ids=["zamba2", "mamba2"])
@pytest.mark.parametrize("n", [1, 64, 2048])
def test_prefill_flops_is_the_sum_over_tokens(cfg, n):
    want = sum(costs.token_flops(cfg, p, logits=p == n - 1)
               for p in range(n))
    assert costs.prefill_flops(cfg, n) == pytest.approx(want, rel=1e-12)


def test_least_time_takes_the_binding_bound():
    peak = costs.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12
    assert peak["int8_ops"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert costs.least_time_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert costs.least_time_s(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")
