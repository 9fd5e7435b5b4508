"""``correct`` on the small cells: true for the served path as it is,
false with each fault a one-chip serving cell can have planted under the
path the window drives."""

import pytest

from bench import harness
from bench.tests import faults, smoke

WORKLOADS = ["zamba2-chat", "mamba2-chat"]


def run(workload, fault=None):
    hook = faults.FAULTS[fault] if fault else None
    return harness.run(workload, smoke.SEED, smoke.SECONDS, False,
                       require_chip=False, spec=smoke.spec(workload),
                       engine_hook=hook)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_not_correct(workload, fault):
    out = run(workload, fault)
    assert not out["correct"], out["checks"]
