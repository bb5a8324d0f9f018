"""Each fault a training cell can have, planted under the timed path on
four CPU devices, comes out not correct."""
import pytest

from test_bench_train_control import train_case


@pytest.mark.parametrize("fault", ["state-unchanged", "half-batch",
                                   "exchange-left-out"])
def test_planted_fault_is_not_correct(fault):
    r = train_case("--fault", fault)
    assert not r["correct"], r["checks"]
