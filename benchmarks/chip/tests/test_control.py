"""The control, the plain reference computed on the design rounded to
bfloat16, comes out not correct where the configuration as run comes out
correct: on three datasets, for each loss, at a size the CPU holds."""
import pytest

import control
from test_harness import tiny


@pytest.mark.parametrize("loss", ["squared", "logistic"])
def test_control_fails_where_the_program_passes(loss):
    cfg = tiny(loss)
    for seed in (21, 22, 23):
        ok = control.readings(cfg, seed, "fp32")
        low = control.readings(cfg, seed, "bf16")
        assert ok["correct"], ok
        assert not low["correct"], low
