"""The rule that turns calibration readings into a limit."""
import pytest

from bench.calibrate import limits


def _r(loss, grad, change):
    return {"loss_gap": [loss, ""], "grad_gap": [grad, ""],
            "change_gap": [change, ""]}


def test_limits_rule():
    readings = {
        "program": {1: _r(1e-5, 2e-3, 1e-3), 2: _r(2e-5, 1e-3, 4e-3)},
        "control": {1: _r(5e-5, 7e-3, 1.0), 2: _r(4e-5, 8e-3, 0.9)},
        "half_batch": {1: _r(1e-5, 0.3, 0.02), 2: _r(1e-5, 0.25, 0.03)},
        "no_sync": {1: _r(1e-5, 2e-3, 0.2), 2: _r(1e-5, 2e-3, 0.3)},
    }
    got = limits(readings)
    # loss: the control reads 2x the lower reading, under 3x: no upper
    assert got["loss_gap"]["lower"] == 2e-5
    assert got["loss_gap"]["upper"] is None
    assert got["loss_gap"]["limit"] is None
    # grad: control 7e-3 >= 3 x 2e-3; half batch 0.25 >= 10x; the least
    assert got["grad_gap"]["upper"] == ("control", 7e-3)
    # change: no_sync 0.2 >= 10 x 4e-3 and under the control's 0.9
    assert got["change_gap"]["upper"] == ("no_sync", 0.2)
    lim = got["change_gap"]["limit"]
    assert 4e-3 < lim < 0.2
    assert lim == pytest.approx(4e-3 ** (1 / 3) * 0.2 ** (2 / 3))
