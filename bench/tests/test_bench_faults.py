"""The output check fails when the timed path is broken underneath:
the tiny cell is driven on the CPU with each fault of ``faults.py``
planted in the engine, and ``correct`` has to come out false. On the
chip, at the cells' size, ``control.py --fault`` reads the same faults
(PERF.md)."""
import pytest

import bench_tiny
import faults


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = bench_tiny.run(bench_tiny.cell())
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap_mean"]["value"] > \
        res["checks"]["gap_mean"]["limit"]
