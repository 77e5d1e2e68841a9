"""The traffic generator, and each mix end to end at a tiny size with
the program's Pallas kernels in interpret mode."""
import json

import numpy as np
import pytest

from bench import drive, harness
from bench.tests import tiny


def test_arrivals_same_gaps_for_every_seed():
    tr = {"rate_qps": 50}
    a, b = drive.arrivals(tr, 4.0, 1), drive.arrivals(tr, 4.0, 2**40 + 3)
    assert len(a) == len(b) == 200
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)), rtol=1e-9)
    assert np.all(np.diff(a) >= 0) and a[0] > 0
    assert a[-1] == pytest.approx(4.0)


def test_arrivals_with_bursts():
    tr = {"rate_qps": 20, "burst": {"factor": 4, "on_s": 1, "period_s": 4}}
    a = drive.arrivals(tr, 8.0, 5)
    # two periods of 1 s at 80/s and 3 s at 20/s
    assert len(a) == 280
    assert abs(int(np.sum((a % 4) < 1)) - 160) <= 5


@pytest.mark.parametrize("cell", [c["name"] for c in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_mix_end_to_end_interpret(cell):
    from repro.kernels import ops
    s = tiny.interpret_spec(cell)
    with ops.backend("interpret"):
        line = tiny.run(s, seconds=0.3)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in s.end_to_end}
    assert line["compiles_in_window"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())
