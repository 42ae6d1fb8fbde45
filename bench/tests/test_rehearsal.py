"""Each cell's code path, rehearsed on the CPU at toy widths with the
Pallas kernels in interpret mode: set-up, the window, the metric readers
and the comparison with the reference.  The numbers are CPU numbers and
are never reported; the entry point itself refuses to run off the chip."""
import subprocess
import sys
import time

import jax
import pytest

from bench import run
from bench.tests import small


@pytest.mark.parametrize("name", sorted(small.CELLS))
def test_cell_runs_and_is_correct(name):
    cell = small.cell(name)
    res = run.run_cell(cell, 2**33 + 1, 2.0, False, small.PEAKS,
                       jax.devices()[:1], time.perf_counter())
    assert res["correct"] is True, res["check"]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("name", ["wtab-batch", "tl1-batch"])
def test_control_comes_out_wrong(name):
    """The reference one precision step down (int4 tables, int4 codes) in
    the program's place is judged as a run is, and is not correct where
    the program's own tokens are."""
    cell = small.cell(name)
    seed = 5
    _, sent = run.measure(cell, seed, 1.0, False, small.PEAKS, jax.devices()[:1],
                          time.perf_counter())
    ok, got = run.compare(cell, seed, sent)
    control_ok, control = run.compare(cell, seed, sent, control=True)
    assert ok is True, got
    assert control_ok is False, control
    assert control["logit_gap"]["value"] > control["logit_gap"]["limit"]


def test_entry_point_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tl1-batch", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
