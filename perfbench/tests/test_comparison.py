"""The comparison passes the program as it is and fails it with a fault
planted underneath the timed path, and fails the float32 reference's
bf16x3 control put in the program's place.  A run here skips only the
look for a chip."""
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

ONE_CHIP = ["fleet.full", "paper.mc20", "fleet.service_p10"]
# the faults each cell can have (the exchange between chips: fleet.mesh4)
FAULTS = ["frozen_state", "half_batch", "altered_answer", "altered_gain"]


def _line(root, capsys, workload, seed):
    from perfbench import run

    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0.01", "--trace", "0"],
                    root=root, require_tpu=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(tiny_root, capsys, workload):
    line = _line(tiny_root, capsys, workload, 2**31 + 7)
    assert line["correct"] is True, line["checks"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_planted_fault_is_not_correct(tiny_root, capsys, workload, fault):
    from perfbench import faults

    with faults.FAULTS[fault]():
        line = _line(tiny_root, capsys, workload, 2**31 + 11)
    assert line["correct"] is False
    assert line["failed"] >= 1


def test_altered_mask_is_not_correct(tiny_root, capsys):
    from perfbench import faults

    with faults.altered_mask():
        line = _line(tiny_root, capsys, "fleet.service_p10", 2**31 + 13)
    assert line["correct"] is False
    assert line["checks"]["mask_miss"]["number"] > 0


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload):
    """The bf16x3 control fails the cell's own limits at its own sizes, the
    fleet cut to 2,000 agents (the control's gap is a bias of every agent's
    gradient, so it does not shrink with fewer agents)."""
    import jax

    from perfbench import compare
    from perfbench.harness import Benchmark

    jax.config.update("jax_default_matmul_precision", "highest")
    bench = Benchmark(REPO)
    work = bench.workload(workload)
    limits = bench.limits(work)
    for seed in (5, 6, 7):
        cell = bench.cell(work, seed)
        cell.config = dict(cell.config,
                           n_agents=min(cell.config["n_agents"], 2000))
        cell.build()
        prior = {}
        answers = [a for i in range(2)
                   for a in compare.control_answers(cell, i, prior)]
        gaps = compare.check(cell, answers, limits)
        assert not compare.is_correct(compare.verdict(gaps, limits), limits)


MESH = """
import json, sys
sys.path[:0] = [{repo!r}, {repo!r} + "/src"]
from perfbench import faults, run
fault = sys.argv[1]
ctx = faults.FAULTS[fault]() if fault != "sound" else None
if ctx: ctx.__enter__()
run.main(["--workload", "fleet.mesh4", "--seed", "4294967301",
          "--seconds", "0.01", "--trace", "0"], root={root!r},
         require_tpu=False)
"""


@pytest.mark.parametrize("fault", ["sound", "no_exchange"] + FAULTS)
def test_four_chip_cell(tiny_root, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", MESH.format(repo=REPO, root=tiny_root), fault],
        capture_output=True, text=True, timeout=600, env=env)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (fault == "sound"), line["checks"]
