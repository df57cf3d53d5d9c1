"""A tiny copy of the benchmark (same files, small sizes) for CPU tests.

Run with ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests``; the Pallas
kernel runs in interpret mode on the CPU.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]

# sizes a CPU test can hold; every other setting is the cell's own
TINY_CONFIG = {"n_agents": 8, "batch_m": 4}
TINY_TRAFFIC = {"agent_blocks": 3}
TINY_MC = {"mc_runs": 3}
TINY_ROUNDS = 3


def make_tiny(root: str) -> str:
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name in os.listdir(os.path.join(root, "perfbench", "configs")):
        path = os.path.join(root, "perfbench", "configs", name)
        cfg = json.load(open(path))
        cfg.update(TINY_CONFIG)
        if cfg["mc_runs"] > 1:
            cfg.update(TINY_MC)
        json.dump(cfg, open(path, "w"))
    for name in os.listdir(os.path.join(root, "perfbench", "traffic")):
        path = os.path.join(root, "perfbench", "traffic", name)
        t = json.load(open(path))
        for k, v in TINY_TRAFFIC.items():
            if k in t:
                t[k] = v
        if t["entry"] == "monte_carlo":
            t["rounds"] = TINY_ROUNDS
        json.dump(t, open(path, "w"))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("checkout")))
