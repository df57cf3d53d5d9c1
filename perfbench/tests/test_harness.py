"""The harness finds a cell by name, a new cell is new files only, the last
line keeps the contract, and a run without a TPU gives no result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, make_tiny


def _run(root, capsys, workload, seed=3000000001, require_tpu=False,
         seconds=0.01):
    from perfbench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, require_tpu=require_tpu)
    out = capsys.readouterr()
    return rc, out.out, out.err


def test_finds_every_cell_by_name():
    from perfbench.harness import Benchmark

    bench = Benchmark(REPO)
    for w in bench.spec["workloads"]:
        work = bench.workload(w["name"])
        assert bench.config(work)["name"] == w["config"]
        assert bench.traffic(work)["entry"]
        assert set(bench.limits(work)["limits"])
        for kind in ("end_to_end", "per_layer"):
            for m in bench.metrics(kind, work):
                if kind == "per_layer":
                    assert callable(bench.reader(m))
        assert {m["name"] for m in bench.metrics("end_to_end", work)} == {
            "agent_steps_per_s", "setup_s"}
        cell = bench.cell(work, 1)
        assert cell.entry_mod.__name__.endswith(cell.traffic["entry"])
        assert cell.chan.__name__.endswith(cell.config["channel"]["kind"])
        assert callable(cell.ref.agent_grads)


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            p = os.path.join(d, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_only(tmp_path, capsys):
    root = make_tiny(str(tmp_path))
    before = _digest(root)
    json.dump({"entry": "run_jit", "rounds": 1, "agent_blocks": 5},
              open(os.path.join(root, "perfbench/traffic/blocks5.json"), "w"))
    shutil.copy(os.path.join(root, "perfbench/limits/fleet.full.json"),
                os.path.join(root, "perfbench/limits/fleet.blocks5.json"))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["workloads"].append({"name": "fleet.blocks5",
                              "config": "fleet_1e4_rayleigh",
                              "traffic": "blocks5", "chips": 1, "why": "t"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    rc, out, _ = _run(root, capsys, "fleet.blocks5")
    assert rc == 0
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())


NAKAGAMI = '''"""Nakagami-m power gain: Gamma(m, omega/m)."""
import jax
import jax.numpy as jnp


def draw(channel, key, shape):
    return jax.random.gamma(key, channel["m"], tuple(shape), jnp.float32) \
        * (channel["omega"] / channel["m"])


def mean(channel):
    return channel["omega"]
'''


def _add_cell(root, config, workload, limits_from="fleet.full"):
    name = config["name"]
    json.dump(config, open(os.path.join(
        root, "perfbench/configs", name + ".json"), "w"))
    shutil.copy(os.path.join(root, "perfbench/limits", limits_from + ".json"),
                os.path.join(root, "perfbench/limits", workload + ".json"))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": name, "source": "t",
                            "file": f"perfbench/configs/{name}.json",
                            "reduced": [], "why": "t"})
    spec["workloads"].append({"name": workload, "config": name,
                              "traffic": "full", "chips": 1, "why": "t"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))


def test_a_new_channel_is_new_files_only(tmp_path, capsys):
    """A configuration with another channel kind: its file, the channel's
    gains and mean, a limits file and entries; no file there changes."""
    root = make_tiny(str(tmp_path))
    before = _digest(root)
    with open(os.path.join(root, "perfbench/channels/nakagami.py"), "w") as f:
        f.write(NAKAGAMI)
    cfg = json.load(open(os.path.join(
        root, "perfbench/configs/fleet_1e4_rayleigh.json")))
    cfg.update(name="fleet_nakagami", alpha=1e-3,
               channel={"kind": "nakagami", "m": 0.1, "omega": 1.0})
    _add_cell(root, cfg, "nakagami.full")
    rc, out, _ = _run(root, capsys, "nakagami.full")
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())


@pytest.mark.parametrize("group,kind", [("env", "windy"),
                                        ("policy", "GaussianPolicy"),
                                        ("policy", "NoSuchPolicy"),
                                        ("channel", "lognormal")])
def test_an_unknown_kind_is_an_error(tmp_path, capsys, group, kind):
    """A configuration that names a kind the reference, the program or the
    channel files do not have gives no result, never a default."""
    root = make_tiny(str(tmp_path))
    cfg = json.load(open(os.path.join(
        root, "perfbench/configs/fleet_1e4_rayleigh.json")))
    cfg["name"] = "other"
    cfg[group] = dict(cfg[group], kind=kind)
    _add_cell(root, cfg, "other.full")
    if group == "channel":     # no perfbench/channels/lognormal.py
        rc, out, err = _run(root, capsys, "other.full")
        assert rc == 2 and out == "" and "lognormal" in err
        return
    with pytest.raises(ValueError, match=kind):
        _run(root, capsys, "other.full")
    assert capsys.readouterr().out == ""


def test_last_line_keeps_the_contract(tiny_root, capsys):
    rc, out, err = _run(tiny_root, capsys, "fleet.full")
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"agent_steps_per_s", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for c in line["checks"].values():
        assert set(c) == {"number", "limit"}
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_no_tpu_no_result(tiny_root, capsys):
    rc, out, err = _run(tiny_root, capsys, "fleet.full", require_tpu=True)
    assert rc == 1 and out == "" and "TPU" in err


@pytest.mark.parametrize("missing", ["program", "config"])
def test_a_checkout_without_its_files_gives_no_result(tmp_path, missing):
    root = make_tiny(str(tmp_path))
    if missing == "program":
        os.unlink(os.path.join(root, "src"))
    else:
        os.unlink(os.path.join(root, "perfbench/configs/"
                               "fleet_1e4_rayleigh.json"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "fleet.full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2 and proc.stdout == ""
