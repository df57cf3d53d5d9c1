"""The trace reduction on a small trace recorded on one TPU v5e: a fused
uplink kernel call and a 50-step loop of tiny fusions."""
import os

from types import SimpleNamespace

import numpy as np
import pytest

from conftest import REPO
from perfbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_small_trace():
    s = trace.reduce(DATA)
    (dev,) = s.devices
    assert dev.name == "/device:TPU:0" and not dev.truncated
    assert dev.n_ops == 67
    assert dev.window_ns == 11102.0 and dev.busy_ns == 11085.0
    ((operands, (calls, ns)),) = dev.kernels.items()
    assert operands.startswith("f32[1,8]{1,0}, u32[1,1]{1,0}, f32[1,4]")
    assert calls == 1 and ns == 441.0
    assert dev.collective_ns == 0.0
    ops = dict(s.device_ops())
    assert ops["while.11"] == 9.701e-06
    assert len(s.idle_gaps) == 10
    assert all(g[1] > 0 for g in s.idle_gaps)
    assert s.busy_s <= s.window_s


def test_union_counts_nested_ops_once():
    starts = np.array([0.0, 2.0, 3.0, 10.0])
    ends = np.array([5.0, 4.0, 8.0, 12.0])
    busy, gaps = trace._union(starts, ends)
    assert busy == 10.0 and gaps.tolist() == [[8.0, 10.0]]


def test_op_name():
    assert trace.op_name("%while.127 = (s32[]) while(%x)") == "while.127"
    assert trace.COLLECTIVE.search("%a = f32[4] all-reduce-start(%b)")
    assert trace.KERNEL in (
        '%k = f32[1,512] custom-call(%c), custom_call_target="tpu_custom_call"')
    assert trace.KERNEL not in (
        '%s = f32[8] custom-call(%x), custom_call_target="Sharding"')
    assert trace.kernel_operands(
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[1,8]{1,0}, u32[20,1,1]{2,1,0}}, frontend_attributes={}'
    ) == "f32[1,8]{1,0}, u32[20,1,1]{2,1,0}"


def _launch(name, operands):
    return (f"%{name} = f32[1,256] custom-call(%a, %b), custom_call_target="
            f'"tpu_custom_call", operand_layout_constraints={{{operands}}}, '
            "frontend_attributes={kernel_metadata={}}")


UPLINK = "f32[1,8]{1,0}, u32[1,1]{1,0}, f32[1,10]{1,0}, f32[10,256]{1,0}"
VMAPPED = "f32[1,8]{1,0}, u32[20,1,1]{2,1,0}, f32[20,1,10]{2,1,0}"
OTHER = "f32[8,128]{1,0}, s32[8,128]{1,0}"


def test_uplink_roofline_counts_only_the_uplink_kernel():
    """Another Pallas kernel's launches stay out of the uplink's roofline."""
    from perfbench.harness import Benchmark

    ops = [(_launch("f.1", UPLINK), 0, 100), (_launch("g.2", OTHER), 200, 900),
           (_launch("vmap__.1", VMAPPED), 1200, 100),
           (_launch("g.2", OTHER), 1400, 900)]
    plane = SimpleNamespace(name="/device:TPU:0", lines=[SimpleNamespace(
        name="XLA Ops", events=[SimpleNamespace(name=t, start_ns=s,
                                                duration_ns=d)
                                for t, s, d in ops])])
    dev = trace._device(plane)
    assert dev.kernels[OTHER] == [2, 1800.0]
    assert dev.kernels[UPLINK] == [1, 100.0]
    bench = Benchmark(REPO)
    read = bench.reader({"name": "uplink_roofline"})
    work = bench.workload("fleet.full")
    cell = bench.cell(work, 1)
    peaks = {"hbm_bytes_per_s": 1e13, "bf16_flops_per_s": 1e15}
    ctx = SimpleNamespace(summary=trace.Summary([dev], []), cell=cell,
                          config=cell.config, peaks=peaks)
    # two launches of 12*d bytes at 10 TB/s over their 200 ns
    d = cell.ref.n_params(cell.config)
    assert read(ctx) == pytest.approx(100.0 * 2 * (12 * d / 1e13) / 200e-9)
    dev.kernels.pop(UPLINK)
    dev.kernels.pop(VMAPPED)
    assert read(ctx) is None
