"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Per device plane (``/device:TPU:<i>``), from its ``XLA Ops`` line:

* busy: the union of the op intervals (ops nest: a ``while`` op spans the
  ops of its body), inside the window;
* window: from the first op's start to the last op's end, cut where the
  profiler reports ``Trace Buffers Dropped`` (a trace holds a few million
  events; after that it records nothing, so the window ends there);
* time per op name; collectives; Pallas kernel launches
  (``tpu_custom_call``), counted and timed per operand list, which is what
  names a kernel in the trace (the op's own name is that of the jitted
  function around it): a per-layer reader picks its kernel's launches by
  their operands;
* the longest gaps between ops, each named by the innermost host event
  (any host thread) that spans its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
# a Pallas (Mosaic) kernel; XLA's own custom calls have other targets
KERNEL = 'custom_call_target="tpu_custom_call"'
OPERANDS = "operand_layout_constraints={"
DROPPED = "Trace Buffers Dropped"


@dataclass
class Device:
    name: str
    busy_ns: float = 0.0
    window_ns: float = 0.0
    n_ops: int = 0
    truncated: bool = False
    op_ns: Dict[str, float] = field(default_factory=dict)
    # operand list -> [launches, ns] of each Pallas kernel
    kernels: Dict[str, List[float]] = field(default_factory=dict)
    collective_ns: float = 0.0
    gaps: List[Tuple[float, float]] = field(default_factory=list)


@dataclass
class Summary:
    devices: List[Device]
    idle_gaps: List[Tuple[str, float]]   # (host activity, seconds)

    @property
    def busy_s(self) -> float:
        return float(np.mean([d.busy_ns for d in self.devices])) / 1e9

    @property
    def window_s(self) -> float:
        return float(np.mean([d.window_ns for d in self.devices])) / 1e9

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.op_ns.items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in best]


def op_name(text: str) -> str:
    """``%while.127 = (s32[], ...) while(...)`` -> ``while.127``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def kernel_operands(text: str) -> str:
    """The operand list of a Pallas kernel launch's op text, ``""`` where
    the text gives none: ``f32[1,8]{1,0}, u32[1,1]{1,0}, ...``."""
    i = text.find(OPERANDS)
    if i < 0:
        return ""
    i += len(OPERANDS)
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            if depth == 0:
                return text[i:j]
            depth -= 1
    return text[i:]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(starts: np.ndarray, ends: np.ndarray) -> Tuple[float, np.ndarray]:
    """Length of the union of intervals, and the gaps between them as
    (start, end) rows."""
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    gap = s[1:] > e[:-1]
    gaps = np.stack([e[:-1][gap], s[1:][gap]], axis=1)
    total = (e[-1] - s[0]) - float(np.sum(gaps[:, 1] - gaps[:, 0]))
    return total, gaps


def reduce(path: str, n_gaps: int = 10) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(_device(plane, n_gaps))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    gaps = sorted((g for d in devices for g in d.gaps),
                  key=lambda g: g[0] - g[1])[:n_gaps]
    return Summary(devices, [[_host_label(host, g), (g[1] - g[0]) / 1e9]
                             for g in gaps])


def _device(plane, n_gaps: int = 10) -> Device:
    dev = Device(plane.name)
    cut = np.inf
    starts, ends = [], []
    for line in plane.lines:
        if line.name == "XLA TraceMe":
            for ev in line.events:
                if ev.name == DROPPED:
                    cut = min(cut, ev.start_ns)
                    dev.truncated = True
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            text, t0, dur = ev.name, ev.start_ns, ev.duration_ns
            starts.append(t0)
            ends.append(t0 + dur)
            name = op_name(text)
            dev.op_ns[name] = dev.op_ns.get(name, 0.0) + dur
            if KERNEL in text:
                k = dev.kernels.setdefault(kernel_operands(text), [0, 0.0])
                k[0] += 1
                k[1] += dur
            elif COLLECTIVE.search(text):
                dev.collective_ns += dur
    if not starts:
        return dev
    s, e = np.asarray(starts, np.float64), np.asarray(ends, np.float64)
    keep = s < cut
    s, e = s[keep], np.minimum(e[keep], cut)
    dev.n_ops = int(s.size)
    dev.busy_ns, gaps = _union(s, e)
    dev.window_ns = float(e.max() - s.min())
    longest = np.argsort(gaps[:, 0] - gaps[:, 1])[:n_gaps]
    dev.gaps = [tuple(g) for g in gaps[longest]]
    return dev


def _host_label(host, gap) -> str:
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for t0, t1, name in host:
        if t0 <= mid <= t1 and (best is None or t1 - t0 < best[1] - best[0]):
            best = (t0, t1, name)
    return best[2] if best else "no host event"
