#!/usr/bin/env python3
"""Benchmark run of one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 perfbench/run.py --workload fleet.full --seed 7 --seconds 10 --trace 0

Set-up builds the cell from its configuration and traffic files, makes one
warm call (compiling, or loading from the compile cache in ``.jax_cache``
of this checkout), then the window runs calls back to back for
``--seconds``.  ``--trace 0`` reports the end-to-end metrics; ``--trace 1``
adds one call after the window, profiled over its last second, and reports
the per-layer metrics.
Every call's answers are then compared with the plain reference
(``perfbench/compare.py``); the numbers compared are printed with their
limits as the last lines of standard error and under ``checks`` in the
result, the last line of standard output.

Exits 1 without a result when JAX finds no TPU or fewer chips than the
cell asks for, and 2 when a file the cell names is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench.harness import Benchmark, Missing  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str, code: int) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return code


TRACE_TAIL_S = 1.0


def _traced_call(cell, i: int, tdir: str, call_s: float):
    """Call ``i`` with the profiler on for about its last ``TRACE_TAIL_S``
    seconds.  A trace holds a few million device ops, and the serial
    scatter loop of a fleet round makes that many in under 4 s: the tail
    is what a trace can hold whole, and it holds the end of the round,
    where the uplink kernel and the collectives run."""
    import jax

    # device ops and the runtime's host events; no Python call events and
    # no HLO dumps, which only slow the trace down
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        done = pool.submit(cell.call, i)
        time.sleep(max(0.0, call_s - TRACE_TAIL_S))
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            return done.result()
        finally:
            jax.profiler.stop_trace()


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True,
         t_start: float = T_START) -> int:
    args = _args(argv)
    # the compile cache lives at a fixed path inside this checkout; the
    # program takes the directory from this variable, read when JAX loads
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    try:
        bench = Benchmark(root)
        work = bench.workload(args.workload)
        cell = bench.cell(work, args.seed)
        limits = bench.limits(work)
        e2e = bench.metrics("end_to_end", work)
        layers = [(m, bench.reader(m)) for m in bench.metrics("per_layer", work)]
    except Missing as e:
        return _fail(str(e), 2)
    config, traffic = cell.config, cell.traffic
    try:
        from repro.utils import platform as rplat
    except ImportError as e:
        return _fail(f"the program is not in this checkout: {e}", 2)
    rplat.enable_compile_cache()
    import jax

    if config["precision"] != "float32":
        return _fail(f"no reference for precision {config['precision']!r}", 2)
    # float32 as the configuration states: a TPU's default matmul is one
    # bfloat16 pass
    jax.config.update("jax_default_matmul_precision", "highest")
    devices = jax.devices()
    chips = int(work["chips"])
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        return _fail(f"needs {chips} TPU chip(s); JAX sees {len(devices)} "
                     f"{devices[0].platform} device(s)", 1)
    used = devices[:chips]

    from perfbench import compare

    t_devices = time.perf_counter()
    cell.build(devices)
    t_build = time.perf_counter()
    outs = [(0, cell.call(0))]
    setup_s = time.perf_counter() - t_start
    setup_parts = {"start_and_devices_s": t_devices - t_start,
                   "build_s": t_build - t_devices,
                   "first_call_s": t_start + setup_s - t_build}

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    while True:
        i = len(outs)
        outs.append((i, cell.call(i)))
        elapsed = time.perf_counter() - t0
        if elapsed >= args.seconds:
            break
    calls = len(outs) - 1
    rate = calls * cell.agent_steps_per_call / elapsed
    window_compiles = len(compiles)
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in used)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(memory)}

    breakdown = None
    if args.trace:
        from perfbench import trace as rtrace
        from perfbench.peaks import peaks

        tdir = os.path.join(root, ".perfbench_trace")
        shutil.rmtree(tdir, ignore_errors=True)
        i = len(outs)
        t_trace = time.perf_counter()
        outs.append((i, _traced_call(cell, i, tdir, elapsed / calls)))
        t_reduce = time.perf_counter()
        summary = rtrace.reduce(rtrace.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        t_done = time.perf_counter()
        ctx = SimpleNamespace(summary=summary, config=config, traffic=traffic,
                              cell=cell, rate=rate, chips=chips,
                              peaks=peaks(used[0].device_kind))
        metrics = {}
        for m, read in layers:
            value = read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.device_ops(),
                     "idle_gaps": [[n, float(s)] for n, s in summary.idle_gaps]}
        truncated = [d.name for d in summary.devices if d.truncated]
        print(json.dumps({"trace_ops": [d.n_ops for d in summary.devices],
                          "traced_call_s": t_reduce - t_trace,
                          "reduce_s": t_done - t_reduce,
                          "trace_truncated": truncated,
                          "kernel_launches": [d.kernels
                                              for d in summary.devices]}),
              file=sys.stderr)
    else:
        values = {"agent_steps_per_s": rate, "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in e2e}

    print(json.dumps({"window_calls": calls, "window_s": elapsed,
                      "compiles_in_window": window_compiles,
                      "setup_s": setup_s, **setup_parts}), file=sys.stderr)
    answers = [a for i, out in outs for a in cell.answers(i, out)]
    del outs
    t_check = time.perf_counter()
    gaps = compare.check(cell, answers, limits)
    checks = compare.verdict(gaps, limits)
    failed = compare.failed_calls(gaps, limits)
    correct = compare.is_correct(checks, limits)
    print(json.dumps({"compared": gaps.info,
                      "check_s": time.perf_counter() - t_check}),
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['number']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(gaps.calls),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
