#!/usr/bin/env python3
"""Readings that the correctness limits are set from (run on the chip).

    python3 perfbench/calibrate.py --workload fleet.full --seeds 1,2,3 \\
        --modes sound,control,frozen_state --calls 1 --out readings.jsonl

For each mode and seed, one JSON line with the compared numbers:

* ``sound``   -- the program as a benchmark run drives it (set-up's call
  and ``--calls - 1`` more), checked as ``run.py`` checks it;
* ``control`` -- the plain reference at ``bf16x3`` put in the program's
  place for the same calls (``compare.control_answers``);
* a fault of ``perfbench/faults.py`` -- the program with that fault
  planted (or at the TPU's default precision), checked the same way.

One process: the program compiles once per mode.  Exits 1 without a reading
when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(bench, work, seeds, modes, calls: int, out):
    import jax

    from perfbench import compare, faults

    limits = bench.limits(work)
    for mode in modes:
        for seed in seeds:
            t0 = time.perf_counter()
            cell = bench.cell(work, seed)
            if mode == "control":
                cell.build()
                prior = {}
                answers = [a for i in range(calls)
                           for a in compare.control_answers(cell, i, prior)]
                gaps = compare.check(cell, answers, limits)
            else:
                # the check too: the program's own draws are taken from the
                # program as it ran
                with faults.FAULTS[mode]() if mode != "sound" else _nothing():
                    cell.build()
                    answers = [a for i in range(calls)
                               for a in cell.answers(i, cell.call(i))]
                    gaps = compare.check(cell, answers, limits)
            rec = {"workload": work["name"], "seed": seed, "mode": mode,
                   "numbers": gaps.numbers, "info": gaps.info,
                   "s": time.perf_counter() - t0,
                   "device": jax.devices()[0].device_kind}
            print(json.dumps(rec), file=out, flush=True)
            print(json.dumps(rec), file=sys.stderr, flush=True)


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="sound")
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from perfbench.harness import Benchmark

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    bench = Benchmark(ROOT)
    work = bench.workload(args.workload)
    from repro.utils import platform as rplat

    rplat.enable_compile_cache()
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(work["chips"]):
        print(f"calibrate: needs {work['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        readings(bench, work, [int(s) for s in args.seeds.split(",")],
                 args.modes.split(","), args.calls, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
