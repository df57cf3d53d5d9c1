"""The fused uplink kernel's share of its roofline, in percent: the least
time for the bytes and FLOPs its launches need (the entry's
``uplink_launch``, from the configuration's shapes; HBM bytes bound at
these shapes) over the device time of its launches in the trace.

The kernel (``kernels/ota_fused.py``) is picked by its operands: its eight
runtime constants as one ``f32[1,8]`` row, then its PRNG seed as
``u32[...,1,1]`` (vmapped launches add leading axes).  Other Pallas
kernels are left out.  Nothing to read where the trace holds no launch of
it."""
import re

UPLINK = re.compile(r"^f32\[1,8\]\{[^}]*\}, u32\[(?:\d+,)*1,1\]")


def read(ctx):
    calls = busy_ns = 0
    for d in ctx.summary.devices:
        for operands, (n, ns) in d.kernels.items():
            if UPLINK.match(operands):
                calls += n
                busy_ns += ns
    if calls == 0 or busy_ns <= 0:
        return None
    cell = ctx.cell
    need = cell.entry_mod.uplink_launch(ctx.config,
                                        cell.ref.n_params(ctx.config))
    least = max(need["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                need["flops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * calls * least / (busy_ns / 1e9)
