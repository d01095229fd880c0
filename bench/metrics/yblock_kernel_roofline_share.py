"""The y-blocked windowed kernel's share of its roofline, in %: the least
time of the steps it made, from the benchmark's own work per site and
peaks (bench/lib/work.py; bytes bound it), over the device time of the
kernels whose name marks a y-blocked window (`tdp_windowed..._yb<rows>_...`),
mean over the devices.  Nothing to read where no such kernel ran."""
from bench.lib import trace as tr, work


def is_yblock_kernel(op: str) -> bool:
    return tr.is_kernel(op) and "_yb" in op


def read(ctx):
    per_dev = [tr.op_seconds(ops, ctx.window, is_yblock_kernel)
               for ops in ctx.trace.devices.values()]
    if not per_dev or min(per_dev) == 0 or ctx.kernel_steps == 0:
        return None
    least, _ = work.least_step_seconds(ctx.sites_per_chip, ctx.device_kind)
    return 100.0 * least * ctx.kernel_steps / (sum(per_dev) / len(per_dev))
