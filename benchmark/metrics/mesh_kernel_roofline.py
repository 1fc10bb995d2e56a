"""Kernels, on a server that holds several chips: the least time the
chips could take together for a query's bytes (each column it reads,
once, at its staged width, times the WHOLE table's rows, over the
configuration's `chips` times one chip's HBM peak) over the device-busy
time a query took in the traced window, which trace_reduce already
gives as the mean over the chips. kernel_roofline divides by one chip's
peak whatever the cell holds, so on four chips it reads four times the
truth: this is that reading over `chips`, and the same number on one."""
from metrics import kernel_roofline


def read(ctx):
    one_chip = kernel_roofline.read(ctx)
    return None if one_chip is None else one_chip / ctx["config"]["chips"]
