"""Roofline share of the XOR parity kernels: the bytes of their operands
and results over their device time, against the chip's HBM bandwidth."""

from bench import trace


def read(run):
    return trace.roofline_share(run, ("_xor_update_kernel",
                                      "_xor_fold_kernel"))
