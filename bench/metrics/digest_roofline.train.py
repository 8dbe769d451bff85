"""Roofline share of the canary digest kernel: the bytes of its operands
and results over its device time, against the chip's HBM bandwidth (the
kernel does integer sums on the vector unit; bytes bound it)."""

from bench import trace


def read(run):
    return trace.roofline_share(run, ("_row_checksum_kernel",))
