"""Roofline share of the paged-KV gather kernel: each grid step reads one
block and writes one, so its bytes are twice its result's; over its device
time, against the chip's HBM bandwidth."""

from bench import trace


def read(run):
    return trace.roofline_share(run, ("_gather_block_kernel",),
                                bytes_rule="twice_results")
