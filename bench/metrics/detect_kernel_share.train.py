"""Share of the device's busy time spent in the canary digest and the
XOR parity kernels."""

from bench import trace

KERNELS = ("_row_checksum_kernel", "_xor_update_kernel", "_xor_fold_kernel")


def read(run):
    tr = run.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(r["seconds"]
               for r in trace.kernel_stats(tr, KERNELS).values())
    return 100.0 * secs / tr["busy_s"]
