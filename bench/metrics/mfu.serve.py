"""Model FLOP/s utilisation of serving over the traced engine steps:
the prefill and decode operations of the requests served in the span
(replayed tokens not counted), over the span and the chips' bf16 peak."""


def read(run):
    c = run["counters"]
    if not c.get("traced_s"):
        return None
    return 100.0 * c["traced_flops"] / c["traced_s"] / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
