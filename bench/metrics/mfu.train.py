"""Model FLOP/s utilisation of training over the traced steps: the
operations the forward and backward passes require (remat recompute not
counted) times steps, over the traced span and the chips' bf16 peak."""

from bench import cost


def read(run):
    c = run["counters"]
    if not c.get("traced_s"):
        return None
    t = run["traffic"]
    flops = cost.train_flops(run["dims"], t["global_batch"], t["seq_len"]) \
        * c["traced_steps"]
    return 100.0 * flops / c["traced_s"] / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
