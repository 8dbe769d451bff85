"""Model FLOP/s utilisation of training over the traced steps: the
operations the forward and backward passes require, as the
configuration's yardstick counts them (remat recompute not counted),
times steps, over the traced span and the chips' bf16 peak."""


def read(run):
    c = run["counters"]
    if not c.get("traced_s"):
        return None
    t = run["traffic"]
    flops = run["yardstick"].train_flops(
        run["dims"], t["global_batch"], t["seq_len"]) * c["traced_steps"]
    return 100.0 * flops / c["traced_s"] / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
