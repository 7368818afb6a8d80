"""Useful model operations of the traced rounds over the traced window
over the chip's peak, in percent.

Per client and round: tau SGD steps, the two loss evaluations and the
four estimate gradients Heroes computes, each layer counted at the
cheaper of rank-space application and compose-then-dense
(``flops.client_round_flops``), so no implementation can read above
100%."""

import flops


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if not tr or not peaks or ctx.get("kind") != "train":
        return None
    m, t = ctx["model"], ctx["traffic"]
    b, seq = t["engine"]["batch_size"], t["seq_len"]
    total = sum(flops.client_round_flops(m, int(a["width"]),
                                         max(int(a["tau"]), 1), b, seq)
                for assigns in ctx["assigns"] for a in assigns.values())
    chips = ctx["chips"]
    return 100.0 * total / tr["window_s"] / (peaks["flops_per_s"] * chips)
