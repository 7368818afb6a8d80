"""Least-work model operations of the traced rounds over the traced
window over the chip's peak, in percent, for the latent-attention +
experts decoder.

Per client and round: tau SGD steps, the two loss evaluations and the
four estimate gradients, each composed layer at the cheaper of
rank-space application and compose-then-dense, and the routed experts
at the pairs the program counted (``flops_mla_moe.client_round_flops``),
so no implementation can read above 100%."""

import flops_mla_moe as fm


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    clients = fm.counted_clients(ctx)
    if not tr or not peaks or not clients:
        return None
    m, t = ctx["model"], ctx["traffic"]
    b, seq = t["engine"]["batch_size"], t["seq_len"]
    total = sum(fm.client_round_flops(
        m, int(a["width"]), max(int(a["tau"]), 1), b, seq,
        a["moe.routed_pairs"], a["backward.moe.routed_pairs"])
        for a in clients)
    return 100.0 * total / tr["window_s"] / (peaks["flops_per_s"]
                                             * ctx["chips"])
