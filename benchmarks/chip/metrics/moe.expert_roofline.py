"""Share of its roofline that the grouped expert matmuls reach, in
percent: the least time the chip could take for them (per client, the
larger of their operations over peak FLOP/s and their bytes over peak
HBM bandwidth, from the routed pairs the program counted at the client's
width: ``flops_mla_moe.grouped_matmul_cost``), summed, over the device
time of every ``ragged-dot`` op in the trace (the grouped matmuls,
forward and backward, and their group metadata), whatever implements
them."""

import flops_mla_moe as fm
from trace_reduce import instruction

OP = "ragged-dot"


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    clients = fm.counted_clients(ctx)
    if not tr or not peaks or not clients:
        return None
    spent = sum(o["seconds"] for text, o in tr["ops"].items()
                if instruction(text).startswith(OP))
    if spent <= 0.0:
        return None
    m = ctx["model"]
    best = 0.0
    for a in clients:
        tau = max(int(a["tau"]), 1)
        fl, by = fm.grouped_matmul_cost(
            m, int(a["width"]), tau + 6, a["moe.routed_pairs"], tau + 4,
            a["backward.moe.routed_pairs"])
        best += max(fl / peaks["flops_per_s"], by / peaks["hbm_bytes_per_s"])
    return 100.0 * best / spent
