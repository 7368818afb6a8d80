"""Share of its roofline that the ``compose_pallas`` kernel reaches, in
percent: for each kernel call in the trace, the least time the chip
could take (the larger of its operations over peak FLOP/s and its bytes
over peak HBM bandwidth, from the operand shapes in that call's HLO
text), summed, over the kernel's summed device time."""

import re

from trace_reduce import instruction

_SHAPE = re.compile(r"(bf16|f32|f16|s32)\[([\d,]+)\]")
_ITEM = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4}


def _shapes(text):
    return [(t, [int(d) for d in dims.split(",")])
            for t, dims in _SHAPE.findall(text)]


def _size(t, dims):
    n = _ITEM[t]
    for d in dims:
        n *= d
    return n


def kernel_cost(text):
    """(flops, bytes) of one compose call from its HLO text: output
    ``(k, I, N)`` from the basis ``(k, I, R)`` and the blocks laid out
    as ``(R, N)``; 2 operations per multiply-add of the contraction over
    R, bytes of both operands and the output."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    sh = _shapes(text)
    if len(sh) < 3:
        return None
    (to, out), (ta, a), (tb, b) = sh[0], sh[1], sh[2]
    if a[-1] != b[0] or out[-1] != b[-1]:
        return None
    outer = 1
    for d in out:
        outer *= d
    return 2 * outer * a[-1], _size(to, out) + _size(ta, a) + _size(tb, b)


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if not tr or not peaks:
        return None
    best, spent = 0.0, 0.0
    # ops are keyed by their whole HLO text: each shape is costed apart
    for text, o in tr["ops"].items():
        if "compose_pallas" not in instruction(text):
            continue
        cost = kernel_cost(text)
        if cost is None:
            continue
        fl, by = cost
        best += o["count"] * max(fl / peaks["flops_per_s"],
                                 by / peaks["hbm_bytes_per_s"])
        spent += o["seconds"]
    if spent <= 0.0:
        return None
    return 100.0 * best / spent
