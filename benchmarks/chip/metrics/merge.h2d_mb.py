"""Megabytes (1e6 bytes) per round that the collective merge hands from
the host to its compiled call: the program's ``merge.h2d_bytes``
counter, as carried on each window ``merge.compiled`` span."""

import program_spans


def read(ctx):
    b = program_spans.per_round_attr(ctx, "merge.compiled", "h2d_bytes")
    return None if b is None else b / 1e6
