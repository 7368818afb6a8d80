"""Megabytes (1e6 bytes) per round of trained client params copied to
the host: the program's ``trainer.d2h_bytes`` counter, as carried on
each window ``trainer.pull`` span."""

import program_spans


def read(ctx):
    b = program_spans.per_round_attr(ctx, "trainer.pull", "d2h_bytes")
    return None if b is None else b / 1e6
