"""Host milliseconds per round in the collective merge's preparation:
the program's ``merge.prep`` spans (blend, scatter into zero-padded
contributions, stack), inside ``aggregate.merge``."""

import program_spans


def read(ctx):
    s = program_spans.per_round_seconds(ctx, "merge.prep")
    return None if s is None else 1e3 * s
