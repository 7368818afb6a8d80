"""Host milliseconds per round in the clients' estimate step: the
program's ``trainer.estimate`` spans (three estimate batches, four
gradients, the (L, sigma^2, G^2) estimates read back), inside
``trainer.local_train``."""

import program_spans


def read(ctx):
    s = program_spans.per_round_seconds(ctx, "trainer.estimate")
    return None if s is None else 1e3 * s
