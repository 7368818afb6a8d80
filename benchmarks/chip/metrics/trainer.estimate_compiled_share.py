"""Share of the clients whose estimate step ran as one compiled program:
the ``compiled`` attributes of the window's ``trainer.estimate`` spans
over their count.  None where no span carries ``compiled``, as a build
that runs the estimator op by op records none."""

import program_spans

SPAN = "trainer.estimate"


def read(ctx):
    compiled = program_spans.per_round_attr(ctx, SPAN, "compiled")
    if compiled is None:
        return None
    spans = sum(1 for e in ctx["obs"]["spans"]
                if e.get("type") == "span" and e["name"] == SPAN)
    return compiled * ctx["rounds"] / spans
