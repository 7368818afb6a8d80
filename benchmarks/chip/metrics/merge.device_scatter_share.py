"""Share of the clients whose Eq. 5 contributions the collective merge
scattered on the device: the ``device_scatter`` attributes of the
window's ``merge.prep`` spans over their ``clients``.  None where no span
carries ``device_scatter``, as a build that scatters on the host records
none."""

import program_spans


def read(ctx):
    scattered = program_spans.per_round_attr(ctx, "merge.prep",
                                             "device_scatter")
    clients = program_spans.per_round_attr(ctx, "merge.prep", "clients")
    if scattered is None or not clients:
        return None
    return scattered / clients
