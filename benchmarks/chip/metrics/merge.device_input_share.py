"""Share of the clients whose trained factors the collective merge took
from the device with no host copy: the ``device_input`` attributes of
the window's ``merge.prep`` spans over their ``clients``.  None where no
span carries ``device_input``, as a build that copies the factors
through the host records none."""

import program_spans


def read(ctx):
    on_device = program_spans.per_round_attr(ctx, "merge.prep",
                                             "device_input")
    clients = program_spans.per_round_attr(ctx, "merge.prep", "clients")
    if on_device is None or not clients:
        return None
    return on_device / clients
