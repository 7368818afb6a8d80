"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or ctx.get("kind") != "train":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
