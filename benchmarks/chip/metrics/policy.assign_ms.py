"""Host milliseconds per round in the assignment policy (the
``bench.assign`` span around ``eng.assignment.assign``)."""


def read(ctx):
    d = ctx["spans"].get("bench.assign")
    if not d or ctx.get("kind") != "train":
        return None
    return 1e3 * sum(d) / ctx["rounds"]
