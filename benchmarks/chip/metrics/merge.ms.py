"""Milliseconds per round in the server merge (the ``bench.merge`` span
around ``aggregator.aggregate``, ended by ``block_until_ready`` of the
merged state in the traced run)."""


def read(ctx):
    d = ctx["spans"].get("bench.merge")
    if not d or ctx.get("kind") != "train":
        return None
    return 1e3 * sum(d) / ctx["rounds"]
