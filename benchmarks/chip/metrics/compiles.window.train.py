"""Programs compiled or loaded inside a training window (``jax.monitoring``
events); 0 when every program was warmed in set-up."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    return ctx["compiles_window"]
