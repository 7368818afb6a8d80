"""Milliseconds per round inside the sequential trainer's per-client
``trainer.local_train`` spans (the program's telemetry, on in the traced
run)."""


def read(ctx):
    obs = ctx.get("obs")
    if not obs:
        return None
    spans = [e for e in obs["spans"] if e.get("type") == "span"
             and e["name"] == "trainer.local_train"]
    if not spans:
        return None
    return 1e3 * sum(e["t1"] - e["t0"] for e in spans) / ctx["rounds"]
