"""The engine's wall-clock span names, each with what it times.

Every ``Recorder.wall_span`` the engine opens is named here, so the
docs, the report and readers of profiler traces work from one list.
An enabled wall span also enters a ``jax.profiler.TraceAnnotation`` of
the same name, so in a ``jax.profiler`` trace it sits on the host
thread that opened it, on the device trace's clock.  Each recorded span
names its ``parent``: the enclosing wall span on the same thread.

Dispatch is asynchronous: a span that only launches device work ends
before the work does, and the wait lands in the first span that reads a
result back (``trainer.loss`` after ``trainer.sgd``, for instance).
"""

from __future__ import annotations

from typing import Dict

SPANS: Dict[str, str] = {
    "round.assign":
        "the assignment policy picks each sampled client's width and tau",
    "round.evaluate":
        "the server evaluates the merged model on the test set",
    "trainer.client_params":
        "the view a client receives is cut from the global state",
    "trainer.local_train":
        "one client's local update: its steps, losses and estimates",
    "trainer.sgd":
        "dispatch of the tau local steps, with their batch staging",
    "trainer.loss":
        "the first batch's loss before and after the steps, read back",
    "trainer.estimate":
        "the three estimate batches and (L, sigma^2, G^2) as one compiled "
        "program, read back once (attribute compiled=1, counter "
        "trainer.estimate_compiled_clients)",
    "trainer.pull":
        "trained params copied to the host (counter trainer.d2h_bytes)",
    "trainer.host_stage":
        "cohort trainer: host batch staging of one width group",
    "trainer.device_step":
        "cohort trainer: the compiled step, synced when telemetry is on",
    "aggregate.merge":
        "the server merge of one round (aggregator.aggregate)",
    "merge.prep":
        "collective merge: contributions blended, scattered and stacked "
        "(counter merge.device_scatter_clients)",
    "merge.compiled":
        "collective merge: the compiled call, with its host-to-device "
        "copies (counter merge.h2d_bytes)",
    "checkpoint.save":
        "one checkpoint write",
}
