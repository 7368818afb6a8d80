"""Round event loops over the virtual clock.

``SyncRoundLoop`` is the paper's round (Alg. 1 / Eq. 19): sample K
clients, train all, aggregate, charge the makespan ``max_n (tau mu + nu)``
to the wall clock.  Histories are pinned bitwise by the golden legacy
fixtures (tests/fixtures/golden_legacy_histories.json).

``SemiAsyncRoundLoop`` keeps up to M clients in flight and aggregates as
soon as the fastest K of them finish.  Stragglers stay in flight across
aggregation events and merge later with a staleness-discounted weight
``decay ** staleness`` (their update was computed against an older
global model), the FedAsync/FedBuff-style rule adapted to every
scheme's aggregator.  The wall clock advances event-by-event to the
K-th completion, so fast clients stop paying for slow ones.

Both loops are pure state transitions: ``run_round(state)`` returns
``(state', log)`` built with ``dataclasses.replace`` — the wall/traffic
counters, params, bound, Heroes tallies and (semi-async) the in-flight
dispatch records all travel inside the :class:`~repro.fl.types.ServerState`,
which is exactly what makes a round boundary checkpointable.  The time
model's per-round noise streams are keyed by ``het.round``; the loops
*derive* it from the state (``het.round = state.round + 1`` while round
``state.round`` runs) instead of advancing a hidden counter, so a
restored state replays identical times.

Both loops hand the same ``weights`` dict to ``aggregator.aggregate``;
with the collective backend the staleness blend is folded into the
dense contribution prep, so semi-async events use the identical
compiled merge as synchronous rounds (no separate weighted path).

``FLConfig.sample_weighted`` rides that same path: per-client sample
counts become blend weights ``K * s_n / sum(s)``, which turns the
cohort mean into the sample-count-weighted mean — exactly — for the
global-mean rules.  The weights can exceed 1, so partitioned rules
(per-block / per-region / per-width subsets, where the blend residuals
do not cancel) see an extrapolated weighting rather than a per-subset
weighted mean; see ``FLConfig.sample_weighted``.  Semi-async
multiplies the weights into the staleness discounts.  Off by default —
seed histories stay bitwise.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.fl.engine.base import RoundLoop
from repro.fl.types import InFlight, RoundLog, ServerState


def _sample_weights(eng, clients) -> Dict[int, float]:
    """Sample-count weights ``K * s_n / sum(s)`` for one merge cohort.

    Routed through the aggregators' blend-weights path
    (``w * update + (1 - w) * global`` before the scheme's mean), this
    reduces the plain cohort mean to ``sum(s_n * u_n) / sum(s_n)`` —
    the FedAvg paper's sample-weighted objective — because the blend
    residuals ``(1 - w_n)`` cancel over the cohort.  Weights are NOT
    clamped to [0, 1]: sample-heavy clients carry w > 1, which is what
    makes the global mean exact but turns per-subset rules into an
    extrapolation (see ``FLConfig.sample_weighted``).
    """
    s = np.array([eng.data.num_samples(n) for n in clients], np.float64)
    w = s * (len(clients) / s.sum())
    return {n: float(wn) for n, wn in zip(clients, w)}


class SyncRoundLoop(RoundLoop):
    """Synchronous makespan round (paper Eq. 19)."""

    def run_round(self, state: ServerState) -> Tuple[ServerState, RoundLog]:
        eng = self.eng
        cfg = eng.cfg
        eng.het.round = state.round + 1  # per-round time-noise stream key
        # cohort via the participation scheduler (uniform default is the
        # legacy eng.rng.choice draw, bitwise)
        clients = eng.sample_clients(state, cfg.clients_per_round)
        if not clients:
            raise RuntimeError(
                "participation scheduler returned an empty cohort "
                f"(scheduler={type(eng.sampler).__name__}, "
                f"num_clients={cfg.num_clients})")
        obs = eng.obs
        with obs.wall_span("round.assign", clients=len(clients)):
            state, assigns = eng.assignment.assign(state, clients)
        results = eng.trainer.train_all(state, assigns)
        times = {}
        traffic = state.traffic
        up = 0.0
        for n, a in assigns.items():
            mu = eng.het.iter_time(n, eng.flops_per_iter(a["width"]))
            b = eng.payload.bytes(a)
            nu = eng.het.upload_time(n, b)
            times[n] = a["tau"] * mu + nu
            traffic += 2 * b  # down + up
            up += b  # symmetric payloads: uplink == downlink == b
            if obs.enabled:
                t0 = state.wall
                t_train = t0 + a["tau"] * mu
                obs.span("client.train", t0, t_train, client=int(n),
                         width=int(a["width"]), tau=int(a["tau"]),
                         round=state.round + 1)
                obs.span("client.upload", t_train, t_train + nu,
                         client=int(n), bytes=b, round=state.round + 1)
                obs.counter_add("traffic.up", b, width=int(a["width"]))
                obs.counter_add("traffic.down", b, width=int(a["width"]))
        weights = (_sample_weights(eng, list(results))
                   if cfg.sample_weighted else None)
        with obs.wall_span("aggregate.merge", clients=len(results)):
            state = eng.aggregator.aggregate(
                dataclasses.replace(state, traffic=traffic,
                                    traffic_up=state.traffic_up + up,
                                    traffic_down=state.traffic_down + up),
                results, assigns, weights=weights)
        makespan = max(times.values())
        wait = float(np.mean([makespan - t for t in times.values()]))
        state = dataclasses.replace(state, wall=state.wall + makespan,
                                    round=state.round + 1)
        acc = None
        if state.round % cfg.eval_every == 0 or state.round == 1:
            with obs.wall_span("round.evaluate", round=state.round):
                acc = eng.aggregator.evaluate(state)
        if obs.enabled:
            obs.observe("round.makespan", makespan)
            obs.observe("round.wait", wait)
            obs.event("round.aggregate", state.wall, round=state.round,
                      clients=len(results))
        log = RoundLog(state.round, state.wall, state.traffic, makespan, wait,
                       float(np.mean([a["tau"] for a in assigns.values()])),
                       acc, up_bytes=up, down_bytes=up)
        state = dataclasses.replace(state, history=state.history + (log,))
        return state, log


class SemiAsyncRoundLoop(RoundLoop):
    """Aggregate the fastest K of M in-flight clients per event.

    One ``run_round`` call = one aggregation event.  Training results are
    computed eagerly at dispatch against the then-current global state —
    exactly what a straggler's update would contain when it finally
    lands — and merged with weight ``staleness_decay ** staleness``.
    Dispatch records live in ``state.in_flight``; a record still in
    flight after an event holds its params as numpy
    (``ClientResult.host_params``), so an event boundary checkpoints
    stragglers and all.
    """

    def __init__(self, k: Optional[int] = None,
                 staleness_decay: Optional[float] = None):
        self._k_override = k
        self._decay_override = staleness_decay

    def setup(self, eng) -> None:
        super().setup(eng)
        cfg = eng.cfg
        self.k = self._k_override or cfg.async_k \
            or max(1, cfg.clients_per_round // 2)
        self.decay = (self._decay_override if self._decay_override is not None
                      else cfg.staleness_decay)

    def _dispatch(self, state: ServerState,
                  clients: List[int]) -> ServerState:
        eng = self.eng
        obs = eng.obs
        with obs.wall_span("round.assign", clients=len(clients)):
            state, assigns = eng.assignment.assign(state, clients)
        results = eng.trainer.train_all(state, assigns)
        traffic = state.traffic
        up = 0.0
        new = []
        for n, a in assigns.items():
            mu = eng.het.iter_time(n, eng.flops_per_iter(a["width"]))
            b = eng.payload.bytes(a)
            nu = eng.het.upload_time(n, b)
            traffic += 2 * b
            up += b
            finish = state.wall + a["tau"] * mu + nu
            new.append(InFlight(n, a, results[n], finish, state.round))
            if obs.enabled:
                t_train = state.wall + a["tau"] * mu
                obs.span("client.train", state.wall, t_train, client=int(n),
                         width=int(a["width"]), tau=int(a["tau"]),
                         round=state.round + 1)
                obs.span("client.upload", t_train, finish, client=int(n),
                         bytes=b, round=state.round + 1)
                obs.counter_add("traffic.up", b, width=int(a["width"]))
                obs.counter_add("traffic.down", b, width=int(a["width"]))
        return dataclasses.replace(state, traffic=traffic,
                                   traffic_up=state.traffic_up + up,
                                   traffic_down=state.traffic_down + up,
                                   in_flight=state.in_flight + tuple(new))

    def run_round(self, state: ServerState) -> Tuple[ServerState, RoundLog]:
        eng = self.eng
        cfg = eng.cfg
        obs = eng.obs
        eng.het.round = state.round + 1
        up0, down0 = state.traffic_up, state.traffic_down
        busy = {t.client for t in state.in_flight}
        need = cfg.clients_per_round - len(state.in_flight)
        if need > 0:
            # the eligible pool can be empty (clients_per_round >
            # num_clients, every client already in flight, or no client
            # passes its participation gate): skip the dispatch instead
            # of spuriously advancing assignment-policy state on [].
            newly = eng.sample_clients(state, need, exclude=busy)
            if newly:
                state = self._dispatch(state, newly)
        if not state.in_flight:
            raise RuntimeError(
                "semi-async round with no dispatchable clients "
                f"(num_clients={cfg.num_clients}, "
                f"clients_per_round={cfg.clients_per_round})")

        # stable sort: ties keep dispatch order, like the legacy in-place
        # list sort, so event composition is reproducible
        flight = sorted(state.in_flight, key=lambda t: t.finish)
        k = min(self.k, len(flight))
        t_k = flight[k - 1].finish
        done = [t for t in flight if t.finish <= t_k]
        remaining = [t for t in flight if t.finish > t_k]

        results = {t.client: t.result for t in done}
        assigns = {t.client: t.assign for t in done}
        stale = sum(1 for t in done if state.round > t.dispatched)
        # all-fresh events take the cheap synchronous merge path
        weights = None if stale == 0 else {
            t.client: self.decay ** (state.round - t.dispatched)
            for t in done}
        if cfg.sample_weighted:
            sw = _sample_weights(eng, list(results))
            weights = sw if weights is None else \
                {n: sw[n] * weights[n] for n in sw}
        if obs.enabled:
            for t in done:
                obs.observe("staleness", float(state.round - t.dispatched))
        with obs.wall_span("aggregate.merge", clients=len(results),
                           stale=stale):
            state = eng.aggregator.aggregate(state, results, assigns,
                                             weights=weights)
        # stragglers must not pin device memory (trained params, cohort
        # stacks and their host caches) across events: degrade their
        # results to numpy now, so device results die with their event —
        # which also keeps in-flight records checkpointable as-is
        remaining = tuple(
            dataclasses.replace(
                t, result=dataclasses.replace(
                    t.result, params=t.result.host_params(obs)))
            for t in remaining)

        makespan = t_k - state.wall  # time since the previous aggregation
        wait = float(np.mean([t_k - t.finish for t in done]))
        state = dataclasses.replace(state, wall=t_k, round=state.round + 1,
                                    in_flight=remaining)
        acc = None
        if state.round % cfg.eval_every == 0 or state.round == 1:
            with obs.wall_span("round.evaluate", round=state.round):
                acc = eng.aggregator.evaluate(state)
        if obs.enabled:
            obs.observe("round.makespan", makespan)
            obs.observe("round.wait", wait)
            obs.event("round.aggregate", state.wall, round=state.round,
                      clients=len(results), stale=stale,
                      in_flight=len(remaining))
            obs.gauge_set("loop.in_flight", len(remaining))
        log = RoundLog(state.round, state.wall, state.traffic, makespan, wait,
                       float(np.mean([a["tau"] for a in assigns.values()])),
                       acc, stale=stale,
                       up_bytes=state.traffic_up - up0,
                       down_bytes=state.traffic_down - down0)
        state = dataclasses.replace(state, history=state.history + (log,))
        return state, log
