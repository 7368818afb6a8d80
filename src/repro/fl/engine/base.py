"""Component contracts of the layered FL engine.

A *scheme* (FedAvg, ADP, HeteroFL, Flanc, Heroes, ...) is a bundle of
five independently testable components wired to a shared
:class:`~repro.fl.engine.runner.EngineRunner`:

  AssignmentPolicy        who trains what: (width, tau, block ids) per client
  PayloadModel            traffic accounting: bytes shipped per assignment
  Aggregator              global-state owner: init / client view / merge / eval
  LocalTrainer            client-update backend: sequential or batched cohort
  RoundLoop               virtual-clock event loop: synchronous or semi-async
  ParticipationScheduler  who is offered the round: cohort sampling policy
                          (implementations + registry live in
                          repro.fl.population.schedulers)

Each component is bound to the runner with :meth:`setup` for its *static*
collaborators (model, heterogeneity profile, config, merger).  All
*round* state — params, BoundState, rng, wall/traffic/round counters,
scheduler tallies, participation bookkeeping, in-flight dispatches —
lives in one explicit :class:`~repro.fl.types.ServerState` value that
``RoundLoop.run_round(state) -> (state', RoundLog)`` threads state-in /
state-out through every contract below.  Components never stash round
state on themselves or the runner, which is what makes a round boundary
checkpointable (``FLConfig.checkpoint_every``) and resumable bitwise.
The contract deliberately mirrors where the paper's five schemes
actually differ (Sec. VI-B), so a new scheme is a policy bundle, not a
runner subclass.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Dict, Optional, Sequence, Tuple

from repro.fl.client import ClientResult
from repro.fl.types import RoundLog, ServerState
from repro.obs.recorder import NOOP

if TYPE_CHECKING:  # pragma: no cover
    from repro.fl.engine.runner import EngineRunner

Assignment = Dict[str, Any]  # {"width": int, "tau": int, [block-id keys]}


class Component:
    """Base: every engine component is bound to one runner."""

    eng: "EngineRunner"

    def setup(self, eng: "EngineRunner") -> None:
        self.eng = eng

    @property
    def obs(self):
        """The bound runner's telemetry recorder (:mod:`repro.obs`);
        the shared no-op before :meth:`setup` binds a runner."""
        return getattr(getattr(self, "eng", None), "obs", NOOP)


class AssignmentPolicy(Component):
    """Decides (width, tau, tensor blocks) for a set of sampled clients.

    ``assign`` returns ``(state', assigns)``: any control state the
    policy advances (Heroes' per-block counters) is carried in
    ``state.sched``, never on the policy instance.  The returned dict's
    insertion order is the order every downstream consumer iterates in,
    which keeps histories reproducible.
    """

    def init_state(self, state: ServerState) -> ServerState:
        """Attach policy-owned fields to a fresh state (default: none)."""
        return state

    def assign(self, state: ServerState, clients: Sequence[int],
               ) -> Tuple[ServerState, Dict[int, Assignment]]:
        raise NotImplementedError


class PayloadModel(Component):
    """Bytes shipped one way for one client's assignment."""

    def bytes(self, assignment: Assignment) -> float:
        raise NotImplementedError


class Aggregator(Component):
    """Owns the global model layout: init, per-client view, merge, eval.

    The model itself lives in ``state.params`` (scheme-shaped pytree);
    ``init_global``/``aggregate`` return updated states rather than
    assigning runner attributes.

    ``aggregate`` accepts optional per-client ``weights`` in [0, 1] used
    by asynchronous loops for staleness discounting: a client's
    contribution is blended as ``w * update + (1 - w) * current_global``
    before the scheme's own merge rule runs, so ``weights=None`` (or all
    ones) reproduces the synchronous rule bitwise.

    Merges run through the engine's collective backend (``eng.merger``,
    one compiled call per round, clients on a device axis when a mesh is
    present — staleness weights included) unless
    ``FLConfig(agg_backend="host")`` selects the per-client eager
    scatter loop kept as the parity reference.
    """

    def init_global(self, state: ServerState) -> ServerState:
        raise NotImplementedError

    def client_params(self, state: ServerState, n: int,
                      assignment: Assignment) -> Any:
        """The parameter view shipped to client ``n`` this round."""
        raise NotImplementedError

    def aggregate(
        self,
        state: ServerState,
        results: Dict[int, ClientResult],
        assigns: Dict[int, Assignment],
        weights: Optional[Dict[int, float]] = None,
    ) -> ServerState:
        raise NotImplementedError

    def evaluate(self, state: ServerState) -> float:
        raise NotImplementedError


class LocalTrainer(Component):
    """Runs the local updates for every assigned client of one dispatch.

    Reads the global view through ``aggregator.client_params(state, ...)``
    and the round index from ``state.round`` (the per-client data/rng
    streams are keyed ``(seed, round, client)``).  Returned
    ``ClientResult.params`` trees are host-resident (numpy): the trained
    params are pulled to the host, and the collective aggregation
    backend then uploads each client's blocks and scatters them into the
    dense zero-padded contributions + masks on the device.
    """

    def train_all(self, state: ServerState,
                  assigns: Dict[int, Assignment]) -> Dict[int, ClientResult]:
        raise NotImplementedError


class RoundLoop(Component):
    """Advances the virtual clock by one aggregation event.

    ``run_round(state)`` returns ``(state', log)`` where ``state'`` is a
    new :class:`~repro.fl.types.ServerState` (``dataclasses.replace``)
    with the log appended to ``state'.history`` — the runner only
    installs the returned value and decides whether to checkpoint it.
    """

    def run_round(self, state: ServerState,
                  ) -> Tuple[ServerState, RoundLog]:
        raise NotImplementedError


class ParticipationScheduler(Component):
    """Samples one round's cohort from the client population.

    Contract for ``sample(state, k, exclude)``:

      * returns distinct client ids (draws WITHOUT replacement), none of
        them in ``exclude`` (clients already in flight, semi-async);
      * returns at most ``k`` ids; fewer only when the eligible pool is
        smaller (availability/resource gates, or everyone excluded);
      * consumes ``state.rng`` — the sequential round RNG carried by the
        server state — for the cohort selection, so schedulers sit
        *inside* the seeded history contract (the default uniform policy
        reproduces the loops' legacy inline sampling bitwise) and resume
        exactly from a checkpointed rng state;
      * does O(cohort) expected work: per-client gates are derived from
        keyed hash streams and the population profile, never from
        resident per-client state.

    Round loops call :meth:`~repro.fl.engine.runner.EngineRunner.sample_clients`,
    which delegates here and records participation in
    ``state.participation`` (shared by identity with the population
    registry when one is bound).  Implementations + the ``SCHEDULERS``
    registry live in :mod:`repro.fl.population.schedulers`.
    """

    def sample(self, state: ServerState, k: int, exclude=frozenset()) -> list:
        raise NotImplementedError
