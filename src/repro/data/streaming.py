"""Streaming client pipelines: shard views, the host RNG contract, and
a prefetching per-client batch loader.

Instead of materializing every client's full shard
(``x_train[part]`` copies — O(dataset) extra host memory per setup),
:class:`ShardView` keeps ONE global array per split plus per-client
index vectors and gathers only the minibatches a round actually
touches.  Views quack like arrays for everything the FL runtime does
with a shard (``len``, fancy indexing, iteration-free gathers), so the
sequential trainer, the legacy runners and ``local_train`` consume them
unchanged.

:class:`ClientDataLoader` owns the *host RNG contract* shared by every
training backend: client ``n`` in round ``r`` draws from
``np.random.default_rng((seed, r, n))`` — ``tau`` training-batch index
draws of size ``batch``, then 3 estimate-batch draws.  Batches prepared
through the loader are therefore byte-identical to both the sequential
loop and the pre-subsystem cohort path.  ``prefetch`` stages the next
cohort group's host gather on a background thread (numpy only — no jax
calls off the main thread) while the device runs the current group.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence, Tuple

import numpy as np


class ShardView:
    """Lazy per-client view: ``view[idx] == base[indices[idx]]``."""

    __slots__ = ("base", "indices")

    def __init__(self, base: np.ndarray, indices: np.ndarray):
        self.base = base
        self.indices = np.asarray(indices, np.int64)

    def __len__(self) -> int:
        return len(self.indices)

    def __getitem__(self, idx):
        return self.base[self.indices[idx]]

    @property
    def shape(self):
        return (len(self.indices),) + self.base.shape[1:]

    @property
    def dtype(self):
        return self.base.dtype

    @property
    def ndim(self) -> int:
        return self.base.ndim

    def materialize(self) -> np.ndarray:
        return self.base[self.indices]

    def __array__(self, dtype=None):
        out = self.materialize()
        return out.astype(dtype) if dtype is not None else out


class VirtualShardList:
    """Population-sized shard sequence backed by a pure index function.

    ``parts[n]`` builds a :class:`ShardView` from ``index_fn(n)`` on
    demand, so a 10^6-client partition costs nothing until a client is
    actually sampled — the O(cohort) stand-in for a materialized
    ``num_clients``-long partition list.  ``index_fn`` must be pure in
    ``n`` (repro.fl.population.VirtualPartition), which is what keeps
    shards identical across processes and independent of the population
    size or query order.  ``registry`` optionally carries the
    :class:`~repro.fl.population.PopulationRegistry` the engine binds
    its heterogeneity model and participation bookkeeping to.
    """

    virtual = True

    def __init__(self, base: np.ndarray, index_fn: Callable[[int], np.ndarray],
                 size: int, registry=None):
        self.base = base
        self.index_fn = index_fn
        self.size = size
        self.registry = registry

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, n) -> ShardView:
        n = int(n)
        if not 0 <= n < self.size:
            raise IndexError(n)
        return ShardView(self.base, self.index_fn(n))

    def __iter__(self):
        return (self[n] for n in range(self.size))


def make_shards(x: np.ndarray, y: np.ndarray, parts,
                streaming: bool = True):
    """Per-client (parts_x, parts_y) from global arrays + index lists.

    ``streaming=True`` returns :class:`ShardView`s over the single
    global array; ``streaming=False`` materializes the legacy per-client
    copies.  Gathered minibatches are byte-identical either way.

    A *lazy* partition — anything exposing ``indices(n)`` and ``len``,
    e.g. :class:`repro.fl.population.VirtualPartition` — yields
    :class:`VirtualShardList`s instead: no per-client index arrays are
    materialized, each sampled client's shard is derived on demand.
    """
    if callable(getattr(parts, "indices", None)):
        size = len(parts)
        return (VirtualShardList(x, parts.indices, size),
                VirtualShardList(y, parts.indices, size))
    if streaming:
        return ([ShardView(x, p) for p in parts],
                [ShardView(y, p) for p in parts])
    return [x[p] for p in parts], [y[p] for p in parts]


def draw_batch_indices(rng: np.random.Generator, num_samples: int,
                       tau: int, batch_size: int, estimate: bool,
                       tau_pad: Optional[int] = None
                       ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """One client-round's minibatch indices from ``rng``: tau training
    draws, then (with ``estimate``) 3 estimate draws, each of
    ``batch_size`` indices into the client's ``num_samples``.

    Returns ``(idx, est_idx)`` with ``idx`` of shape
    ``(tau_pad or tau, batch_size)`` (padding steps repeat the last real
    batch — they are masked no-ops in the cohort step) and ``est_idx``
    of shape ``(3, batch_size)`` or None.
    """
    idx = np.stack([rng.integers(0, num_samples, batch_size)
                    for _ in range(tau)])
    pad = (tau_pad or tau) - tau
    if pad > 0:
        idx = np.concatenate([idx, np.broadcast_to(idx[-1], (pad, batch_size))])
    est_idx = None
    if estimate:
        est_idx = np.stack([rng.integers(0, num_samples, batch_size)
                            for _ in range(3)])
    return idx, est_idx


def round_batch_indices(seed: int, rnd: int, n: int, num_samples: int,
                        tau: int, batch_size: int, estimate: bool,
                        tau_pad: Optional[int] = None
                        ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The engine's host RNG contract: :func:`draw_batch_indices` from
    client ``n``'s round-``rnd`` stream ``default_rng((seed, rnd, n))``."""
    return draw_batch_indices(np.random.default_rng((seed, rnd, n)),
                              num_samples, tau, batch_size, estimate, tau_pad)


def stack_client_shards(per_client: Sequence[np.ndarray], chunks: int,
                        step_leading: bool = False):
    """Stack per-client batch arrays into ``chunks`` contiguous groups.

    The cohort trainer's device mesh wants *per-device host shards*, not
    one monolithic stacked batch: each chunk is stacked separately (and
    stays a separate numpy array) so the prefetch thread hands the main
    thread exactly the pieces ``device_put`` ships, one per device —
    the full cohort batch never exists contiguously on the host.

    ``step_leading=True`` moves the per-client step axis in front of the
    client axis (``(C/chunks, tau, ...) -> (tau, C/chunks, ...)``), the
    layout the compiled cohort step consumes.  ``chunks=1`` reproduces
    the single-device monolithic stack bitwise.
    """
    n = len(per_client)
    if n % chunks:
        raise ValueError(f"{n} clients not divisible into {chunks} chunks")
    per = n // chunks
    out = []
    for c in range(chunks):
        stk = np.stack(per_client[c * per:(c + 1) * per])
        out.append(np.moveaxis(stk, 0, 1) if step_leading else stk)
    return out


class ClientDataLoader:
    """Per-client minibatch streams over (possibly lazy) shards.

    One instance serves a whole run: the engine binds it to its
    partition set at construction and trainers call :meth:`draw_round`
    for host batches / :meth:`prefetch` to pipeline host gathers ahead
    of device steps.
    """

    def __init__(self, parts_x: Sequence, parts_y: Sequence,
                 prefetch_depth: int = 2):
        if len(parts_x) != len(parts_y):
            raise ValueError(f"{len(parts_x)} x-shards vs {len(parts_y)} y")
        self.parts_x, self.parts_y = parts_x, parts_y
        self.prefetch_depth = max(1, prefetch_depth)
        # telemetry recorder (repro.obs); the engine runner rebinds this
        # to its live recorder — the default no-op keeps standalone
        # loaders uninstrumented at zero cost
        from repro.obs.recorder import NOOP
        self.obs = NOOP
        # live prefetch workers: (stop event, thread) pairs, so close()
        # can release them deterministically even when a round body died
        # before its generator's finally ran
        self._workers: list = []
        self._workers_lock = threading.Lock()

    def close(self) -> None:
        """Release every background prefetch worker this loader started.

        Safe to call repeatedly.  Without it, a generator abandoned by an
        exception in the round body only stops its worker when the GC
        collects the generator — until then the daemon thread sits
        blocked on its bounded queue.
        """
        with self._workers_lock:
            workers, self._workers = self._workers, []
        for stop, _ in workers:
            stop.set()
        for _, t in workers:
            t.join(timeout=5.0)

    def __enter__(self) -> "ClientDataLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @classmethod
    def from_dataset(cls, dataset, parts: Sequence[np.ndarray],
                     streaming: bool = True, **kw) -> "ClientDataLoader":
        px, py = make_shards(dataset.x, dataset.y, parts, streaming)
        return cls(px, py, **kw)

    @property
    def num_clients(self) -> int:
        return len(self.parts_x)

    def num_samples(self, n: int) -> int:
        return len(self.parts_y[n])

    def shard(self, n: int):
        return self.parts_x[n], self.parts_y[n]

    def gather(self, n: int, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Host batch arrays for arbitrary (possibly 2-D) sample indices."""
        x, y = self.parts_x[n], self.parts_y[n]
        return x[idx], y[idx]

    def draw_round(self, n: int, *, seed: int, rnd: int, tau: int,
                   batch_size: int, estimate: bool,
                   tau_pad: Optional[int] = None):
        """(xs, ys, est) for one client-round under the RNG contract.

        ``xs``/``ys`` lead with the (padded) step axis; ``est`` is the
        ``(3, batch, ...)`` estimate-batch pair or None.
        """
        idx, est_idx = round_batch_indices(
            seed, rnd, n, self.num_samples(n), tau, batch_size, estimate,
            tau_pad)
        xs, ys = self.gather(n, idx)
        est = self.gather(n, est_idx) if est_idx is not None else None
        return xs, ys, est

    def prefetch(self, items: Iterable[Any],
                 fn: Callable[[Any], Any]) -> Iterator[Any]:
        """Yield ``fn(item)`` in order, computing up to ``prefetch_depth``
        items ahead on a background thread.

        ``fn`` must be host-only (numpy): it runs off the main thread so
        the device step of group *g* overlaps the gather of *g+1*.
        """
        items = list(items)
        if len(items) <= 1:  # nothing to overlap
            for it in items:
                yield fn(it)
            return
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_depth)
        stop = threading.Event()
        _END, _ERR = object(), object()

        def put(obj) -> bool:
            """Bounded put that gives up when the consumer is gone."""
            while not stop.is_set():
                try:
                    q.put(obj, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for it in items:
                    if stop.is_set() or not put(fn(it)):
                        return
                put(_END)
            except BaseException as e:  # surfaced in the consumer
                put((_ERR, e))

        t = threading.Thread(target=worker, daemon=True,
                             name="client-data-prefetch")
        with self._workers_lock:
            self._workers.append((stop, t))
        t.start()
        obs = self.obs
        try:
            while True:
                if obs.enabled:
                    # stall = consumer time blocked on the staging thread;
                    # depth sampled just before the blocking get
                    obs.observe("data.prefetch_depth", q.qsize())
                    t0 = time.perf_counter()
                    got = q.get()
                    obs.observe("data.prefetch_stall_s",
                                time.perf_counter() - t0)
                else:
                    got = q.get()
                if got is _END:
                    break
                if isinstance(got, tuple) and len(got) == 2 \
                        and got[0] is _ERR:
                    raise got[1]
                yield got
        finally:
            # consumer done or abandoned mid-stream (downstream error /
            # closed generator): release the worker, don't leak it or
            # the staged batches it is blocked on
            stop.set()
            while not q.empty():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)
            with self._workers_lock:
                self._workers = [(s, th) for s, th in self._workers
                                 if th is not t and th.is_alive()]
