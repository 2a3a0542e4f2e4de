"""State-exchange backends for sharded partitioning.

A backend is anything with ``.rank``, ``.world``, and
``exchange(key, state) -> [ShardState] * world`` — an all-gather at a
named rendezvous point (``p<pass>_r<round>`` or ``final``).  Three
implementations, cheapest first:

* ``ThreadExchange`` — all workers are threads of one process; states
  move through a dict guarded by a condition variable.  This is the
  **emulated** backend tier-1 runs: ``run_worker`` executes the exact
  same code against it as against the multi-process backends.
* ``FileExchange`` — each worker is its own process; states are
  published as atomically-renamed ``.npz`` files in a shared directory
  and peers poll for them.  No coordinator, no sockets — works anywhere
  a shared filesystem does (which out-of-core partitioning already
  assumes for the graph itself).
* ``JaxDistributedExchange`` — ``jax.distributed``-initialized variant
  of FileExchange: rank/world come from the JAX process group
  (``jax.process_index()``), bulk state still moves through the shared
  directory.  Requires a configured coordinator; gated so the rest of
  the stack never imports it implicitly.

Every backend is deterministic in *content*: merges are commutative and
associative (``StreamingPartitioner.merge_rules``), so arrival order
never matters.
"""
from __future__ import annotations

import os
import threading
import time

from .state import ShardState

__all__ = ["ExchangeTimeout", "FileExchange", "JaxDistributedExchange",
           "ThreadExchange"]


class ExchangeTimeout(RuntimeError):
    """A rendezvous did not complete in time (a peer died or stalled)."""


class ThreadExchange:
    """In-process hub: create once with the world size, hand each worker
    thread its ``for_rank(r)`` view.  ``abort(exc)`` wakes every waiter
    with the failure so one dead worker cannot hang the rest."""

    def __init__(self, world: int, *, timeout_s: float = 120.0):
        self.world = int(world)
        self.timeout_s = float(timeout_s)
        self._slots: dict = {}      # key -> {rank: ShardState}
        self._reads: dict = {}      # key -> ranks done collecting
        self._cv = threading.Condition()
        self._exc: BaseException | None = None

    def abort(self, exc: BaseException) -> None:
        with self._cv:
            if self._exc is None:
                self._exc = exc
            self._cv.notify_all()

    def for_rank(self, rank: int) -> "_ThreadExchangeView":
        return _ThreadExchangeView(self, int(rank))

    def _exchange(self, rank: int, key: str, state: ShardState):
        deadline = time.monotonic() + self.timeout_s
        with self._cv:
            self._slots.setdefault(key, {})[rank] = state
            self._cv.notify_all()
            while len(self._slots.get(key, ())) < self.world:
                if self._exc is not None:
                    raise RuntimeError(
                        f"exchange {key!r} aborted: peer failed"
                    ) from self._exc
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cv.wait(remaining):
                    raise ExchangeTimeout(
                        f"rank {rank}: exchange {key!r} incomplete after "
                        f"{self.timeout_s:.0f}s "
                        f"({len(self._slots[key])}/{self.world} states)")
            states = [self._slots[key][r] for r in range(self.world)]
            done = self._reads.setdefault(key, set())
            done.add(rank)
            if len(done) == self.world:     # last reader frees the slot
                del self._slots[key], self._reads[key]
        return states


class _ThreadExchangeView:
    def __init__(self, hub: ThreadExchange, rank: int):
        self._hub = hub
        self.rank = rank
        self.world = hub.world

    def exchange(self, key: str, state: ShardState):
        return self._hub._exchange(self.rank, key, state)


class FileExchange:
    """Shared-directory all-gather: publish ``<key>_w<rank>.npz``
    atomically, poll until every peer's file exists, load them all.
    Files persist after the rendezvous — that is a feature: a worker
    resuming from a checkpoint mid-pass finds its peers' earlier rounds
    still on disk and re-joins without any replay protocol."""

    def __init__(self, directory: str, rank: int, world: int, *,
                 timeout_s: float = 300.0, poll_s: float = 0.05):
        self.directory = directory
        self.rank = int(rank)
        self.world = int(world)
        self.timeout_s = float(timeout_s)
        self.poll_s = float(poll_s)
        os.makedirs(directory, exist_ok=True)

    def _path(self, key: str, rank: int) -> str:
        return os.path.join(self.directory, f"{key}_w{rank:03d}.npz")

    def exchange(self, key: str, state: ShardState):
        state.save(self._path(key, self.rank))
        deadline = time.monotonic() + self.timeout_s
        states = []
        for r in range(self.world):
            path = self._path(key, r)
            while not os.path.exists(path):
                if time.monotonic() > deadline:
                    raise ExchangeTimeout(
                        f"rank {self.rank}: no state from rank {r} at "
                        f"{path} after {self.timeout_s:.0f}s")
                time.sleep(self.poll_s)
            states.append(ShardState.load(path))
        return states


class JaxDistributedExchange(FileExchange):
    """FileExchange whose rank/world come from an initialized
    ``jax.distributed`` process group (real multi-host launches where
    each worker also drives its own accelerators).  The group provides
    identity and lifetime; bulk state still rides the shared directory —
    the O(|V|) state per round is filesystem-cheap next to the O(|E|)
    stream every worker is already reading from it."""

    def __init__(self, directory: str, *, coordinator_address=None,
                 num_processes=None, process_id=None,
                 timeout_s: float = 300.0, poll_s: float = 0.05):
        import jax
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        except Exception as e:       # no coordinator / already initialized
            if "already initialized" not in str(e):
                raise RuntimeError(
                    "jax.distributed.initialize failed — set "
                    "--coordinator (JAX_COORDINATOR_ADDRESS), "
                    "--workers, and --rank, or use --backend fs"
                ) from e
        super().__init__(directory, rank=jax.process_index(),
                         world=jax.process_count(), timeout_s=timeout_s,
                         poll_s=poll_s)
