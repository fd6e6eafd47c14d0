"""Micro-batching inference engine for compiled LUT networks.

The LUT-side analogue of ``serve/engine.py``: requests queue up, every
engine tick drains up to ``block`` of them, pads to the fixed block shape,
and runs ONE jitted lookup cascade for the whole block.  A folded network
has no KV cache and no sequential decode — each request is a single
feed-forward row — so the continuous-batching problem reduces to classic
micro-batching: fixed block shape (one XLA compilation, ever), pad the
tail, amortize dispatch overhead across the block.

Since PR 3 the engine is **double-buffered**: JAX dispatch is async, so a
tick *dispatches* block N+1 while block N's device computation is still in
flight and only *retires* (waits on + scatters) a block once ``depth``
blocks are outstanding.  Host-side work — padding the next block, fanning
results back onto requests — overlaps device compute instead of
serializing with it; nothing blocks until :meth:`drain`.  ``depth=1``
reproduces the old synchronous tick exactly.  With ``repro.tracing``
enabled, each piece of a block's path is a span (``engine.fill``,
``engine.put``, ``engine.launch``, ``engine.wait``, ``engine.fetch``,
``engine.scatter``; a whole :meth:`LUTEngine.tick` is ``engine.tick``);
off, the spans cost nothing.

The cascade itself is a ``CompiledLUTNetwork.compile_backend`` executor —
any registered lookup backend (take / onehot / pallas / fused, DESIGN.md
§2), optionally mesh-sharded via ``mesh=`` (DESIGN.md §3) — and fully
self-contained, so an engine can be stood up from a ``.npz`` artifact with
no training state anywhere in the process.  ``block``, ``backend``,
``depth`` and the mesh are fixed at construction (the jitted block
function is compiled once for that shape); the attributes are read-only
and raise on assignment — build a new engine to change them.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.pipeline import CompiledLUTNetwork
from repro.serve.faults import DrainTimeout


@dataclasses.dataclass
class LUTRequest:
    rid: int
    x: np.ndarray                       # [in_features] float input row
    codes: Optional[np.ndarray] = None  # [n_out] int32 result
    logits: Optional[np.ndarray] = None
    done: bool = False
    # dispatch attempts that failed or were abandoned; the fleet's
    # supervision caps this at ResiliencePolicy.max_retries
    attempts: int = 0
    # wall-clock submission time, stamped by callers that track end-to-end
    # request latency (the fleet tier); 0.0 = unstamped
    t_submit: float = 0.0
    # stream (cell-mode) extras: the state codes this step consumes, the
    # next-state codes it produced, and the stream the step belongs to
    state: Optional[np.ndarray] = None       # [n_state] int32
    next_state: Optional[np.ndarray] = None  # [n_state] int32
    stream_id: Optional[object] = None


@dataclasses.dataclass
class LUTEngineStats:
    ticks: int = 0                      # blocks dispatched
    requests: int = 0
    rows_padded: int = 0

    def summary(self) -> dict:
        """Flat JSON-ready snapshot — the supported way for benchmarks and
        dashboards to consume stats.  Per-tick wall time is the
        ``engine.tick`` span of ``repro.tracing``."""
        return {
            "ticks": self.ticks,
            "requests": self.requests,
            "rows_padded": self.rows_padded,
        }


class LUTEngine:
    """Double-buffered micro-batching engine over one planned backend.

    ``depth`` is the maximum number of blocks in flight on the device:
    1 = synchronous (each ``tick`` dispatches and immediately retires its
    block — the pre-PR-3 behavior), 2+ = async double-buffering (``tick``
    dispatches without waiting; the oldest block is retired only when the
    pipeline is full or at :meth:`drain`).
    """

    def __init__(self, net: CompiledLUTNetwork, *, block: int = 256,
                 backend: Optional[str] = None, mesh=None, depth: int = 1,
                 executor=None, cell=None, placement=None,
                 faults=None, scope: Optional[str] = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.net = net
        self._block = int(block)
        self._depth = int(depth)
        self.queue: Deque[LUTRequest] = collections.deque()
        self.stats = LUTEngineStats()
        self._next_rid = 0
        # fault seam (serve/faults.py): when an injector is configured the
        # engine crosses its executor_call seam on every dispatch and reads
        # ages off the injector's skewable clock; scope labels this engine
        # (the tenant/model id under a fleet) for fault matching and
        # DrainTimeout diagnostics
        self._faults = faults
        self._scope = scope
        self._now = faults.clock.now if faults is not None else time.perf_counter
        # (requests, codes, logits, next-state-or-None, t_dispatch),
        # oldest first
        self._inflight: Deque[Tuple] = collections.deque()
        if mesh is not None and placement is not None:
            raise ValueError("pass either mesh= or placement=, not both")
        if cell is not None:
            # stream (cell) mode: the block function is the folded
            # recurrent step (repro.stream.cell) — each request carries
            # its state codes in and its next-state codes out.  The cell
            # owns the per-(backend, placement) jit cache.
            if executor is not None:
                raise ValueError("pass either cell= or executor=")
            if net is not cell.net:
                raise ValueError("cell= must wrap the engine's net")
            if mesh is not None:
                from repro import backends as _b
                placement = _b.Placement(mesh)
            self._cell = cell
            self._cell_backend, self._cell_placement = backend, placement
            key, _ = cell._key(backend, placement)
            self._backend = key[0]
            self._in_features = cell.cell.n_in
            self._n_state = cell.cell.n_state
            self._zero_state = cell.cell.zero_state_code()
            self._executor = None
            self._fwd = None
            self._fault_placement = placement
            return
        self._cell = None
        self._in_features = net.cfg.in_features
        if executor is not None:
            # fleet hook: a pre-built PlannedExecutor (e.g. from the tenant
            # registry's LRU cache) — the engine never plans or caches
            if backend is not None and backend != executor.backend:
                raise ValueError(
                    f"executor runs backend {executor.backend!r}, "
                    f"not {backend!r}")
            if mesh is not None:
                raise ValueError("pass mesh= at executor build time, "
                                 "not alongside executor=")
            self._executor = executor
        else:
            self._executor = net.compile_backend(backend or net.backend,
                                                 mesh=mesh,
                                                 placement=placement)
        self._backend = self._executor.backend
        self._fwd = self._executor.codes_and_logits
        self._fault_placement = getattr(self._executor, "placement", None)

    @property
    def cell(self):
        """The CompiledStreamCell in stream mode, else None."""
        return self._cell

    # -- fixed-at-construction attributes ------------------------------------
    # The jitted block function is compiled once for (block, backend, mesh);
    # silently accepting a new value used to do nothing — now it raises.
    @property
    def block(self) -> int:
        return self._block

    @block.setter
    def block(self, _value):
        raise AttributeError(
            "LUTEngine.block is fixed at construction (the block function "
            "is jit-compiled for this shape); build a new engine instead")

    @property
    def backend(self) -> str:
        return self._backend

    @backend.setter
    def backend(self, _value):
        raise AttributeError(
            "LUTEngine.backend is fixed at construction (the backend is "
            "planned and jitted once); build a new engine instead")

    @property
    def depth(self) -> int:
        return self._depth

    @property
    def inflight(self) -> int:
        """Blocks currently dispatched but not yet retired."""
        return len(self._inflight)

    # -- queueing ------------------------------------------------------------
    def submit(self, x: np.ndarray, *, state: Optional[np.ndarray] = None,
               stream_id=None) -> LUTRequest:
        """Enqueue one input row; returns the request handle.  In cell
        mode ``state`` is the step's state codes (default: initial)."""
        if self._cell is not None and state is None:
            state = np.full((self._n_state,), self._zero_state, np.int32)
        req = LUTRequest(rid=self._next_rid, x=np.asarray(x, np.float32),
                         state=state, stream_id=stream_id)
        self._next_rid += 1
        self.queue.append(req)
        self.stats.requests += 1
        return req

    def submit_many(self, xs: np.ndarray, t_submit: float = 0.0, *,
                    states: Optional[np.ndarray] = None,
                    stream_ids=None) -> List[LUTRequest]:
        """Enqueue every row of ``xs`` with ONE dtype conversion.

        Per-row ``submit`` pays a ``np.asarray`` per request — measurably
        the largest serial cost of bulk workloads (it cannot overlap
        device compute, unlike the per-tick work).  Handles share row
        views of the converted matrix.  ``t_submit`` stamps every handle
        at construction (the fleet's request-latency clock) instead of a
        second per-row pass by the caller.  In cell mode ``states``
        ([n, n_state] int codes, default initial) and ``stream_ids`` ride
        along the same way."""
        with tracing.span("engine.enqueue"):
            xs = np.asarray(xs, np.float32)
            base = self._next_rid
            if self._cell is not None:
                if states is None:
                    states = np.full((len(xs), self._n_state),
                                     self._zero_state, np.int32)
                else:
                    states = np.asarray(states, np.int32)
                reqs = [LUTRequest(rid=base + i, x=row, t_submit=t_submit,
                                   state=s,
                                   stream_id=(None if stream_ids is None
                                              else stream_ids[i]))
                        for i, (row, s) in enumerate(zip(xs, states))]
            else:
                reqs = [LUTRequest(rid=base + i, x=row, t_submit=t_submit)
                        for i, row in enumerate(xs)]
        self._next_rid += len(reqs)
        self.queue.extend(reqs)
        self.stats.requests += len(reqs)
        return reqs

    # -- the pump ------------------------------------------------------------
    # dispatch_block/retire_oldest are public: the multi-tenant fleet tier
    # (serve/fleet.py) drives many engines through them with a GLOBAL
    # in-flight budget, reusing this double-buffered machinery per tenant
    # while owning the cross-tenant retirement order itself.
    def dispatch_block(self) -> List[LUTRequest]:
        """Pad up to ``block`` queued requests and launch the cascade
        WITHOUT waiting for the result (JAX dispatch is async).  Returns
        the dispatched requests ([] when the queue was empty).

        Exception-safe: if the executor (or an injected fault) raises, the
        popped requests are requeued at the FRONT of the queue in their
        original order before the exception propagates — no request is
        lost, no in-flight slot is leaked, and a stream's
        exactly-one-step-queued invariant (the router/fleet busy sets)
        still holds, so the engine accepts new work after a poisoned
        batch."""
        if not self.queue:
            return []
        with tracing.span("engine.fill"):
            batch: List[LUTRequest] = []
            while self.queue and len(batch) < self._block:
                batch.append(self.queue.popleft())
            if tracing.enabled():
                # queue wait of the rows a caller stamped
                now = time.perf_counter()
                waits = [now - req.t_submit for req in batch if req.t_submit]
                tracing.count("queue.wait_s", sum(waits))
                tracing.count("queue.rows", len(waits))
            xb = np.zeros((self._block, self._in_features), np.float32)
            # one C-level fill, not a per-row python loop: the dispatch
            # path is host-side work the async pipeline hides behind
            # device compute
            xb[:len(batch)] = [req.x for req in batch]
        # stamp BEFORE the fault seam: an injected hang skews the clock
        # during dispatch, so the block's age already exceeds the stall
        # when supervision first looks at it
        t0 = self._now()
        try:
            if self._faults is not None:
                self._faults.executor_call(scope=self._scope,
                                           placement=self._fault_placement)
            if self._cell is not None:
                # a stream step takes host arrays: the state fill and the
                # transfer are part of its launch
                with tracing.span("engine.launch"):
                    sb = np.full((self._block, self._n_state),
                                 self._zero_state, np.int32)
                    sb[:len(batch)] = [req.state for req in batch]
                    codes, logits, s_next = self._cell.step(
                        xb, sb, backend=self._cell_backend,
                        placement=self._cell_placement)
            else:
                with tracing.span("engine.put"):
                    xd = jnp.asarray(xb)
                with tracing.span("engine.launch"):
                    codes, logits = self._fwd(xd)
                s_next = None
        except BaseException:
            for req in batch:
                req.attempts += 1
            self.queue.extendleft(reversed(batch))
            raise
        self._inflight.append((batch, codes, logits, s_next, t0))
        self.stats.rows_padded += self._block - len(batch)
        self.stats.ticks += 1
        return batch

    def oldest_age(self) -> float:
        """Seconds since the oldest in-flight block was dispatched, on the
        fault-injector clock when one is configured (0.0 when idle).  This
        is what deadline supervision reads — an injected hang shows up
        here without any real sleeping."""
        if not self._inflight:
            return 0.0
        return self._now() - self._inflight[0][4]

    def abandon_oldest(self) -> List[LUTRequest]:
        """Give up on the oldest in-flight block WITHOUT waiting on the
        device: requeue its requests at the front of the queue (original
        order, attempts incremented) and return them.  The deadline path —
        the device may still complete the abandoned computation, but its
        results are dropped and the rows recomputed, which is safe because
        every backend is bit-identical and requests are idempotent."""
        if not self._inflight:
            return []
        batch = self._inflight.popleft()[0]
        for req in batch:
            req.attempts += 1
        self.queue.extendleft(reversed(batch))
        return batch

    def retire_oldest(self) -> List[LUTRequest]:
        """Wait on the OLDEST in-flight block, fan results out, and return
        the completed requests ([] when nothing is in flight)."""
        if not self._inflight:
            return []
        batch, codes, logits, s_next, _t0 = self._inflight.popleft()
        if tracing.enabled():
            # waiting on the device apart from the copies (untraced, the
            # copies below wait)
            with tracing.span("engine.wait"):
                jax.block_until_ready((codes, logits, s_next))
        with tracing.span("engine.fetch"):
            codes_np, logits_np = np.asarray(codes), np.asarray(logits)
        with tracing.span("engine.scatter"):
            # list(ndarray) materializes the row views in one C loop
            for req, c, lg in zip(batch, list(codes_np), list(logits_np)):
                req.codes = c
                req.logits = lg
                req.done = True
            if s_next is not None:
                for req, s in zip(batch, list(np.asarray(s_next))):
                    req.next_state = s
        return batch

    def _dispatch(self) -> int:
        return len(self.dispatch_block())

    def _retire(self) -> int:
        return len(self.retire_oldest())

    def tick(self) -> int:
        """Dispatch one block; retire the oldest once ``depth`` blocks are
        in flight.  Returns the number of requests completed this tick
        (with ``depth > 1`` completion trails dispatch — drain() retires
        the stragglers)."""
        with tracing.span("engine.tick"):
            if self.queue:
                self._dispatch()
            completed = 0
            while len(self._inflight) > self._depth - 1:
                completed += self._retire()
        return completed

    def drain(self, timeout: Optional[float] = None) -> int:
        """Retire every in-flight block (the only place the engine blocks
        on the device unconditionally).

        ``timeout`` bounds the wait per block: before each blocking
        retire, if the oldest in-flight block is already older than
        ``timeout`` seconds (injector clock when faults are configured),
        a diagnostic :class:`DrainTimeout` names the stuck scope and
        block instead of blocking forever.  The check is age-based, so an
        injected hang (clock skew) trips it immediately; a genuinely
        wedged device call that has not yet exceeded the age can still
        block once — Python offers no safe way to interrupt a foreign
        blocking call, and the age check is the honest contract."""
        completed = 0
        while self._inflight:
            if timeout is not None:
                age = self.oldest_age()
                if age > timeout:
                    batch = self._inflight[0][0]
                    scope = self._scope if self._scope is not None else "engine"
                    raise DrainTimeout(
                        f"drain timed out: oldest in-flight block on "
                        f"{scope!r} ({len(batch)} requests, backend "
                        f"{self._backend!r}) is {age:.3f}s old "
                        f"(timeout {timeout:.3f}s)",
                        scope=self._scope, requests=len(batch), age_s=age)
            completed += self._retire()
        return completed

    def run(self, xs: np.ndarray) -> np.ndarray:
        """Convenience: submit every row of ``xs``, tick until the queue
        is empty, drain the pipeline.

        Returns logits [len(xs), n_out] in submission order."""
        reqs = self.submit_many(xs)
        while self.queue:
            self.tick()
        self.drain()
        return np.stack([r.logits for r in reqs])
