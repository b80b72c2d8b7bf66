"""Graph-solver service: continuous-batching request layer over the fused
device-resident inference engine (DESIGN.md §9, §14).

The engine/driver split mirrors the training half (DESIGN.md §8): the
fused solve (`repro.core.engine.get_solve_step`) is the numerical engine —
one jitted while_loop per dispatch, one host↔device sync — and this module
is the request-level driver on top: submission, power-of-two size
bucketing with isolated-node padding (`repro.serving.bucketing`), a
per-bucket compiled-step cache, batched dispatch, and per-request result
extraction.  Policy parameters come from a `repro.checkpoint` snapshot or
are injected directly.

Two serving modes share every layer below submission:

- **Sync (batch) mode** — the original demo/test path: ``submit()``
  queues, ``drain()`` serves everything queued in bucket order.
- **Async (SLO) mode** — ``submit_async()`` returns a :class:`SolveFuture`
  immediately; a background thread consults the deadline-aware
  :class:`~repro.serving.scheduler.DeadlineScheduler` (EDF among ready
  queues, anti-starvation override, partial dispatch after
  ``max_wait_ms``, depth-bounded admission with
  :class:`ServiceOverloaded` fast-rejects) and dispatches batches
  continuously.  Per-request enqueue/dispatch/complete timestamps ride on
  every :class:`SolveResponse`, making tail latency a measured quantity
  (`benchmarks/serving_latency.py`).

``warmup(buckets, problems)`` traces, lowers, and compiles every expected
(bucket, problem, mesh) executable OFF the request path, so the first real
dispatch of a bucket never eats a cold jit compile; compile time is
accounted in ``ServiceStats.compile_seconds``, never in
``solve_seconds``.  Pair with
:func:`repro.compile_cache.enable_compile_cache` to persist
compiled executables across process restarts.

    svc = GraphSolverService.from_checkpoint(ckpt_dir, cfg)
    svc.warmup([16, 32])                    # zero cold compiles under traffic
    fut = svc.submit_async(adj, deadline_ms=100.0)
    resp = fut.result()                     # SolveResponse with timestamps
    svc.close()                             # or: with svc: ...
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Union

import numpy as np

from ..core.graphrep import GraphRep, get_rep
from ..core.mesh import normalize_spatial
from ..core.policy import PolicyConfig, PolicyParams
from .bucketing import (MIN_BUCKET, BatchPlan, bucket_nodes, build_plan,
                        plan_batches, unpad_solution)
from .scheduler import DeadlineScheduler, PendingRequest


class ServiceOverloaded(RuntimeError):
    """Admission-control fast-reject: the async queue is at its depth
    bound.  Raised by ``submit_async`` so the caller can shed/retry
    instead of queueing unbounded (and therefore deadline-doomed) work."""


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    id: int
    adj: np.ndarray            # (n, n) dense adjacency
    n: int
    problem: str = "mvc"
    enqueue_t: float = 0.0     # perf_counter at submission


@dataclasses.dataclass(frozen=True)
class SolveResponse:
    id: int
    solution: np.ndarray       # (n,) mask over the REQUEST's nodes
    size: int                  # |S|
    policy_evals: int          # evals of the batch this request rode in
    bucket: int                # padded node count it was served at
    problem: str
    # per-request latency accounting (all time.perf_counter values;
    # 0.0 when the request was constructed outside the service):
    enqueue_t: float = 0.0     # submission
    dispatch_t: float = 0.0    # its batch entered the device
    complete_t: float = 0.0    # its batch's results were fetched

    @property
    def latency_s(self) -> float:
        """Submission-to-completion wall time (queue wait + solve)."""
        return self.complete_t - self.enqueue_t

    @property
    def wait_s(self) -> float:
        """Queue wait: submission to batch dispatch."""
        return self.dispatch_t - self.enqueue_t


@dataclasses.dataclass
class ServiceStats:
    requests: int = 0
    batches: int = 0
    partial_batches: int = 0   # dispatches with unused (padded) rows
    compiles: int = 0          # REQUEST-PATH compiled-step cache misses
    warmup_compiles: int = 0   # ahead-of-time compiles via warmup()
    cache_hits: int = 0
    rejected: int = 0          # admission-control fast-rejects
    padded_rows: int = 0       # unused batch rows dispatched (all buckets)
    # compile (trace+lower+jit, measured on a born-done dummy batch) is
    # accounted separately from the steady-state device solve so latency
    # numbers derived from the service are honest (DESIGN.md §14):
    compile_seconds: float = 0.0
    solve_seconds: float = 0.0
    padded_rows_by_bucket: Dict[int, int] = dataclasses.field(
        default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class SolveFuture:
    """Completion handle for one async submission.  ``result()`` blocks
    until the background scheduler has dispatched the request's batch;
    a dispatch failure re-raises here."""

    def __init__(self, request_id: int):
        self.id = request_id
        self._event = threading.Event()
        self._response: Optional[SolveResponse] = None
        self._exception: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> SolveResponse:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.id} not served "
                               f"within {timeout}s")
        if self._exception is not None:
            raise self._exception
        return self._response

    def _set_result(self, response: SolveResponse) -> None:
        self._response = response
        self._event.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._exception = exc
        self._event.set()


class GraphSolverService:
    """Batched graph-solver frontend over the fused inference engine.

    Parameters
    ----------
    params : PolicyParams — the (pre)trained policy.
    cfg : PolicyConfig — supplies num_layers and the rep/spatial selection
        (the same config-driven switches as training; the service always
        dispatches to the fused device engine — use ``repro.core.solve``
        directly for the host-loop reference).  ``cfg.spatial`` selects
        the 2-D ``(data, graph)`` mesh (DESIGN.md §10): each bucket
        dispatch spreads its rows across the ``data`` axis, so
        ``max_batch`` is the PER-DEVICE row count and one dispatch serves
        ``max_batch × dp`` requests.
    multi_node : adaptive top-d commit schedule (§4.5.1) per evaluation.
    max_batch : rows per data-axis device per dispatch; every batch is
        padded to exactly ``max_batch × dp`` rows so each
        (bucket, problem, mesh) triple compiles ONCE.
    sparse_max_degree : sparse backend only — neighbor-list width per
        bucket.  The default pins it to the bucket's node count (the only
        traffic-independent safe bound), keeping shapes fully static; pass
        a smaller cap when the traffic's degrees are bounded (graphs
        exceeding it are rejected rather than silently truncated).
    csr_max_edges : csr backend only — directed edge slots per bucket, the
        edge-array analogue of ``sparse_max_degree``.  The default pins it
        to nb² (the traffic-independent bound); pass the traffic's true
        edge bound to keep per-dispatch state edge-proportional (graphs
        exceeding it are rejected rather than silently truncated).
    max_wait_ms : async mode — partial-dispatch bound: a queue's head
        never waits longer than this for batch companions before its
        (possibly underfilled) batch dispatches (DESIGN.md §14).
    max_queue_depth : async mode — admission bound: ``submit_async``
        raises :class:`ServiceOverloaded` once this many requests are
        queued, shedding load instead of letting every deadline blow.
    default_deadline_ms : async mode — deadline applied when a
        ``submit_async`` call passes none (None → no deadline; such
        requests sort last in the EDF order).
    starvation_factor : async mode — a ready queue head older than
        ``starvation_factor × max_wait_ms`` preempts the EDF order
        (oldest first), bounding rare-bucket wait under hot-bucket floods.
    """

    def __init__(self, params: PolicyParams, cfg: PolicyConfig, *,
                 rep: Union[str, GraphRep, None] = None,
                 multi_node: bool = True, max_batch: int = 8,
                 min_bucket: int = MIN_BUCKET,
                 sparse_max_degree: Optional[int] = None,
                 csr_max_edges: Optional[int] = None,
                 max_wait_ms: float = 50.0,
                 max_queue_depth: int = 512,
                 default_deadline_ms: Optional[float] = None,
                 starvation_factor: float = 2.0):
        from ..core.engine import get_solve_step
        self.params = params
        self.cfg = cfg
        self.rep = get_rep(rep if rep is not None else cfg.graph_rep)
        self.multi_node = multi_node
        self.max_batch = max_batch
        self.mesh_shape = normalize_spatial(cfg.spatial)   # (dp, sp)
        # bucket dispatch spreads rows over the data axis: max_batch rows
        # per device, max_batch·dp per compiled batch
        self.rows_per_dispatch = max_batch * self.mesh_shape[0]
        self.min_bucket = min_bucket
        self.sparse_max_degree = sparse_max_degree
        self.csr_max_edges = csr_max_edges
        self.default_deadline_ms = default_deadline_ms
        self.stats = ServiceStats()
        self._queue: Deque[SolveRequest] = deque()
        self._next_id = 0
        self._compiled: Dict[tuple, object] = {}
        self._bucket_reps: Dict[int, GraphRep] = {}
        self._results: Dict[int, SolveResponse] = {}
        self._get_solve_step = get_solve_step
        # async plumbing: _cond guards queue/scheduler/id/running state,
        # _device_lock serializes compile + dispatch device work
        self._cond = threading.Condition()
        self._device_lock = threading.Lock()
        self._sched = DeadlineScheduler(
            self.rows_per_dispatch, max_wait_ms=max_wait_ms,
            max_queue_depth=max_queue_depth,
            starvation_factor=starvation_factor, min_bucket=min_bucket)
        self._thread: Optional[threading.Thread] = None
        self._running = False

    @classmethod
    def from_checkpoint(cls, ckpt_dir, cfg: PolicyConfig,
                        step: Optional[int] = None,
                        **kw) -> "GraphSolverService":
        """Load policy params from a `repro.checkpoint` snapshot."""
        from ..checkpoint import load_policy
        params, _step = load_policy(ckpt_dir, cfg, step)
        return cls(params, cfg, **kw)

    # -- request intake -----------------------------------------------------
    def _validate(self, adj: np.ndarray, problem: str) -> np.ndarray:
        """Reject malformed adjacencies and unknown / padding-unsafe
        environments up front (``env.ensure_padding_safe``) instead of
        failing mid-dispatch with other requests in flight."""
        from ..core import env as env_lib
        env_lib.ensure_padding_safe(problem)
        adj = np.asarray(adj, np.float32)
        if adj.ndim != 2 or adj.shape[0] != adj.shape[1]:
            raise ValueError(f"expected a square (n, n) adjacency, "
                             f"got {adj.shape}")
        return adj

    def _make_request(self, adj: np.ndarray, problem: str) -> SolveRequest:
        # caller holds self._cond
        rid = self._next_id
        self._next_id += 1
        return SolveRequest(id=rid, adj=adj, n=adj.shape[0],
                            problem=problem,
                            enqueue_t=time.perf_counter())

    def submit(self, adj: np.ndarray, problem: str = "mvc") -> int:
        """Sync mode: enqueue one graph for the next ``drain()``; returns
        the request id."""
        adj = self._validate(adj, problem)
        with self._cond:
            req = self._make_request(adj, problem)
            self._queue.append(req)
            self.stats.requests += 1
        return req.id

    def submit_async(self, adj: np.ndarray, problem: str = "mvc",
                     deadline_ms: Optional[float] = None) -> SolveFuture:
        """Async mode: admit one graph into the deadline scheduler and
        return a :class:`SolveFuture` immediately.  The background
        dispatch thread (started on first use) forms batches continuously
        — no ``drain()`` involved.  Raises :class:`ServiceOverloaded`
        at the admission bound."""
        adj = self._validate(adj, problem)
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        with self._cond:
            req = self._make_request(adj, problem)
            deadline_t = (req.enqueue_t + deadline_ms / 1e3
                          if deadline_ms is not None else math.inf)
            future = SolveFuture(req.id)
            if not self._sched.offer(PendingRequest(req, deadline_t,
                                                    future)):
                self.stats.rejected += 1
                raise ServiceOverloaded(
                    f"request rejected: {len(self._sched)} queued at the "
                    f"admission bound ({self._sched.max_queue_depth})")
            self.stats.requests += 1
            self._start_locked()
            self._cond.notify_all()
        return future

    def pending(self) -> int:
        return len(self._queue) + len(self._sched)

    # -- compiled-step cache / warmup ---------------------------------------
    def _bucket_rep(self, nb: int) -> GraphRep:
        """The backend a bucket dispatches through.  Sparse states must pin
        their neighbor-list width per bucket, csr states their edge-slot
        count (the singletons derive both from each batch's true topology,
        which would retrace the jitted solve whenever traffic changes
        it)."""
        if self.rep.name not in ("sparse", "csr"):
            return self.rep
        rep = self._bucket_reps.get(nb)
        if rep is None:
            if self.rep.name == "csr":
                from ..core.graphrep import CsrRep
                rep = CsrRep(max_edges=self.csr_max_edges or nb * nb)
            else:
                from ..core.graphrep import SparseRep
                rep = SparseRep(max_degree=self.sparse_max_degree or nb)
            self._bucket_reps[nb] = rep
        return rep

    def _cache_key(self, nb: int, problem: str) -> tuple:
        return (nb, problem, self.rep.name, self.multi_node,
                self.cfg.num_layers, self.mesh_shape,
                self.cfg.kernel, self.cfg.compute)

    def _ensure_compiled(self, nb: int, problem: str, *,
                         warm: bool = False):
        """Build AND compile the fused solve for one (bucket, problem),
        timing the compile into ``stats.compile_seconds``.  Compilation is
        forced by executing on a batch of empty graphs: identical shapes
        to a real dispatch, but every row is born done, so the while_loop
        exits immediately and the measured cost is (within ~a ms) pure
        trace+lower+jit — the same trick ``warmup()`` uses to keep
        compiles off the request path entirely."""
        key = self._cache_key(nb, problem)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ..core.inference import MAX_D, init_solve_state
        fn = self._get_solve_step(
            rep=self._bucket_rep(nb), problem=problem,
            num_layers=self.cfg.num_layers,
            use_adaptive=self.multi_node, spatial=self.mesh_shape,
            kernel=self.cfg.kernel, compute=self.cfg.compute)
        dummy = np.zeros((self.rows_per_dispatch, nb, nb), np.float32)
        state = self._place(init_solve_state(self._bucket_rep(nb), dummy,
                                             problem))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(self.params, state,
                                 jnp.asarray(nb + MAX_D, jnp.int32)))
        self.stats.compile_seconds += time.perf_counter() - t0
        if warm:
            self.stats.warmup_compiles += 1
        else:
            self.stats.compiles += 1
        self._compiled[key] = fn
        return fn

    def _place(self, state):
        """Mesh placement of a fresh solve state — the layout the fused
        solve keeps it in (``mesh.solve_state_specs``), so the state
        buffers it donates alias the while_loop carry from the first
        call."""
        if self.mesh_shape == (1, 1):
            return state
        from ..core.mesh import make_mesh, shard_solve_state
        return shard_solve_state(make_mesh(*self.mesh_shape), state)

    def _solve_fn(self, nb: int, problem: str):
        """Per-bucket compiled-step cache: one fused solve per
        (bucket, problem) — shapes are fixed by the bucketing (and, on the
        sparse backend, by the pinned neighbor-list width), so a hit never
        retraces."""
        fn = self._compiled.get(self._cache_key(nb, problem))
        if fn is not None:
            self.stats.cache_hits += 1
            return fn
        return self._ensure_compiled(nb, problem)

    def warmup(self, buckets: Sequence[int],
               problems: Sequence[str] = ("mvc",)) -> dict:
        """Ahead-of-time compile: trace/lower/jit every
        (bucket, problem, mesh) executable the given traffic will touch,
        OFF the request path.  ``buckets`` entries are rounded up to their
        power-of-two bucket, so passing expected request SIZES works too.
        After a warmup covering the traffic's buckets,
        ``stats.compiles == 0`` holds through the measured window — the
        acceptance contract guarded by `benchmarks/serving_latency.py`.
        Combined with :func:`repro.compile_cache.enable_compile_cache`, a
        restarted process
        warms from the on-disk executable cache instead of recompiling."""
        t0 = time.perf_counter()
        compiled = []
        with self._device_lock:
            for problem in problems:
                for b in buckets:
                    nb = bucket_nodes(int(b), self.min_bucket)
                    before = len(self._compiled)
                    self._ensure_compiled(nb, problem, warm=True)
                    if len(self._compiled) > before:
                        compiled.append([nb, problem])
        return {"compiled": compiled,
                "seconds": time.perf_counter() - t0,
                "warmup_compiles": self.stats.warmup_compiles}

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, plan: BatchPlan) -> List[SolveResponse]:
        import jax
        import jax.numpy as jnp
        from ..core.inference import MAX_D, init_solve_state
        fn = self._solve_fn(plan.nb, plan.problem)
        state = self._place(init_solve_state(self._bucket_rep(plan.nb),
                                             plan.adj, plan.problem))
        t0 = time.perf_counter()
        # the dispatch's single host↔device sync: one result fetch
        out, evals, _committed = fn(self.params, state,
                                    jnp.asarray(plan.nb + MAX_D, jnp.int32))
        sol, evals = jax.device_get((out.solution, evals))
        t1 = time.perf_counter()
        self.stats.solve_seconds += t1 - t0
        self.stats.batches += 1
        unused = self.rows_per_dispatch - len(plan.request_ids)
        self.stats.padded_rows += unused
        self.stats.padded_rows_by_bucket[plan.nb] = (
            self.stats.padded_rows_by_bucket.get(plan.nb, 0) + unused)
        if unused:
            self.stats.partial_batches += 1
        enqueue_ts = plan.enqueue_ts or (0.0,) * len(plan.request_ids)
        out = []
        for row, (rid, n, et) in enumerate(zip(plan.request_ids,
                                               plan.sizes, enqueue_ts)):
            mask = unpad_solution(sol[row], n)
            out.append(SolveResponse(
                id=rid, solution=mask, size=int(mask.sum()),
                policy_evals=int(evals), bucket=plan.nb,
                problem=plan.problem, enqueue_t=et, dispatch_t=t0,
                complete_t=t1))
        return out

    # -- async scheduler thread ---------------------------------------------
    def _start_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._running = True
            self._thread = threading.Thread(
                target=self._scheduler_loop,
                name="graph-solver-scheduler", daemon=True)
            self._thread.start()

    def _scheduler_loop(self) -> None:
        """Continuous batching: sleep until the scheduler has a ready
        batch (or a head's max_wait expires), dispatch it outside the
        lock, resolve its futures; on shutdown, flush what is queued."""
        while True:
            with self._cond:
                batch = None
                while self._running:
                    batch = self._sched.next_batch(time.perf_counter())
                    if batch is not None:
                        break
                    wake = self._sched.next_wake(time.perf_counter())
                    timeout = (None if wake is None
                               else max(wake - time.perf_counter(), 1e-4))
                    self._cond.wait(timeout)
                if batch is None:
                    batch = self._sched.next_batch(time.perf_counter(),
                                                   force=True)
                    if batch is None:
                        return              # stopped and fully flushed
            (nb, problem), pendings = batch
            plan = build_plan([p.req for p in pendings], nb, problem,
                              self.rows_per_dispatch)
            try:
                with self._device_lock:
                    responses = self._dispatch(plan)
            except BaseException as exc:    # pragma: no cover - device OOM etc.
                for p in pendings:
                    p.future._set_exception(exc)
                continue
            by_id = {r.id: r for r in responses}
            for p in pendings:
                p.future._set_result(by_id[p.req.id])

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def close(self) -> None:
        """Stop the async scheduler thread; queued requests are flushed
        (dispatched, possibly underfilled) before it exits, so every
        issued future resolves."""
        with self._cond:
            thread = self._thread
            self._running = False
            self._cond.notify_all()
        if thread is not None:
            thread.join()
        self._thread = None

    def __enter__(self) -> "GraphSolverService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- sync drain ---------------------------------------------------------
    def drain(self) -> Dict[int, SolveResponse]:
        """Serve every pending sync request: bucket, pad, batch, run the
        fused engine per batch, unpad per request.

        Crash-safe: if a dispatch raises (e.g. an OOM compiling a new
        bucket), unserved requests go back on the queue for retry and
        already-computed responses are held over for the next drain —
        nothing is silently dropped."""
        with self._cond:
            if self._running:
                raise RuntimeError(
                    "drain() is the sync path; the async scheduler is "
                    "running — resolve futures or close() first")
            requests = list(self._queue)
            self._queue.clear()
        pending = {r.id: r for r in requests}
        try:
            for plan in plan_batches(requests, self.rows_per_dispatch,
                                     self.min_bucket):
                with self._device_lock:
                    responses = self._dispatch(plan)
                for resp in responses:
                    self._results[resp.id] = resp
                    pending.pop(resp.id, None)
        except BaseException:
            with self._cond:
                self._queue.extend(pending.values())
            raise
        results, self._results = self._results, {}
        return results

    def serve(self, adjs: Sequence[np.ndarray],
              problem: str = "mvc") -> List[SolveResponse]:
        """Convenience: submit a request stream and drain it, preserving
        submission order in the returned list."""
        ids = [self.submit(a, problem) for a in adjs]
        results = self.drain()
        return [results[i] for i in ids]
