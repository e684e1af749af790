"""Fleet serving: continuous batching + A/B routing over a ModelRegistry.

The static ``VisionEngine`` runs one model and serialises host and
device work: wait ``max_wait_ms`` → stack → launch → block on results →
repeat, leaving the device idle during every host phase.  ``FleetEngine``
replaces that loop with a **continuous, double-buffered scheduler** over
every model in a ``ModelRegistry``:

  * requests land on bounded **per-model queues** (backpressure: submit
    blocks when a model's queue is full);
  * one worker drains the queues with **smooth weighted round-robin** —
    a model with weight 3 gets three batches for every one of a
    weight-1 model, with no starvation;
  * the worker keeps **one batch in flight on device while assembling
    the next on host**: the in-flight batch *is* the wait timer — while
    the device is busy, arrivals accumulate toward the next batch for
    free, and a queue that reaches ``batch_size`` mid-flight is stacked
    and padded while the device still computes.  There is no
    ``max_wait_ms``: under load, batches are full without ever sleeping
    on a wall clock; from idle, a request launches after at most one
    sub-ms coalescing window (``coalesce_ms``, which exists only so a
    burst of co-arriving requests shares one padded launch instead of
    each paying a full one).  Because every launch is padded to a fixed
    cost, partial queues are never popped mid-flight — they regroup
    with the requests this flight's delivery unblocks (see
    ``_next_batch``).

``Router`` sits in front of ``submit``: a routing target is either a
concrete model id (passthrough) or a **split alias** whose weighted arms
are chosen by a deterministic hash of the request id — the same request
id always lands on the same arm, across processes and restarts, which is
what makes an A/B experiment analysable.

Numerics are untouched: batches are assembled with the same helpers as
``VisionEngine`` and run the same compiled plans, so fleet-routed logits
are bit-exact with a standalone engine (asserted in tests/test_fleet.py).
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import numpy as np

from repro.obs.metrics import MetricRegistry
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.registry import ModelEntry, ModelRegistry
from repro.serving.stats import (
    REQUEST_DEADLINE_SECONDS,
    SLACK_BUCKETS,
    SLO_DEADLINE_SECONDS,
    SLO_VIOLATIONS_TOTAL,
    EngineStats,
    Slo,
)
from repro.serving.vision import (
    Request,
    VisionResult,
    assemble_batch,
    fail_batch,
    resolve_batch,
)


# ---------------------------------------------------------------------------
# Router — deterministic A/B traffic splitting
# ---------------------------------------------------------------------------


def _hash_fraction(request_id: str) -> float:
    """Deterministic uniform fraction in [0, 1) from a request id."""
    digest = hashlib.sha256(str(request_id).encode()).digest()
    return int.from_bytes(digest[:8], "big") / 2**64


def parse_split(spec: str) -> dict[str, float]:
    """CLI split spec ``"a=0.9,b=0.1"`` → {model_id: weight}."""
    arms: dict[str, float] = {}
    for part in spec.split(","):
        mid, _, w = part.partition("=")
        mid = mid.strip()
        if not mid or not w:
            raise ValueError(f"bad split spec {spec!r} (want a=0.9,b=0.1)")
        arms[mid] = float(w)
    return arms


class Router:
    """Maps routing targets to model ids, with weighted A/B split aliases.

    A target that is not a split alias resolves to itself, so concrete
    model ids route with zero configuration.  Split arms are normalised
    and kept in sorted order: the arm is picked by where the request-id
    hash falls in the cumulative weight line, so the arm choice is a pure
    function of (splits, request id).
    """

    def __init__(self, splits: dict[str, dict[str, float]] | None = None):
        self._splits: dict[str, tuple[tuple[str, float], ...]] = {}
        for alias, arms in (splits or {}).items():
            self.add_split(alias, arms)

    def add_split(self, alias: str, arms: dict[str, float]) -> None:
        if not arms:
            raise ValueError(f"split {alias!r} has no arms")
        total = float(sum(arms.values()))
        if total <= 0:
            raise ValueError(f"split {alias!r} weights must sum > 0")
        if any(w < 0 for w in arms.values()):
            raise ValueError(f"split {alias!r} has a negative weight")
        self._splits[alias] = tuple(
            (mid, w / total) for mid, w in sorted(arms.items())
        )

    def arms(self, alias: str) -> tuple[tuple[str, float], ...]:
        return self._splits[alias]

    @property
    def aliases(self) -> list[str]:
        return sorted(self._splits)

    def resolve(self, target: str, request_id: str) -> str:
        """Routing target + request id → concrete model id."""
        arms = self._splits.get(target)
        if arms is None:
            return target
        frac = _hash_fraction(request_id)
        acc = 0.0
        for mid, w in arms:
            acc += w
            if frac < acc:
                return mid
        return arms[-1][0]  # frac ~ 1.0 lands on the last arm


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------


class FleetEngine:
    """Multi-model continuous-batching engine over a ModelRegistry.

    One daemon worker serves every registered model; per-model queues are
    drained by smooth weighted round-robin and batches are double-
    buffered (assemble N+1 on host while N runs on device).

    Observability: ``metrics`` (defaulting to the registry's shared
    ``MetricRegistry``, if it has one) adds the fleet-wide counters as
    ``serve_*_total{model="_fleet"}`` plus a per-model
    ``serve_queue_depth`` gauge and a ``serve_batch_fill`` histogram
    (real fraction of every launched batch).  ``tracer`` (an
    ``obs.Tracer``) records one span per batch-lifecycle phase —
    assemble / dispatch / fetch / deliver — tagged with the model id;
    both default to no-ops with zero hot-path cost.

    SLO attribution: a model whose ``ModelEntry`` carries an
    ``Slo(deadline_ms)`` gets every delivered request's deadline slack
    recorded (``serve_request_deadline_seconds{model=…}`` histogram,
    ``serve_slo_violations_total{model=…}`` counter,
    ``serve_slo_deadline_seconds`` gauge for dashboards) plus an
    engine-local roll-up in ``slo_snapshot()`` — see
    ``serving.stats.Slo``.
    """

    def __init__(
        self,
        registry: ModelRegistry,
        *,
        batch_size: int = 32,
        queue_depth: int = 256,
        weights: dict[str, float] | None = None,
        router: Router | None = None,
        coalesce_ms: float = 1.0,
        metrics: MetricRegistry | None = None,
        tracer: Tracer | None = None,
    ):
        self.registry = registry
        self.batch_size = batch_size
        self.queue_depth = queue_depth
        self.coalesce_ms = coalesce_ms
        self.router = router or Router()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # pre-bound batch-lifecycle spans: the name (and, for attr-less
        # phases, the attrs dict) is resolved once here instead of per
        # batch, to keep tracing within the 3% that
        # benchmarks/obs_overhead.py measures on the CPU
        self._span_assemble = self.tracer.bind("fleet.assemble")
        self._span_dispatch = self.tracer.bind("fleet.dispatch")
        self._span_fetch = self.tracer.bind("fleet.fetch")
        self._span_deliver = self.tracer.bind("fleet.deliver")
        # per-model SLO accounting (requests, violations) — written only
        # by the worker thread, read by slo_snapshot()
        self._slo_counts: dict[str, list[int]] = {}
        # inherit the registry's shared metrics so a metrics-enabled fleet
        # needs no extra plumbing; an explicit metrics= still wins
        self.metrics = metrics if metrics is not None else registry.metrics
        if self.metrics is not None:
            # fleet-wide counters join the per-model families under a
            # reserved label value (a real id can't be empty, "_fleet" is
            # ours by convention)
            self.stats = EngineStats(registry=self.metrics,
                                     labels={"model": "_fleet"})
            self._depth_gauge = self.metrics.gauge(
                "serve_queue_depth", "queued requests per model",
                labels=("model",),
            )
            self._fill_hist = self.metrics.histogram(
                "serve_batch_fill",
                "real (unpadded) fraction of each launched batch",
                buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
            )
            self._deadline_hist = self.metrics.histogram(
                REQUEST_DEADLINE_SECONDS,
                "per-request deadline slack in seconds "
                "(negative = SLO violated)",
                labels=("model",), buckets=SLACK_BUCKETS,
            )
            self._slo_violations = self.metrics.counter(
                SLO_VIOLATIONS_TOTAL,
                "requests answered after their model's SLO deadline",
                labels=("model",),
            )
            self._slo_deadline = self.metrics.gauge(
                SLO_DEADLINE_SECONDS,
                "configured per-model SLO deadline",
                labels=("model",),
            )
        else:
            self.stats = EngineStats()  # fleet-wide; per-model in entry.stats
            self._depth_gauge = None
            self._fill_hist = None
            self._deadline_hist = None
            self._slo_violations = None
            self._slo_deadline = None
        self._weights = dict(weights or {})
        self._wrr: dict[str, float] = {}
        self._queues: dict[str, deque[Request]] = {}
        self._cond = threading.Condition()
        self._closed = False
        self._auto_id = 0
        self._worker = threading.Thread(target=self._serve_loop, daemon=True)
        self._worker.start()

    # ---- client API -------------------------------------------------------

    def submit(self, image: np.ndarray, *, model: str,
               request_id: str | None = None) -> "Future[VisionResult]":
        """Enqueue one image for ``model`` (a model id or a split alias).

        Blocks only when the target model's queue is full (backpressure).
        ``request_id`` pins A/B routing; omitted ids get a process-local
        sequence number (unique, but not stable across runs — pass real
        ids when the experiment assignment matters).
        """
        if request_id is None:
            with self._cond:
                request_id = f"auto-{self._auto_id}"
                self._auto_id += 1
        model_id = self.router.resolve(model, request_id)
        entry = self.registry.get(model_id)  # raises on unknown id
        if tuple(image.shape) != entry.input_shape:
            raise ValueError(
                f"image shape {tuple(image.shape)} != model "
                f"{model_id!r} input shape {entry.input_shape}"
            )
        req = Request(np.asarray(image, np.int32), Future(),
                      time.perf_counter())
        with self._cond:
            if self._closed:
                raise RuntimeError("engine is closed")
            while True:
                # re-fetched after every wait: the idle housekeeping may
                # have deleted an evicted model's drained queue while we
                # slept, and appending to that orphaned deque would strand
                # the request (the worker only scans self._queues)
                q = self._queues.setdefault(model_id, deque())
                if len(q) < self.queue_depth:
                    break
                self._cond.wait()
                if self._closed:
                    raise RuntimeError("engine is closed")
            q.append(req)
            if self._depth_gauge is not None:
                self._depth_gauge.labels(model=model_id).set(len(q))
            self._cond.notify_all()
        return req.future

    def classify(self, images, *, model: str) -> list[int]:
        """Blocking convenience: a list of images → predicted labels."""
        futs = [self.submit(img, model=model) for img in images]
        return [f.result().label for f in futs]

    def snapshot(self) -> dict:
        """Fleet-wide + per-model stats in one JSON-ready dict."""
        return {"fleet": self.stats.snapshot(),
                "models": self.registry.snapshot(),
                "slo": self.slo_snapshot()}

    def slo_snapshot(self) -> dict:
        """Per-model SLO attribution: {model: requests/violations/frac}.

        Only models with a configured ``Slo`` appear.  Written solely by
        the worker thread; a concurrent read sees some prefix of the
        delivered batches, never a torn one (the two list slots are
        updated under the GIL in one bytecode run).
        """
        return {
            mid: {"requests": c[0], "violations": c[1],
                  "violation_frac": c[1] / c[0] if c[0] else 0.0}
            for mid, c in sorted(self._slo_counts.items())
        }

    def close(self):
        """Drain every queue (all futures resolve) and stop the worker."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._worker.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---- worker -----------------------------------------------------------

    def _pick_model(self, *, commit: bool = True, min_items: int = 1,
                    aged_before: float | None = None) -> str | None:
        """Smooth weighted round-robin over models with queued work.

        Every active model's credit grows by its weight each round and
        the highest-credit model pays the round total when picked — the
        classic smooth-WRR invariant: over W rounds a weight-w model is
        picked w/W of the time, and no active model starves.

        ``commit=False`` answers "which model *would* be picked" without
        advancing any credits (the coalescing window peeks at its queue).
        ``min_items`` restricts the round to queues holding at least that
        many requests (the mid-flight full-batches-only grab);
        ``aged_before`` additionally admits a partial queue whose HEAD
        request predates that timestamp — the anti-starvation valve: a
        request that was already waiting when the current in-flight batch
        dispatched has sat out a full scheduling round and must not wait
        behind another model's endless full batches.
        Caller holds ``self._cond``.
        """
        active = [
            mid for mid, q in self._queues.items()
            if len(q) >= min_items
            or (q and aged_before is not None
                and q[0].t_submit < aged_before)
        ]
        if not active:
            return None
        total = 0.0
        best = None
        tentative: dict[str, float] = {}
        for mid in sorted(active):  # sorted: deterministic tie-break
            w = self._weights.get(mid, 1.0)
            tentative[mid] = self._wrr.get(mid, 0.0) + w
            total += w
            if best is None or tentative[mid] > tentative[best]:
                best = mid
        if commit:
            self._wrr.update(tentative)
            self._wrr[best] -= total
        return best

    def _next_batch(self, *, block: bool, aged_before: float | None = None):
        """Pop ≤ batch_size requests from the WRR-chosen model queue.

        ``block=False`` is the double-buffering path: a batch is already
        in flight, so return immediately with whatever is queued (maybe
        nothing) instead of idling the host.  Returns ``None`` when there
        is no work — and the engine is closed, if ``block=True``.

        Every batch is a fixed-cost padded launch, so *when* to pop is a
        fill decision, not just a liveness one:

        * mid-flight (``block=False``) only a **full** queue is popped —
          a full batch cannot grow further, so assembling it early is
          free overlap; a partial batch popped now would fragment its
          cohort across several full-price launches, while leaving it
          queued lets the requests that unblock on this flight's
          delivery regroup with it.  Exception (anti-starvation): a
          partial queue whose head request predates the in-flight
          batch's dispatch (``aged_before``) has already sat out one
          full round and is admitted, so another model's sustained
          full-batch load can delay a sparse model by at most ~two
          flights, never unboundedly;
        * from idle (``block=True``) waking on the *first* arrival would
          launch a one-item batch while its co-arrivals land
          microseconds later, so an idle wake holds a bounded
          **coalescing window** (``coalesce_ms``) for a queue to reach
          ``batch_size`` before popping whatever accumulated.
        """
        with self._cond:
            if not block:
                model_id = self._pick_model(min_items=self.batch_size,
                                            aged_before=aged_before)
                return None if model_id is None else self._pop(model_id)
            # idle housekeeping: drop scheduler state (queue + WRR credit)
            # of evicted models once their queues have drained, or a
            # long-lived engine cycling many transient A/B arms leaks one
            # dead deque per id and scans them all every round
            for mid in [m for m, q in self._queues.items()
                        if not q and m not in self.registry]:
                del self._queues[mid]
                self._wrr.pop(mid, None)
            while not any(self._queues.values()):
                if self._closed:
                    return None
                self._cond.wait()
            if self.coalesce_ms > 0:
                # the window watches the queue WRR would actually pop (a
                # peek, not a committed pick) — another model's full queue
                # must not end the window for a still-near-empty winner
                deadline = time.perf_counter() + self.coalesce_ms / 1e3
                while (not self._closed
                       and len(self._queues[
                           self._pick_model(commit=False)])
                       < self.batch_size):
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
            return self._pop(self._pick_model())

    def _pop(self, model_id: str):
        """Pop ≤ batch_size requests; caller holds ``self._cond``."""
        q = self._queues[model_id]
        items = [q.popleft() for _ in range(min(len(q), self.batch_size))]
        if self._depth_gauge is not None:
            self._depth_gauge.labels(model=model_id).set(len(q))
        self._cond.notify_all()  # free backpressured submitters
        return model_id, items

    def _assemble(self, model_id: str, items: list[Request]):
        """Stack + pad one popped batch; returns (entry, items, batch, plan)
        or None on failure (futures failed in place).

        The guard is broad on purpose: ANY escape here (model evicted
        while queued, or evict+re-register changing the input shape so
        the stack fails) would otherwise kill the engine's only worker
        thread and hang every pending future.
        """
        with self._span_assemble(model=model_id, n=len(items)):
            try:
                entry: ModelEntry = self.registry.get(model_id)
                plan = entry.plan  # read once: hot-swap flips atomically
                pad = self.registry.pad_buffer(plan.input_shape)
                batch = assemble_batch(items, pad, self.batch_size)
            except Exception as e:
                fail_batch(items, RuntimeError(
                    f"cannot assemble batch for model {model_id!r} "
                    f"(evicted, or replaced with an incompatible "
                    f"model?): {e}"))
                return None
        return entry, items, batch, plan

    def _dispatch(self, assembled):
        """Asynchronously launch one assembled batch; returns in-flight
        state (entry, items, device array, t_launch) or None on failure."""
        entry, items, batch, plan = assembled
        with self._span_dispatch(model=entry.model_id, n=len(items)):
            t0 = time.perf_counter()
            try:
                dev = plan.logits(batch)  # async — returns immediately
            except Exception as e:  # trace/compile-time failure
                fail_batch(items, e)
                return None
        return entry, items, dev, t0

    def _fetch(self, inflight):
        """Block until one in-flight batch completes; returns results or
        None on failure (futures failed in place).

        The completion time is stamped HERE — delivery happens after the
        next batch's dispatch, and charging this batch's waiters for that
        dispatch (worst case: a cold jit compile of another model) would
        misattribute seconds to requests already finished on device.
        """
        entry, items, dev, t0 = inflight
        with self._span_fetch(model=entry.model_id):
            try:
                logits = np.asarray(jax.device_get(dev))
            except Exception as e:  # runtime failure surfaces at the fetch
                fail_batch(items, e)
                return None
        return entry, items, logits, t0, time.perf_counter()

    def _deliver(self, fetched) -> None:
        """Record stats, then resolve one completed batch's futures.

        Stats land first: a client that unblocks on its future and
        immediately snapshots must already see this batch counted.
        """
        entry, items, logits, t0, t_done = fetched
        n = len(items)
        with self._span_deliver(model=entry.model_id, n=n):
            entry.stats.record_batch(n, self.batch_size - n, t_done - t0)
            self.stats.record_batch(n, self.batch_size - n, t_done - t0)
            if self._fill_hist is not None:
                self._fill_hist.observe(n / self.batch_size)
            if entry.slo is not None:
                self._attribute_slo(entry, items, t_done)
            resolve_batch(items, logits, t_done)

    def _attribute_slo(self, entry: ModelEntry, items: list[Request],
                       t_done: float) -> None:
        """Per-request deadline attribution for one delivered batch.

        Slack is measured against the request's **end-to-end** latency
        (submit → delivery-ready), not the device batch latency — queueing
        behind other models' batches is exactly the cost the future
        SLO-aware scheduler must see.  Negative slack = violation.
        """
        slo: Slo = entry.slo
        deadline_s = slo.deadline_s
        slacks = [deadline_s - (t_done - req.t_submit) for req in items]
        violations = sum(1 for s in slacks if s < 0)
        counts = self._slo_counts.setdefault(entry.model_id, [0, 0])
        counts[0] += len(items)
        counts[1] += violations
        if self.metrics is not None:
            hist = self._deadline_hist.labels(model=entry.model_id)
            # touch the violation counter even when zero: a scrape must
            # distinguish "no misses" from "never attributed"
            violation_ctr = self._slo_violations.labels(
                model=entry.model_id)
            with self.metrics.lock:  # scrape-atomic per batch
                self._slo_deadline.labels(model=entry.model_id).set(
                    deadline_s)
                for s in slacks:
                    hist.observe(s)
                if violations:
                    violation_ctr.inc(violations)

    def _serve_loop(self):
        # The pipeline keeps exactly ONE batch executing at any moment and
        # hides every piece of host work behind it:
        #
        #   assemble N+1   (overlaps N's device execution)
        #   fetch N        (the only blocking point)
        #   dispatch N+1   (device busy again immediately)
        #   deliver N      (futures/argmax/stats overlap N+1's execution)
        #
        # Dispatching N+1 *before* fetching N would put two executions on
        # the device at once — a win only when the device has spare
        # parallelism; on a CPU backend the two thrash one thread pool.
        # This order never oversubscribes and still keeps the gap between
        # consecutive executions down to one host↔device fetch.
        inflight = None
        while True:
            # with a batch on device, don't wait for arrivals (block=False):
            # grab an already-full (or starving — older than the in-flight
            # dispatch) batch so assembly overlaps device work
            nxt = self._next_batch(
                block=inflight is None,
                aged_before=inflight[3] if inflight is not None else None)
            if nxt is None and inflight is None:
                return  # closed and fully drained
            assembled = self._assemble(*nxt) if nxt is not None else None
            fetched = self._fetch(inflight) if inflight is not None else None
            inflight = self._dispatch(assembled) if assembled else None
            if fetched is not None:
                self._deliver(fetched)
