"""Open-loop serving: independent clients send single images on a schedule.

Mix parameters (``bench/traffic/<mix>.json``):

* ``rate_per_s`` — mean arrival rate, fixed in the mix (never searched
  for in a run);
* ``phases`` (optional) — ``[{"rate_per_s": r, "seconds": s}, ...]``,
  a rate profile repeated over the window (bursts), in place of the
  constant rate;
* ``batch_size`` — the ``FleetEngine``'s batch;
* ``distinct_images`` — images made from the seed that requests draw
  from;
* ``warmup_requests`` — requests served before the window, in set-up;
* ``p95_limit_ms`` — the latency limit the rate was chosen under (read
  by the knee sweep, ``bench/sweep_knee.py``).

The schedule is a Poisson process conditioned on its count: every seed
sends the same number of requests in the window, at seeded times and
with seeded images.  Each request is timed from its scheduled send time
to the moment its answer reaches the client, so a stall that delays the
sender or the server raises the tail of every request behind it; how
late the sender ran is recorded too.  A request that fails or never
answers counts as beyond any limit.
"""

from __future__ import annotations

import concurrent.futures
import functools
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import checks, data, model, reference

#: how long after the window the run waits for the last answers
DRAIN_SECONDS = 60.0
REFERENCE_BLOCK = 256


# ---------------------------------------------------------------------------
# Schedule and statistics (no device, no program: unit-tested on a fake clock)
# ---------------------------------------------------------------------------


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of an ascending sequence: the
    ``max(ceil(q·n), 1)``-th smallest value (0.0 when empty)."""
    n = len(sorted_vals)
    if not n:
        return 0.0
    rank = min(max(int(np.ceil(q * n - 1e-9)), 1), n)
    return float(sorted_vals[rank - 1])


def _profile(mix: dict) -> list[tuple[float, float]]:
    if "phases" in mix:
        return [(float(p["rate_per_s"]), float(p["seconds"]))
                for p in mix["phases"]]
    return [(float(mix["rate_per_s"]), 1.0)]


def schedule(mix: dict, seconds: float, rng: np.random.Generator):
    """Send times in [0, seconds) of a Poisson process with the mix's rate
    profile, each phase's count fixed at its expected value."""
    times = []
    t = 0.0
    prof = _profile(mix)
    i = 0
    while t < seconds:
        rate, dur = prof[i % len(prof)]
        end = min(t + dur, seconds)
        count = int(round(rate * (end - t)))
        times.append(np.sort(rng.uniform(t, end, count)))
        t, i = end, i + 1
    return np.concatenate(times) if times else np.zeros(0)


@dataclass
class Sent:
    """The client's record of one window: when each request was due,
    when it went, when its answer came, and the answers."""

    due: np.ndarray
    late: np.ndarray
    done: np.ndarray
    futures: list = field(default_factory=list)
    t0: float = 0.0


def send(times, submit, *, clock=time.perf_counter,
         sleep=time.sleep) -> Sent:
    """Send request ``i`` at ``t0 + times[i]`` through ``submit(i)``, which
    returns a future; never waits for an answer before the next send.
    Every time recorded is relative to ``t0``, the clock at the start."""
    n = len(times)
    rec = Sent(due=np.asarray(times, float), late=np.zeros(n),
               done=np.full(n, np.inf), t0=clock())

    def mark(i, _fut):
        rec.done[i] = clock() - rec.t0

    for i in range(n):
        due = rec.t0 + times[i]
        now = clock()
        if due > now:
            sleep(due - now)
        rec.late[i] = max(clock() - due, 0.0)
        fut = submit(i)
        fut.add_done_callback(functools.partial(mark, i))
        rec.futures.append(fut)
    return rec


def latencies(rec: Sent, ok: np.ndarray) -> np.ndarray:
    """Scheduled-send-to-answer seconds; a failed request is infinite."""
    lat = rec.done - rec.due
    return np.where(ok, lat, np.inf)


# ---------------------------------------------------------------------------
# The cell
# ---------------------------------------------------------------------------


@dataclass
class Session:
    cfg: dict
    weight_key: object
    images: np.ndarray
    image_of: np.ndarray
    times: np.ndarray
    engine: object = None
    model_id: str = ""
    rec: Sent = None
    stats0: tuple = (0, 0)
    stats1: tuple = (0, 0)


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])


def setup(run) -> Session:
    from repro.infer import freeze
    from repro.serving.fleet import FleetEngine
    from repro.serving.registry import ModelRegistry

    cfg, mix = run.config, run.mix
    key = model.seed_key(run.seed)
    k_data, k_weights = jax.random.split(key)
    shape = tuple(cfg.get("image_shape", cfg["input_shape"]))
    x, _ = data.images(k_data, n=mix["distinct_images"], shape=shape,
                       num_classes=cfg["num_classes"])
    images = np.asarray(jax.device_get(x)).reshape(
        mix["distinct_images"], *cfg["input_shape"])
    rng = _rng(run.seed)
    times = schedule(mix, run.seconds, rng)
    image_of = rng.integers(0, len(images), len(times))
    s = Session(cfg=cfg, weight_key=k_weights, images=images,
                image_of=image_of, times=times, model_id=cfg["name"])

    params = model.init_params(cfg, k_weights, mode="served")
    fm = freeze(params, model.nitro_config(cfg))
    registry = ModelRegistry(backend="auto")
    entry = registry.register(s.model_id, fm)
    batch = mix["batch_size"]
    jax.block_until_ready(entry.plan.logits(
        np.zeros((batch, *cfg["input_shape"]), np.int32)))
    tracer = None
    if run.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer(annotate=True)
    s.engine = FleetEngine(registry, batch_size=batch, tracer=tracer)
    warm = [s.engine.submit(images[i % len(images)], model=s.model_id)
            for i in range(mix["warmup_requests"])]
    for f in warm:
        f.result()
    return s


def window(run, s: Session, seconds: float) -> None:
    times = s.times[s.times < seconds]
    stats = s.engine.stats
    s.stats0 = (stats.requests, stats.padded_slots)
    rec = send(times, lambda i: s.engine.submit(
        s.images[s.image_of[i]], model=s.model_id))
    concurrent.futures.wait(rec.futures, timeout=seconds + DRAIN_SECONDS)
    s.stats1 = (stats.requests, stats.padded_slots)
    s.rec = rec
    run.attempted = len(times)
    run.window.update(seconds=seconds, requests=len(times))


def release(run, s: Session) -> None:
    s.engine.close()
    s.engine = None


def served_fill(s: Session) -> float | None:
    """Real share of the launched batch slots in the window, in %."""
    real = s.stats1[0] - s.stats0[0]
    padded = s.stats1[1] - s.stats0[1]
    return 100.0 * real / (real + padded) if real + padded else None


def reference_logits(run, s: Session, **kw) -> np.ndarray:
    dev = run.devices[0]
    params = jax.device_put(
        model.init_params(s.cfg, s.weight_key, mode="served"), dev)
    fwd = jax.jit(lambda p, x: reference.forward(s.cfg, p, x, **kw)[0])
    out = []
    for i in range(0, len(s.images), REFERENCE_BLOCK):
        out.append(np.asarray(fwd(params, jax.device_put(
            s.images[i:i + REFERENCE_BLOCK], dev))))
    return np.concatenate(out)


def check(run, s: Session):
    rec = s.rec
    answers, ok = {}, np.zeros(len(rec.futures), bool)
    for i, f in enumerate(rec.futures):
        if f.done() and f.exception() is None:
            answers[i] = f.result().logits
        else:
            answers[i] = None
    ref = reference_logits(run, s)
    for i, row in answers.items():
        ok[i] = row is not None and np.array_equal(
            np.asarray(row), ref[s.image_of[i]])
    lat = np.sort(latencies(rec, ok))
    horizon = run.window["seconds"] + DRAIN_SECONDS
    p95 = percentile(np.minimum(lat, horizon), 0.95)
    in_window = ok & (rec.done <= run.window["seconds"])
    run.failed = int(np.sum([a is None for a in answers.values()]))
    run.end_to_end["serve_p95_ms"] = p95 * 1e3
    run.end_to_end["serve_images_per_s"] = (
        int(in_window.sum()) / run.window["seconds"])
    run.counters["serve_batch_fill"] = served_fill(s)
    run.counters["gen_late_p99_ms"] = percentile(np.sort(rec.late), 0.99) * 1e3
    run.counters["answered"] = int(ok.sum())
    return checks.serve_checks(answers, ref, s.image_of)
