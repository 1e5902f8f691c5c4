"""Error classes, classification, and the retry/backoff executor.

A production jax_graft deployment dies today on the first transient
fault: XLA surfaces everything as one exception type whose *message*
carries the gRPC-style status (``RESOURCE_EXHAUSTED``, ``UNAVAILABLE``,
``DEADLINE_EXCEEDED`` ...), so callers either swallow everything (the
GL008 anti-pattern) or die on everything. This module is the single
place that reads those messages: :func:`classify` maps any exception to
one of five kinds, and :func:`run` retries the retryable ones with
exponential backoff under a wall-clock deadline — the cooperative analog
of the reference's ``interruptible.hpp`` + the retry loops every
long-running RAFT consumer (raft-dask, the ANN bench harness) writes by
hand.

Kinds:

* ``transient``    — UNAVAILABLE / ABORTED / connection resets; retry.
* ``oom``          — RESOURCE_EXHAUSTED / allocator failures; do NOT
                     retry at the same size — the degradation ladder
                     (:mod:`raft_tpu.resilience.degrade`) halves the
                     chunk and re-dispatches.
* ``dead_backend`` — a backend that stopped answering; retryable once :func:`backend_alive` confirms the
                     device answers again.
* ``interrupted``  — cooperative cancellation
                     (:class:`raft_tpu.core.interruptible.Interruptible`);
                     never retried, always propagated.
* ``fatal``        — everything else (shape errors, ValueError, bugs);
                     never retried.
"""

from __future__ import annotations

import os
import re
import subprocess
import threading
import time
from typing import Callable, Iterable, Optional, Tuple

# classification kinds ------------------------------------------------------

TRANSIENT = "transient"
OOM = "oom"
DEAD_BACKEND = "dead_backend"
INTERRUPTED = "interrupted"
FATAL = "fatal"

KINDS = (TRANSIENT, OOM, DEAD_BACKEND, INTERRUPTED, FATAL)


class ResilienceError(RuntimeError):
    """Base for errors raised by the resilience layer itself."""


class TransientError(ResilienceError):
    """A failure the caller knows to be transient (e.g. a measurement
    stage whose tail says UNAVAILABLE); :func:`classify` maps it to
    ``transient`` without message sniffing."""


class DeadBackendError(ResilienceError):
    """The backend stopped answering and did not come back within the
    retry budget (surfaced as an exception instead of a hang)."""


class DeadlineExceededError(ResilienceError):
    """:func:`run`'s wall-clock deadline expired before an attempt
    succeeded. Carries the last underlying failure as ``__cause__``."""


class ShardDropoutError(ResilienceError):
    """A sharded search lost one or more shards and the caller did not
    opt into partial results (``partial_ok=False``)."""


# message patterns ----------------------------------------------------------
# XLA/PJRT surface status codes inside the exception text; these are the
# spellings observed from jaxlib's XlaRuntimeError.

_OOM_RE = re.compile(
    r"RESOURCE[ _]?EXHAUSTED|out of memory|OOM|allocat\w* .*fail|"
    r"exceeds the memory", re.IGNORECASE,
)
_TRANSIENT_RE = re.compile(
    r"UNAVAILABLE|ABORTED|CANCELLED|DEADLINE[ _]?EXCEEDED|UNKNOWN: |"
    r"connection (reset|refused|closed)|socket closed|broken pipe|"
    r"temporarily unavailable|try again", re.IGNORECASE,
)
_DEAD_RE = re.compile(
    r"dead[ -]?backend|backend .*(unreachable|died|lost)|"
    r"device or resource busy|heartbeat|FAILED[ _]?PRECONDITION: .*donat",
    re.IGNORECASE,
)


def classify(exc: BaseException) -> str:
    """Map an exception to one of :data:`KINDS`.

    Injected faults (:mod:`raft_tpu.resilience.faultinject`) carry their
    kind explicitly; cooperative interruption and the resilience layer's
    own typed errors short-circuit; anything else is classified from its
    message text, defaulting to ``fatal`` (never silently retry an
    unknown failure).

    Every classification is reported to graft-scope
    (:func:`raft_tpu.obs.on_error`): ``errors_total{kind}`` counts it,
    the flight recorder logs it, and — in flight mode — a fatal or
    dead_backend verdict auto-dumps the ring as the post-mortem
    artifact. No-op with ``RAFT_TPU_OBS=off``.
    """
    kind = _classify(exc)
    from raft_tpu import obs

    obs.on_error(kind, exc)
    return kind


def _classify(exc: BaseException) -> str:
    kind = getattr(exc, "fault_kind", None)
    if kind in KINDS:
        return kind
    from raft_tpu.core.interruptible import InterruptedException

    if isinstance(exc, InterruptedException):
        return INTERRUPTED
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return INTERRUPTED
    if isinstance(exc, TransientError):
        return TRANSIENT
    if isinstance(exc, DeadBackendError):
        return DEAD_BACKEND
    if isinstance(exc, MemoryError):
        return OOM
    if isinstance(exc, subprocess.TimeoutExpired):
        # the wedged-stage class: the child never answered
        return DEAD_BACKEND
    return classify_text(str(exc))


def classify_text(text: str) -> str:
    """Classify raw failure text (a subprocess tail, a log line) with the
    same message patterns :func:`classify` applies to exceptions — the
    measurement scripts use this on stage output to decide whether a
    non-zero rc is worth one retry."""
    if _OOM_RE.search(text):
        return OOM
    if _DEAD_RE.search(text):
        return DEAD_BACKEND
    if _TRANSIENT_RE.search(text):
        return TRANSIENT
    return FATAL


# liveness ------------------------------------------------------------------


def backend_alive(timeout_s: float = 30.0) -> bool:
    """In-process device liveness check.

    Dispatches a trivial device op on a daemon worker thread and waits
    up to ``timeout_s``: the known outage mode *hangs* inside the
    runtime holding the GIL-released device lock, so a plain call could
    never return False. A hung probe leaks its daemon thread — by
    construction there is no way to preempt the runtime call.
    """
    done = threading.Event()
    ok: list = []

    def _probe():
        try:
            import jax

            x = jax.device_put(1)
            jax.block_until_ready(x)
            ok.append(True)
        except Exception:  # graft-lint: allow-unclassified-swallow liveness probe: ANY failure means not-alive, classification is the caller's job  # noqa: BLE001
            pass
        finally:
            done.set()

    t = threading.Thread(target=_probe, daemon=True, name="raft-tpu-liveness")
    t.start()
    done.wait(timeout_s)
    return bool(ok)


# the retry executor --------------------------------------------------------

_DEFAULT_RETRY: Tuple[str, ...] = (TRANSIENT, DEAD_BACKEND)

# full-jitter backoff (ISSUE 18): N replicas retrying against one
# recovering worker with bare exponential backoff fire in lockstep —
# every wave lands together, and a rebalancing fleet amplifies the
# storm (the re-replication traffic rides the same transport). Each
# sleep is drawn uniformly from [0, backoff_s * mult**attempt] (the
# AWS "full jitter" schedule), from a process-local seeded RNG so
# drills and tests are deterministic: seed via RAFT_TPU_JITTER_SEED or
# seed_jitter().

_jitter_lock = threading.Lock()


def _fresh_jitter_rng(seed: Optional[int] = None):
    import random

    if seed is None:
        env = os.environ.get("RAFT_TPU_JITTER_SEED", "").strip()
        seed = int(env) if env else None
    return random.Random(seed)


_jitter_rng = _fresh_jitter_rng()


def seed_jitter(seed: Optional[int]) -> None:
    """Re-seed the backoff-jitter RNG (tests / deterministic drills);
    ``None`` restores the env-or-entropy default."""
    global _jitter_rng
    with _jitter_lock:
        _jitter_rng = _fresh_jitter_rng(seed)


def backoff_jitter_s(attempt: int, backoff_s: float,
                     mult: float = 2.0, jitter: bool = True) -> float:
    """The sleep before retry ``attempt`` (0-based): full jitter over
    the exponential cap ``backoff_s * mult**attempt``, or the bare cap
    with ``jitter=False`` (callers that need the worst-case bound for
    deadline math use the cap; the drawn value is always <= it)."""
    cap = backoff_s * (mult ** attempt)
    if not jitter or cap <= 0:
        return cap
    with _jitter_lock:
        return _jitter_rng.uniform(0.0, cap)


def run(
    fn: Callable,
    *args,
    deadline_s: Optional[float] = None,
    retries: int = 3,
    backoff_s: float = 0.5,
    backoff_mult: float = 2.0,
    jitter: bool = True,
    retry_on: Iterable[str] = _DEFAULT_RETRY,
    probe_timeout_s: float = 30.0,
    on_retry: Optional[Callable[[int, str, BaseException], None]] = None,
    token=None,
    **kwargs,
):
    """Run ``fn(*args, **kwargs)`` with classified retry under a deadline.

    * Exceptions are :func:`classify`\\ d; only kinds in ``retry_on``
      (default transient + dead_backend) are retried, up to ``retries``
      times with full-jitter exponential backoff: each sleep is drawn
      uniformly from ``[0, backoff_s * backoff_mult**i]``
      (:func:`backoff_jitter_s` — seeded via ``RAFT_TPU_JITTER_SEED``
      or :func:`seed_jitter`; ``jitter=False`` restores the bare
      exponential schedule). The DEADLINE check uses the un-jittered
      cap, so whether a final retry is attempted does not depend on
      the RNG draw.
    * ``deadline_s`` is a wall-clock budget over ALL attempts: when a
      retry (including its backoff sleep) cannot start inside it,
      :class:`DeadlineExceededError` is raised with the last failure as
      ``__cause__``. The deadline cannot preempt a *running* attempt —
      pair it with a subprocess/thread timeout for hard preemption (the
      measurement scripts use subprocess timeouts as the hard bound).
    * A ``dead_backend`` failure is only retried after
      :func:`backend_alive` confirms the device answers again; a probe
      failure converts the retry into :class:`DeadBackendError`. The
      probe runs on :func:`backend_alive`'s bounded daemon thread and
      its wait is CLAMPED to the remaining ``deadline_s`` — a hanging
      probe (a backend hung in init) counts against the deadline
      instead of stalling the retry loop ``probe_timeout_s`` past it,
      and a probe that times out is classified ``dead_backend``.
    * ``token`` (an :class:`~raft_tpu.core.interruptible.Interruptible`)
      is checked before every attempt so ``cancel()`` from another
      thread stops the retry loop too.
    """
    retry_on = tuple(retry_on)
    start = time.monotonic()
    attempt = 0
    while True:
        if token is not None:
            token.check()
        try:
            return fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — classified, not swallowed
            kind = classify(e)
            if kind not in retry_on or attempt >= retries:
                raise
            # deadline/probe math uses the un-jittered CAP so the
            # retry-vs-give-up decision is deterministic; the actual
            # sleep is the jittered draw (always <= cap)
            cap = backoff_s * (backoff_mult ** attempt)
            sleep = backoff_jitter_s(attempt, backoff_s, backoff_mult,
                                     jitter)
            if deadline_s is not None and \
                    time.monotonic() - start + cap >= deadline_s:
                raise DeadlineExceededError(
                    f"deadline {deadline_s}s exhausted after "
                    f"{attempt + 1} attempt(s); last failure: {kind}"
                ) from e
            if kind == DEAD_BACKEND:
                # clamp the liveness probe to the remaining deadline:
                # backend_alive's bounded daemon-thread join means a
                # hung probe returns at the budget, but an unclamped
                # probe_timeout_s (default 30s) could still stall the
                # loop far past a tighter deadline_s
                probe_budget = probe_timeout_s
                if deadline_s is not None:
                    probe_budget = min(
                        probe_budget,
                        deadline_s - (time.monotonic() - start) - cap,
                    )
                if probe_budget <= 0 or not backend_alive(probe_budget):
                    raise DeadBackendError(
                        f"backend did not come back within "
                        f"{max(probe_budget, 0.0):.3g}s probe budget "
                        f"after: {e}"
                    ) from e
            from raft_tpu import obs

            obs.counter("retries", kind=kind)
            obs.event("retry", attempt=attempt, error_kind=kind,
                      error=str(e)[:200], backoff_s=sleep)
            if on_retry is not None:
                on_retry(attempt, kind, e)
            time.sleep(sleep)
            attempt += 1
