"""Bounded retries with exponential backoff + jitter for wedgeable init.

A TPU backend init can hang inside native code instead of raising. The
init path must never be an infinite hang: it either succeeds or raises
after a bounded number of deadline-bounded attempts.

Knobs (read by :func:`init_retry_env`):

* ``HOROVOD_TPU_INIT_RETRIES`` — max attempts (default 3).
* ``HOROVOD_TPU_INIT_BACKOFF`` — base backoff seconds (default 1.0); the
  delay doubles per attempt, capped at 30s, with ±25% seeded jitter so a
  whole pod slice doesn't re-dial the coordinator in lockstep.
* ``HOROVOD_TPU_INIT_TIMEOUT`` — per-attempt deadline seconds for
  :func:`run_with_deadline` (default 300s — bounded by default, because
  a wedged init hangs rather than raises; 0 disables).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable, Optional, Sequence, Tuple

from . import hvd_logging as logging
from .config import _env_float, _env_int
from .. import metrics

BACKOFF_MAX_SECONDS = 30.0

_m = None


def _retry_metrics():
    global _m
    if _m is None:
        from types import SimpleNamespace

        _m = SimpleNamespace(
            failures=metrics.counter(
                "hvd_retry_attempt_failures_total",
                "Failed attempts inside retry_call (init hardening)."),
            backoff=metrics.counter(
                "hvd_retry_backoff_seconds_total",
                "Total seconds slept backing off between retry attempts."),
            giveups=metrics.counter(
                "hvd_retry_giveups_total",
                "retry_call budgets exhausted (RetryError raised)."))
    return _m


class RetryError(RuntimeError):
    """All attempts failed; ``last`` is the final attempt's exception."""

    def __init__(self, describe: str, attempts: int, last: BaseException):
        self.attempts = attempts
        self.last = last
        super().__init__(
            f"{describe} failed after {attempts} attempt(s): {last}")


class DeadlineExceeded(RuntimeError):
    """The bounded call did not finish within its per-attempt deadline."""


def init_retry_env() -> Tuple[int, float]:
    """(max attempts, base backoff seconds) for the init path."""
    attempts = max(1, _env_int("HOROVOD_TPU_INIT_RETRIES", 3))
    backoff = max(0.0, _env_float("HOROVOD_TPU_INIT_BACKOFF", 1.0))
    return attempts, backoff


def retry_call(fn: Callable[[], Any], *, attempts: int = 3,
               backoff: float = 1.0,
               backoff_max: float = BACKOFF_MAX_SECONDS,
               jitter: float = 0.25, seed: Optional[int] = None,
               describe: str = "operation",
               retry_on: Sequence[type] = (Exception,),
               sleep: Callable[[float], None] = time.sleep) -> Any:
    """Call ``fn`` up to ``attempts`` times with exponential backoff.

    Jitter is drawn from ``random.Random(seed)`` — pass the rank as the
    seed and the delays are deterministic per process yet decorrelated
    across the job. Raises :class:`RetryError` (chained to the last
    failure) when every attempt failed."""
    rng = random.Random(seed)
    retry_on = tuple(retry_on)
    last: Optional[BaseException] = None
    for attempt in range(1, attempts + 1):
        try:
            return fn()
        except retry_on as exc:
            last = exc
            if metrics.on():
                _retry_metrics().failures.inc()
                metrics.record_event("retry", what=describe, attempt=attempt,
                                     attempts=attempts,
                                     error=str(exc)[:200])
            if attempt == attempts:
                break
            delay = min(backoff_max, backoff * (2.0 ** (attempt - 1)))
            if jitter:
                delay *= 1.0 + jitter * rng.uniform(-1.0, 1.0)
            logging.warning(
                "%s failed (attempt %d/%d): %s; retrying in %.1fs",
                describe, attempt, attempts, exc, max(0.0, delay))
            if delay > 0:
                if metrics.on():
                    _retry_metrics().backoff.inc(delay)
                sleep(delay)
    if metrics.on():
        _retry_metrics().giveups.inc()
        metrics.record_event("retry_giveup", what=describe,
                             attempts=attempts, error=str(last)[:200])
    raise RetryError(describe, attempts, last) from last


def run_with_deadline(fn: Callable[[], Any], seconds: float,
                      describe: str = "operation") -> Any:
    """Run ``fn`` on a worker thread and give up after ``seconds``.

    A wedged native call can't be cancelled from Python — on timeout the
    daemon thread is abandoned (and says so in the log) while the caller
    gets a clean :class:`DeadlineExceeded` to retry or fail on, instead of
    hanging the whole rank."""
    if seconds <= 0:
        return fn()
    result: list = []
    error: list = []

    def _body():
        try:
            result.append(fn())
        except BaseException as exc:  # re-raised on the caller thread
            error.append(exc)

    t = threading.Thread(target=_body, name="hvd-deadline-call", daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        logging.error(
            "%s did not finish within %.1fs; abandoning the wedged attempt "
            "on a daemon thread", describe, seconds)
        raise DeadlineExceeded(
            f"{describe} did not finish within {seconds}s")
    if error:
        raise error[0]
    return result[0] if result else None
