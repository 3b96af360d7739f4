"""Fault-tolerance primitives for the BLS dispatch hot path.

Bench rounds before PR 1 lost their official number to *transient*
infrastructure faults (a compile response dropped mid-read, a one-shot
backend-init failure) and to a Mosaic lowering error on an untested
default. The reference client survives exactly this class of failure
through its execution-layer retry/fallback discipline
(``execution_layer``'s engine fallback + ``Fallback::first_success``;
SURVEY §5/§7.3: "keep a host CPU fallback path"). This module is that
discipline for the device dispatch path, built from four pieces:

* :func:`classify` — splits an exception into *transient* (an ABORTED
  runtime status, backend-init races, socket resets, deadline hits:
  retry is likely to succeed) vs *permanent* (Mosaic lowering errors,
  shape mismatches, correctness asserts: retrying is wasted budget,
  degrade instead), plus a ``kind`` label for metrics.
* :class:`RetryPolicy` — bounded exponential backoff with jitter;
  :func:`call_with_retries` applies it to any callable.
* :class:`CircuitBreaker` — closed → open → half-open per dispatch
  rung (``fused`` → ``classic`` → ``native``), mirrored into the
  ``bls_breaker_state`` gauge. Permanent failures trip straight to
  open; transients accumulate to the threshold. Half-open admits one
  probe; its outcome closes or re-opens.
* :class:`FaultInjector` — deterministic fault injection from
  ``LHTPU_FAULT_INJECT=<stage>:<kind>:<count>`` (comma-separable), so
  every rung of the degradation ladder is exercisable in CI without a
  TPU. Kinds raise error strings this JAX and libtpu raise on a TPU, so
  the injection exercises the same classifier path production hits.

Plus :func:`force_with_deadline`, the guard against hangs rather than
errors: a wedged device transfer becomes a classified transient
``DeadlineExceeded`` with stage attribution instead of eating the
bench watchdog budget (deadline-in-a-worker-thread, the same
surface-don't-deadlock discipline as ``common/timeout_lock.py``).

Env knobs (declared in :mod:`lighthouse_tpu.common.knobs`, all read at
call time, not import time — the PR 1 trace-time convention — except
breaker threshold/cooldown, read when a breaker is (re)created, i.e. at
import or :func:`reset`): ``LHTPU_RESILIENCE``, ``LHTPU_RETRY_MAX``,
``LHTPU_RETRY_BASE_MS``, ``LHTPU_RETRY_CAP_MS``, ``LHTPU_RETRY_JITTER``,
``LHTPU_RETRY_SEED``, ``LHTPU_BREAKER_THRESHOLD``,
``LHTPU_BREAKER_COOLDOWN_S``, ``LHTPU_SYNC_DEADLINE_S``,
``LHTPU_FAULT_INJECT``, ``LHTPU_FAULT_HANG_S`` — see the registry (or
README's knob table) for defaults and semantics.
"""

from __future__ import annotations

import random
import sys
import threading
import time

from . import knobs
from .metrics import REGISTRY

TRANSIENT = "transient"
PERMANENT = "permanent"

#: the degradation ladder, best rung first (jax_backend walks it)
LADDER = ("fused", "classic", "native")

# breaker states (the bls_breaker_state gauge values)
CLOSED, OPEN, HALF_OPEN = 0, 1, 2
_STATE_NAMES = {CLOSED: "closed", OPEN: "open", HALF_OPEN: "half-open"}


RETRIES_TOTAL = REGISTRY.counter(
    "bls_dispatch_retries_total",
    "Transient-fault retries inside BLS dispatch, by stage and fault kind",
    ("stage", "kind"),
)
BREAKER_STATE = REGISTRY.gauge(
    "bls_breaker_state",
    "Dispatch-rung circuit breaker state (0=closed, 1=open, 2=half-open)",
    ("path",),
)
DEGRADED_TOTAL = REGISTRY.counter(
    "bls_degraded_dispatches_total",
    "Verifications answered by a rung below the configured dispatch path",
    ("path",),
)
FAULTS_INJECTED = REGISTRY.counter(
    "bls_faults_injected_total",
    "Deterministic faults fired by LHTPU_FAULT_INJECT",
    ("stage", "kind"),
)
DEADLINE_TOTAL = REGISTRY.counter(
    "bls_deadline_exceeded_total",
    "Deadline-bounded operations that hit their deadline",
    ("stage",),
)
BREAKER_TRANSITIONS = REGISTRY.counter(
    "bls_breaker_transitions_total",
    "Breaker state changes by rung and destination state "
    "(the gauge only shows the latest state; flapping needs the counter)",
    ("rung", "to"),
)


def enabled() -> bool:
    """Retry + degradation ladder on? (``LHTPU_RESILIENCE=0`` restores
    the raw raise-through behavior; read per call.)"""
    return bool(knobs.knob("LHTPU_RESILIENCE"))


class DeadlineExceeded(TimeoutError):
    """A deadline-bounded operation (device_sync force) hit its
    deadline — a wedged transfer surfaced as a classified transient
    instead of an indefinite hang."""


class BatchPreempted(InterruptedError):
    """A coalesced batch lost its dispatch window to a higher class
    (the continuous scheduler preempting aggregates/attestations for a
    block). Transient by construction: the abandoned events re-enqueue
    at the front of their lanes exactly once and re-dispatch after the
    preempting work — any layer that observes the abort must retry in
    place, never degrade a rung or count a verdict."""


# --------------------------------------------------------------- classifier

# Message substrings (lowercased match) -> retry-worthiness. PERMANENT
# patterns are checked FIRST: a compile error that happens to mention
# "unavailable" must not be retried forever. The transient table is
# seeded with status strings JAX and libtpu raise on a TPU.
_PERMANENT_PATTERNS: tuple[tuple[str, str], ...] = (
    ("unimplemented primitive", "lowering"),
    ("mosaic", "lowering"),
    ("pallas", "lowering"),
    ("not implemented", "lowering"),
    ("invalid argument", "invalid"),
    ("invalid_argument", "invalid"),
    ("incompatible shapes", "shape"),
    ("resource_exhausted", "oom"),
    ("resource exhausted", "oom"),
    ("out of memory", "oom"),
    # A chip dropping out of the mesh mid-serve: the sharded program is
    # unrunnable until the mesh is rebuilt — permanent for THIS topology
    # (the sharded breaker degrades dispatch to single-chip; half-open
    # re-promotion probes the mesh after the cooldown).
    ("device lost", "chip_loss"),
    ("chip removed from mesh", "chip_loss"),
)
_TRANSIENT_PATTERNS: tuple[tuple[str, str], ...] = (
    # Backend start-up: libtpu refuses while another process holds the
    # chip or its lock file ("Unable to initialize backend 'tpu':
    # ABORTED: The TPU is already in use by process with pid ...").
    ("unable to initialize backend", "backend_init"),
    ("tpu is already in use", "backend_init"),
    ("connection reset", "socket"),
    ("connection refused", "socket"),
    ("connection aborted", "socket"),
    ("broken pipe", "socket"),
    ("socket", "socket"),
    ("unexpected eof", "socket"),
    # An absl ABORTED status from the runtime: the call was cut short
    # and may be re-issued (JaxRuntimeError "ABORTED: Internal error
    # when accessing libtpu multi-process lockfile.", seen when a second
    # process reaches for a held chip).
    ("aborted:", "aborted"),
    ("deadline exceeded", "hang"),
    ("deadline_exceeded", "hang"),
    ("timed out", "timeout"),
    ("timeout", "timeout"),
    ("unavailable", "unavailable"),
    ("temporarily", "unavailable"),
    ("try again", "unavailable"),
)
# Exception types whose class alone decides. Correctness-shaped types
# are permanent no matter the message (an AssertionError mentioning
# "timeout" is still a correctness assert).
_PERMANENT_TYPES = (
    NotImplementedError, AssertionError, TypeError, ValueError,
    KeyError, IndexError, AttributeError, ArithmeticError,
)
_TRANSIENT_TYPES = (ConnectionError, TimeoutError, InterruptedError, OSError)


def classify(exc: BaseException) -> tuple[str, str]:
    """(category, kind) for an exception: category is
    :data:`TRANSIENT` or :data:`PERMANENT`; kind is the metrics label
    (``aborted`` / ``backend_init`` / ``socket`` / ``hang`` /
    ``timeout`` / ``unavailable`` / ``lowering`` / ...). Unrecognized
    errors default to permanent: a wasted retry is cheap, but an
    unbounded retry of a correctness bug would mask it — the ladder
    still rescues the verdict."""
    if isinstance(exc, DeadlineExceeded):
        return TRANSIENT, "hang"
    if isinstance(exc, BatchPreempted):
        return TRANSIENT, "preempted"
    msg = f"{type(exc).__name__}: {exc}".lower()
    if isinstance(exc, _PERMANENT_TYPES):
        for pattern, kind in _PERMANENT_PATTERNS:
            if pattern in msg:
                return PERMANENT, kind
        return PERMANENT, type(exc).__name__
    for pattern, kind in _PERMANENT_PATTERNS:
        if pattern in msg:
            return PERMANENT, kind
    for pattern, kind in _TRANSIENT_PATTERNS:
        if pattern in msg:
            return TRANSIENT, kind
    if isinstance(exc, TimeoutError):
        return TRANSIENT, "timeout"
    if isinstance(exc, _TRANSIENT_TYPES):
        return TRANSIENT, "socket"
    return PERMANENT, "unclassified"


def is_transient(exc: BaseException) -> bool:
    return classify(exc)[0] == TRANSIENT


# ------------------------------------------------------------- retry policy

_JITTER_RNG = random.Random()
_JITTER_SEED_SEEN: str | None = None


def _jitter_rng() -> random.Random:
    """The module jitter RNG, re-seeded whenever LHTPU_RETRY_SEED
    changes (deterministic backoff schedules for tests/drills)."""
    global _JITTER_SEED_SEEN
    seed = knobs.knob("LHTPU_RETRY_SEED")
    if seed != _JITTER_SEED_SEEN:
        _JITTER_SEED_SEEN = seed
        _JITTER_RNG.seed(None if seed is None else seed)
    return _JITTER_RNG


class RetryPolicy:
    """Bounded exponential backoff + jitter (reference:
    execution_layer's capped engine-retry schedule)."""

    def __init__(self, max_retries: int | None = None,
                 base_s: float | None = None, cap_s: float | None = None,
                 jitter: float | None = None):
        self.max_retries = (
            int(knobs.knob("LHTPU_RETRY_MAX")) if max_retries is None
            else max_retries
        )
        self.base_s = (
            knobs.knob("LHTPU_RETRY_BASE_MS") / 1e3 if base_s is None
            else base_s
        )
        self.cap_s = (
            knobs.knob("LHTPU_RETRY_CAP_MS") / 1e3 if cap_s is None
            else cap_s
        )
        self.jitter = (
            knobs.knob("LHTPU_RETRY_JITTER") if jitter is None
            else jitter
        )

    def backoff(self, attempt: int) -> float:
        """Sleep before retry ``attempt`` (1-based): base * 2^(n-1),
        capped, plus up to ``jitter`` fraction on top (decorrelates
        herds of retries against a recovering device)."""
        delay = min(self.cap_s, self.base_s * (2 ** (attempt - 1)))
        if self.jitter > 0 and delay > 0:
            delay *= 1.0 + self.jitter * _jitter_rng().random()
        return delay

    def sleep(self, attempt: int) -> None:
        delay = self.backoff(attempt)
        if delay > 0:
            time.sleep(delay)


def retry_policy() -> RetryPolicy:
    """A policy from the current env (read per call)."""
    return RetryPolicy()


def call_with_retries(fn, stage: str, policy: RetryPolicy | None = None):
    """Run ``fn`` retrying transient failures per ``policy``; permanent
    failures and exhausted budgets re-raise. Every retry lands in
    ``bls_dispatch_retries_total{stage,kind}``."""
    if not enabled():
        return fn()
    if policy is None:
        policy = retry_policy()
    attempt = 0
    while True:
        try:
            return fn()
        except Exception as exc:
            category, kind = classify(exc)
            if category != TRANSIENT or attempt >= policy.max_retries:
                raise
            attempt += 1
            RETRIES_TOTAL.inc(stage=stage, kind=kind)
            policy.sleep(attempt)


# ----------------------------------------------------------- circuit breaker


class CircuitBreaker:
    """closed → open → half-open breaker for one dispatch rung.

    * closed: all calls allowed; ``threshold`` consecutive failures
      (or ONE permanent failure — a lowering bug will not heal) open it.
    * open: calls refused until ``cooldown_s`` elapses, then half-open.
    * half-open: exactly one probe admitted; success closes, failure
      re-opens (and re-arms the cooldown).

    State mirrors into ``bls_breaker_state{path=...}`` (0/1/2).
    ``clock`` is injectable for deterministic tests.
    """

    def __init__(self, name: str, threshold: int | None = None,
                 cooldown_s: float | None = None, clock=time.monotonic):
        self.name = name
        self.threshold = (
            int(knobs.knob("LHTPU_BREAKER_THRESHOLD")) if threshold is None
            else threshold
        )
        self.cooldown_s = (
            knobs.knob("LHTPU_BREAKER_COOLDOWN_S") if cooldown_s is None
            else cooldown_s
        )
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        BREAKER_STATE.set(CLOSED, path=name)

    @property
    def state(self) -> int:
        return self._state

    @property
    def state_name(self) -> str:
        return _STATE_NAMES[self._state]

    def _set(self, state: int) -> None:
        if state != self._state:
            BREAKER_TRANSITIONS.inc(rung=self.name, to=_STATE_NAMES[state])
        self._state = state
        BREAKER_STATE.set(state, path=self.name)

    def allow(self) -> bool:
        """May a call go through this rung right now? (open → half-open
        transition happens here once the cooldown has elapsed.)"""
        with self._lock:
            if self._state == CLOSED:
                return True
            if self._state == OPEN:
                if self._clock() - self._opened_at < self.cooldown_s:
                    return False
                self._set(HALF_OPEN)
                self._probing = True
                return True
            # HALF_OPEN: admit exactly one in-flight probe
            if self._probing:
                return False
            self._probing = True
            return True

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._probing = False
            if self._state != CLOSED:
                self._set(CLOSED)

    def record_failure(self, permanent: bool = False) -> None:
        with self._lock:
            self._failures += 1
            was_probe = self._probing
            self._probing = False
            if permanent or was_probe or self._failures >= self.threshold:
                self._opened_at = self._clock()
                self._set(OPEN)

    def release(self) -> None:
        """Return an admitted-but-unused call slot: a half-open probe
        that its caller decided not to dispatch after all (e.g. the
        sharded planner admitted a dispatch whose retained packs turn
        out not to divide the mesh) must not wedge the breaker
        half-open with a phantom in-flight probe."""
        with self._lock:
            self._probing = False


_BREAKERS: dict[str, CircuitBreaker] = {}
_BREAKERS_LOCK = threading.Lock()


def breaker(path: str) -> CircuitBreaker:
    """The process-wide breaker for a dispatch rung (created on first
    use; env thresholds read then — :func:`reset` re-reads)."""
    with _BREAKERS_LOCK:
        br = _BREAKERS.get(path)
        if br is None:
            br = _BREAKERS[path] = CircuitBreaker(path)
        return br


def breaker_states() -> dict[str, str]:
    """{rung: state-name} for every ladder rung plus any extra breakers
    created on demand (e.g. the dispatch engine's "sharded" breaker —
    bench/report surface)."""
    with _BREAKERS_LOCK:
        extra = [p for p in _BREAKERS if p not in LADDER]
    return {
        path: breaker(path).state_name for path in (*LADDER, *extra)
    }


def breaker_transitions_total() -> float:
    """Sum of ``bls_breaker_transitions_total`` over every rung/state —
    the flap-rate sentinel and the soak's per-epoch delta read this."""
    return sum(v for _, v in BREAKER_TRANSITIONS.items())


# ------------------------------------------------------------ fault injection

# kind -> exception factory raising status strings this JAX and libtpu
# raise on a TPU, so injected faults walk the same classifier path
# production faults do ([injected] marks them in logs).
_FAULT_FACTORIES = {
    "aborted": lambda: RuntimeError(
        "ABORTED: Internal error when accessing libtpu multi-process "
        "lockfile. [injected]"
    ),
    "backend_init": lambda: RuntimeError(
        "Unable to initialize backend 'tpu': ABORTED: The TPU is already "
        "in use by process with pid 4242. Not attempting to load "
        "libtpu.so in this process. [injected]"
    ),
    "socket": lambda: ConnectionResetError(
        "[Errno 104] Connection reset by peer [injected]"
    ),
    "unavailable": lambda: RuntimeError(
        "UNAVAILABLE: TPU device is not available [injected]"
    ),
    "mosaic": lambda: NotImplementedError(
        "Unimplemented primitive in Pallas TPU lowering for "
        "KernelType.TC: dynamic_slice [injected]"
    ),
    "shape": lambda: TypeError(
        "incompatible shapes for dispatch operands [injected]"
    ),
    "assert": lambda: AssertionError("injected correctness assert"),
    "chip_loss": lambda: RuntimeError(
        "INTERNAL: Device lost: TPU chip removed from mesh "
        "(interconnect failure) [injected]"
    ),
    "preempted": lambda: BatchPreempted(
        "coalesced batch preempted by higher-class work [injected]"
    ),
}


class FaultInjector:
    """Deterministic stage-targeted faults from ``LHTPU_FAULT_INJECT``.

    Spec: ``stage:kind:count`` items, comma-separated; each matching
    :meth:`fire` consumes one count and raises the kind's exception
    (``hang`` sleeps ``LHTPU_FAULT_HANG_S`` instead — a wedge, not an
    error). The spec string is re-read every call; changing it resets
    the remaining counts, so one process can run a whole drill matrix.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._spec: str | None = None
        self._remaining: dict[tuple[str, str], int] = {}
        self._warned: set[str] = set()

    def _refresh_locked(self) -> None:
        spec = knobs.knob("LHTPU_FAULT_INJECT")
        if spec == self._spec:
            return
        self._spec = spec
        self._remaining = {}
        for item in filter(None, (p.strip() for p in spec.split(","))):
            try:
                stage, kind, count = item.split(":")
                self._remaining[(stage, kind)] = int(count)
            except ValueError:
                if item not in self._warned:
                    self._warned.add(item)
                    print(
                        f"resilience: ignoring malformed "
                        f"LHTPU_FAULT_INJECT item {item!r} "
                        f"(want stage:kind:count)",
                        file=sys.stderr,
                    )

    def fire(self, stage: str) -> None:
        """Raise (or hang) if the spec has a live fault for ``stage``;
        no-op otherwise. The fast path (no env) is one dict read."""
        if not knobs.knob("LHTPU_FAULT_INJECT"):
            if self._spec:
                with self._lock:
                    self._refresh_locked()
            return
        with self._lock:
            self._refresh_locked()
            kind = None
            for (st, kd), left in self._remaining.items():
                if st == stage and left > 0:
                    self._remaining[(st, kd)] = left - 1
                    kind = kd
                    break
            if kind is None:
                return
        FAULTS_INJECTED.inc(stage=stage, kind=kind)
        if kind == "hang":
            time.sleep(knobs.knob("LHTPU_FAULT_HANG_S"))
            return
        raise _FAULT_FACTORIES.get(
            kind, lambda: RuntimeError(f"injected fault: {kind}")
        )()

    def reset(self) -> None:
        with self._lock:
            self._spec = None
            self._remaining = {}


_INJECTOR = FaultInjector()


def maybe_inject(stage: str) -> None:
    """Fire a pending injected fault for ``stage`` (production no-op
    unless ``LHTPU_FAULT_INJECT`` is set)."""
    _INJECTOR.fire(stage)


def rearm_faults() -> None:
    """Re-arm ``LHTPU_FAULT_INJECT`` counts WITHOUT touching breaker
    state. The injector keeps exhausted counts while the spec string is
    unchanged (so one drill matrix can run in-process); a soak that
    schedules the same fault in consecutive epochs must re-arm at each
    epoch boundary to get that epoch's fresh fault budget."""
    _INJECTOR.reset()


# ------------------------------------------------------------------ deadline


def force_with_deadline(fn, stage: str = "device_sync",
                        deadline_s: float | None = None):
    """Run ``fn`` under a wall-clock deadline; on expiry raise
    :class:`DeadlineExceeded` (transient, kind=hang) with stage
    attribution instead of hanging into the bench watchdog.

    The callable runs in a daemon worker thread that is ABANDONED on
    expiry (a thread wedged inside a dead PJRT transfer cannot be
    cancelled — the caller's retry re-dispatches instead). Injected
    faults for ``stage`` fire inside the guarded region, so the
    ``hang`` kind exercises exactly this deadline. ``deadline_s`` <= 0
    runs inline (no thread, no guard)."""
    if deadline_s is None:
        deadline_s = knobs.knob("LHTPU_SYNC_DEADLINE_S")
    if deadline_s <= 0:
        maybe_inject(stage)
        return fn()
    box: dict = {}

    def run():
        try:
            maybe_inject(stage)
            box["value"] = fn()
        except BaseException as exc:  # surfaced on the caller thread
            box["error"] = exc

    worker = threading.Thread(
        target=run, daemon=True, name=f"lhtpu-{stage}-deadline"
    )
    worker.start()
    worker.join(deadline_s)
    if worker.is_alive():
        DEADLINE_TOTAL.inc(stage=stage)
        raise DeadlineExceeded(
            f"{stage} exceeded its {deadline_s}s deadline "
            f"(wedged device transfer?)"
        )
    if "error" in box:
        raise box["error"]
    return box.get("value")


# --------------------------------------------------------------------- reset


def reset() -> None:
    """Forget breaker state and pending injected faults; re-read breaker
    env knobs on next use. Test/drill isolation hook."""
    with _BREAKERS_LOCK:
        _BREAKERS.clear()
    for path in LADDER:
        breaker(path)  # re-create eagerly so /metrics always shows all rungs
    _INJECTOR.reset()


# Eagerly surface every rung's breaker (gauge=0) on the first scrape.
for _path in LADDER:
    breaker(_path)
del _path
