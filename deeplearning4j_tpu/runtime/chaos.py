"""Process-wide deterministic chaos harness for the serving tier.

The training path already proves its failure handling with induced
faults (``FaultInjector``, runtime/resilience.py) — this module
generalizes that discipline to the WHOLE process: a seeded
``ChaosPlan`` schedules faults against named injection seams
(``fault_point("fleet.dispatch")``-style) wired at every dispatch
boundary, so the breaker/quarantine/hedge/brownout machinery in
serving/fleet.py is tested against the failures it exists for — and
the same fault sequence replays from the same seed.

Seam inventory (every caller passes its payload through the seam so a
``corrupt`` rule can mutate it in flight):

========================  ============================================
seam                      dispatch boundary
========================  ============================================
``host.submit``           ServedModel.submit (serving/host.py)
``host.submit_sequence``  ServedSequenceModel.submit (serving/host.py)
``queue.dispatch``        MicroBatcher coalesced dispatch
                          (serving/queue.py, inside the batch-failure
                          try so an injected raise fails the batch the
                          organic way)
``sequence.step``         SequenceScheduler slot-batched decode step
                          (serving/sequence.py)
``fleet.dispatch``        FleetRouter per-replica dispatch attempt
                          (serving/fleet.py, inside the failover try)
``server.request``        the HTTP GET/POST handlers (serving/
                          server.py; ordinals interleave in request
                          order)
``checkpoint.write``      ResilientFit._save (runtime/resilience.py,
                          inside the retry() lambda)
``checkpoint.restore``    ResilientFit._maybe_resume
========================  ============================================

Fault kinds, per rule: ``raise`` N times, ``wedge`` for T seconds
(blocks on an optional release event — the injectable-clock wedge),
``slow`` by T seconds, and ``corrupt`` (payload transform). Every rule
resolves to an explicit set of per-seam invocation ordinals at
SCHEDULE time — rate-based rules draw those ordinals from the plan's
seeded RNG — so the fired sequence is a pure function of the seed and
each seam's invocation order, never of thread timing. ``plan.events``
records ``(seam, kind, ordinal)`` in fire order; two plans with the
same seed driven through the same traffic produce identical lists.

Zero overhead when nothing is armed: ``fault_point`` is a module-level
read of one global (no lock, no allocation) before returning the
payload unchanged, and an ARMED plan short-circuits the same way for
seams it has no rules for — the armed-vs-disarmed serving overhead
gate (bench `serving_chaos`) holds at <=1.03x because of these two
fast paths. No jax import anywhere in this module, so wiring a seam
into a module can never add an accelerator dependency.

Telemetry: ``dl4j_chaos_injections_total{seam,kind}`` counts every
fired fault (docs/OBSERVABILITY.md); tests separate injected failures
from organic ones by exception type (``ChaosError``).

See docs/RESILIENCE.md "Chaos harness".
"""

from __future__ import annotations

import random
import threading

__all__ = ["ChaosError", "ChaosPlan", "SEAMS", "arm", "armed_plan",
           "disarm", "fault_point", "register_seam", "registered_seams"]

#: the built-in seam inventory; new boundaries add theirs via
#: ``register_seam`` — arming a plan that schedules a name in neither
#: is rejected (a typo'd seam would otherwise silently never fire)
SEAMS = ("host.submit", "host.submit_sequence", "queue.dispatch",
         "sequence.step", "fleet.dispatch", "server.request",
         "checkpoint.write", "checkpoint.restore")

#: seams registered at runtime beyond the built-in inventory
_EXTRA_SEAMS = set()


def register_seam(name):
    """Register a seam name beyond the built-in ``SEAMS`` inventory so
    plans scheduling it pass arm-time validation. Idempotent; returns
    the name (handy at module scope: ``SEAM = register_seam("x.y")``)."""
    name = str(name)
    if not name:
        raise ValueError("seam name must be non-empty")
    with _ARM_LOCK:
        if name not in SEAMS:
            _EXTRA_SEAMS.add(name)
    return name


def registered_seams():
    """Every seam a plan may schedule: the built-in inventory plus
    everything ``register_seam``-ed, as a tuple."""
    with _ARM_LOCK:
        return SEAMS + tuple(sorted(_EXTRA_SEAMS))

_KINDS = ("raise", "wedge", "slow", "corrupt")


class ChaosError(RuntimeError):
    """An INJECTED failure. Everything the harness raises derives from
    this (unless a rule overrides ``exc``), so tests can assert "zero
    non-injected errors" by error class."""


#: the module-level fast path: ``fault_point`` reads this one global
#: and returns immediately when no plan is armed
_PLAN = None
_ARM_LOCK = threading.Lock()


def fault_point(seam, payload=None):
    """The seam hook. Disarmed: one global read, payload returned
    unchanged. Armed: the plan fires whatever it scheduled for this
    invocation ordinal of `seam` (raise/wedge/slow) and returns the
    possibly-corrupted payload."""
    plan = _PLAN  # thread-ok[THR01]: atomic reference read; arm/disarm
    # swap the whole plan object, never mutate a live one's rule book
    if plan is None:
        return payload
    return plan._fire(seam, payload)


def arm(plan):
    """Install `plan` process-wide (replacing any armed plan).

    Rejects a plan that schedules rules against a seam that is neither
    in ``SEAMS`` nor ``register_seam``-ed: a typo'd seam name would
    otherwise arm fine and silently never fire — the chaos run reports
    green without having injected anything."""
    global _PLAN
    with _ARM_LOCK:
        unknown = sorted(set(getattr(plan, "_rules", ()) or ())
                         - set(SEAMS) - _EXTRA_SEAMS)
        if unknown:
            raise ValueError(
                "plan schedules unknown seam(s) "
                + ", ".join(repr(s) for s in unknown)
                + " — not in chaos.SEAMS and never register_seam()-ed; "
                "a typo'd seam would silently never fire")
        _PLAN = plan
    return plan


def disarm():
    """Remove the armed plan (restores the zero-overhead fast path).
    Returns the plan that was armed, or None."""
    global _PLAN
    with _ARM_LOCK:
        plan, _PLAN = _PLAN, None
    return plan


def armed_plan():
    return _PLAN


def default_corrupt(payload):
    """The stock payload corruption: numeric arrays get their first
    element poisoned (NaN for floats, flipped max for ints), strings/
    paths get a suffix that breaks them, bytes get a flipped bit.
    Anything else is returned unchanged (a wrapper object would break
    callers in ways no real corruption does)."""
    try:
        import numpy as np
    except ImportError:  # pragma: no cover - numpy is a hard dep in-repo
        np = None
    if np is not None and isinstance(payload, np.ndarray) \
            and payload.size:
        bad = np.array(payload, copy=True)
        flat = bad.reshape(-1)
        if np.issubdtype(bad.dtype, np.floating):
            flat[0] = np.nan
        elif np.issubdtype(bad.dtype, np.integer):
            flat[0] = np.iinfo(bad.dtype).max
        return bad
    if isinstance(payload, str):
        return payload + ".chaos-corrupt"
    if isinstance(payload, bytes):
        return bytes([payload[0] ^ 0xFF]) + payload[1:] if payload \
            else b"\xff"
    return payload


class ChaosPlan:
    """A seeded, replayable fault schedule over the named seams.

    Build rules before arming; each rule binds to explicit invocation
    ordinals of its seam (``at`` = first ordinal, ``times`` =
    consecutive count), or — for ``random_*`` rules — to ordinals drawn
    from the plan's seeded RNG at schedule time. Ordinals count the
    seam's ``fault_point`` invocations from 0 WHILE the plan is armed
    (a seam with no rules is never counted — that is the armed fast
    path).

    clock/sleep are injectable for deterministic tests: ``sleep``
    defaults to ``time.sleep``; pass e.g. ``ManualClock.advance`` to
    make wedge/slow rules advance virtual time instead of blocking.
    """

    def __init__(self, seed=0, sleep=None):
        import time as _time

        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._sleep = sleep if sleep is not None else _time.sleep
        self._lock = threading.Lock()
        self._rules = {}     # seam -> [rule dict]
        self._counts = {}    # seam -> invocations seen while armed
        #: (seam, kind, ordinal) in fire order — the replay record two
        #: equal-seed plans must produce identically
        self.events = []
        self._m_fired = None  # lazy: telemetry registered on first arm

    # -- schedule --------------------------------------------------------
    def _add(self, seam, kind, fires, **kw):
        if kind not in _KINDS:
            raise ValueError(f"unknown fault kind {kind!r} "
                             f"(one of {_KINDS})")
        rule = {"seam": str(seam), "kind": kind,
                "fires": frozenset(int(i) for i in fires), **kw}
        if not rule["fires"]:
            return self
        with self._lock:
            self._rules.setdefault(str(seam), []).append(rule)
        return self

    def raise_n(self, seam, times=1, at=0, exc=ChaosError,
                message="injected fault"):
        """Raise `exc` on invocations [at, at+times) of `seam`."""
        return self._add(seam, "raise", range(at, at + times),
                         exc=exc, message=str(message))

    def wedge(self, seam, seconds, at=0, times=1, release=None):
        """Block for `seconds` (or until `release` — a
        threading.Event — fires) on invocations [at, at+times): the
        wedged-replica fault."""
        return self._add(seam, "wedge", range(at, at + times),
                         seconds=float(seconds), release=release)

    def slow(self, seam, seconds, at=0, times=1):
        """Sleep `seconds` before proceeding on invocations
        [at, at+times): the slow-replica / slow-disk fault."""
        return self._add(seam, "slow", range(at, at + times),
                         seconds=float(seconds))

    def corrupt(self, seam, at=0, times=1, mutate=None):
        """Pass the seam payload through `mutate` (default:
        ``default_corrupt``) on invocations [at, at+times)."""
        return self._add(seam, "corrupt", range(at, at + times),
                         mutate=mutate or default_corrupt)

    def random_raises(self, seam, rate, window, exc=ChaosError,
                      message="injected fault"):
        """Seeded intermittent failures: each of the first `window`
        invocations of `seam` raises with probability `rate` — the
        ordinals are drawn NOW from the plan RNG, so the same seed
        schedules the same ordinals."""
        fires = [i for i in range(int(window))
                 if self._rng.random() < float(rate)]
        return self._add(seam, "raise", fires, exc=exc,
                         message=str(message))

    def random_slows(self, seam, rate, window, seconds):
        """Seeded intermittent slowness over the first `window`
        invocations of `seam`."""
        fires = [i for i in range(int(window))
                 if self._rng.random() < float(rate)]
        return self._add(seam, "slow", fires, seconds=float(seconds))

    # -- introspection ---------------------------------------------------
    def schedule(self):
        """{seam: sorted fire ordinals per rule} — the replayable
        schedule (a pure function of the seed + rule calls)."""
        with self._lock:
            return {seam: [sorted(r["fires"]) for r in rules]
                    for seam, rules in self._rules.items()}

    def fired(self, seam=None):
        """Count of fired faults (optionally for one seam)."""
        with self._lock:
            if seam is None:
                return len(self.events)
            return sum(1 for s, _, _ in self.events if s == seam)

    # -- runtime ---------------------------------------------------------
    def _metrics(self):
        # lazy so building a plan in a test never touches the registry
        # until the first fault actually fires
        if self._m_fired is None:  # thread-ok[THR01]: double-checked
            # fast path — a stale None just falls through to the lock,
            # where the check repeats before assignment
            with self._lock:
                if self._m_fired is None:
                    from deeplearning4j_tpu.runtime import telemetry

                    self._m_fired = telemetry.get_registry().counter(
                        "dl4j_chaos_injections_total",
                        "chaos faults fired, by seam and kind",
                        labels=("seam", "kind"))
        return self._m_fired  # thread-ok[THR01]: reference read of an
        # assign-once instrument; the registry dedupes by name anyway

    def _fire(self, seam, payload):
        rules = self._rules.get(seam)  # thread-ok[THR01]: rule books
        # are append-only before arming; the armed fast path reads the
        # dict snapshot and misses at worst a rule added mid-traffic
        if not rules:
            return payload  # the armed fast path: seam has no rules
        with self._lock:
            n = self._counts.get(seam, 0)
            self._counts[seam] = n + 1
            due = [r for r in rules if n in r["fires"]]
            for r in due:
                self.events.append((seam, r["kind"], n))
        # act OUTSIDE the lock: wedge/slow block, raise unwinds (a
        # THR03-clean seam can never stall an unrelated seam's fire)
        for r in due:
            self._metrics().labels(seam=seam, kind=r["kind"]).inc()
            kind = r["kind"]
            if kind == "slow":
                self._sleep(r["seconds"])
            elif kind == "wedge":
                ev = r.get("release")
                if ev is not None:
                    ev.wait(r["seconds"])
                else:
                    self._sleep(r["seconds"])
            elif kind == "corrupt":
                payload = r["mutate"](payload)
            elif kind == "raise":
                raise r["exc"](
                    f"chaos[{seam}#{n}]: {r['message']}")
        return payload

    # -- arming ----------------------------------------------------------
    def __enter__(self):
        arm(self)
        return self

    def __exit__(self, *exc):
        disarm()
        return False
