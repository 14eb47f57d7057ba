"""Iteration-level continuous batching for stateful models.

The one-shot tier (serving/queue.py) coalesces REQUESTS; sequence
workloads need the batch re-formed every DECODE STEP — the Orca
iteration-level scheduling insight. A batch run to completion pads
every sequence to its longest and holds finished slots hostage until
the stragglers drain; re-batching per step lets an early-exit slot be
refilled from the queue MID-SEQUENCE, so the device always steps a
full-as-possible batch of live tokens.

One scheduler, two kinds of slot. ``_SlotScheduler`` (private) is
everything that does not depend on what a slot holds:

* a bounded FIFO behind ``submit`` (``QueueFullError`` past
  ``queue_limit`` — backpressure, never a hang) and a **slot table** of
  at most ``max(slot_buckets)`` live requests; free slots are refilled
  from the queue at every iteration boundary;
* slot counts are **bucketed** (``slot_buckets``) through the AOT
  executable cache exactly like the one-shot tier's batch buckets: the
  compile budget is ``len(slot_buckets)``, ``warm()`` precompiles every
  bucket, and a warmed scheduler serves any mix of sequence lengths
  with ZERO steady-state compiles (CompileWatch-gated);
* per-request **deadlines are honored per step**: an expired request —
  queued OR mid-flight — is failed at the next iteration boundary and
  its slot refilled; the caller side of the contract is the request's
  ``wait`` (the release rules are stated once, on
  ``queue.InferenceRequest.wait``);
* one place where a request ends, done or failed (``_end_req``), the
  drivers (a background loop, or ``poll()``/``drain()`` with
  ``start_thread=False`` under an injected ``queue.ManualClock`` — the
  same zero-sleep deterministic test seam the MicroBatcher exposes),
  the ``dl4j_seq_*`` series and ``close``.

The two public classes are what a slot holds and what one iteration
does with it:

* ``SequenceScheduler`` — recurrent nets. A slot carries its own
  per-layer hidden/cell state as host arrays. Every iteration GATHERS
  the live carries into one ``[S, H]`` batch per layer/key (zero rows
  for empty slots), steps the model ONCE via the functional
  ``MultiLayerNetwork.rnnStepBatched`` (nn/multilayer.py), and SCATTERS
  the outputs + new carries back per slot. Rows are independent, so one
  executable per slot bucket serves any occupancy — padding can never
  perturb a live slot, and per-slot output is bitwise what serial
  ``rnnTimeStep`` produces (tests/test_sequence_serving.py gates it).
  Known limit, the PR 8 precedent: when a sequence's steps SPAN
  different slot buckets, the bucket change can alter XLA's dot
  lowering and round 1 ulp apart — within a fixed bucket parity is
  structural and bitwise; pin ``slot_buckets`` to one size where
  bitwise reproducibility across occupancy changes matters more than
  padded-row compute. Generation mode: a request may ask for
  ``extra_steps`` beyond its prompt; the next input row is then
  ``feedback(last_output_row)`` — the host-side closed loop of a
  char-rnn sampler (greedy argmax one-hot by default when the
  scheduler's ``feedback`` is set).
* ``PagedSequenceScheduler`` — paged-attention LMs. A slot holds KV
  pages of a bounded ``PagedKVCache``; an iteration is at most one
  prefill pass of one slot plus one slot-batched decode step (class
  docstring).

See docs/SERVING.md "Sequence serving + the fleet".
"""

from __future__ import annotations

import itertools
import threading
from collections import deque, namedtuple

import numpy as np

from deeplearning4j_tpu.nn.transformer import (PREFILL_CHUNK_PAGES,
                                               prefill_plan)
from deeplearning4j_tpu.ops.pallas_attention import paged_pages_visited
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.chaos import \
    fault_point as _chaos_fault_point
from deeplearning4j_tpu.runtime.chaos import register_seam
from deeplearning4j_tpu.serving.kvcache import (
    KVCacheFullError, PagedKVCache,
)
from deeplearning4j_tpu.serving.queue import (
    DeadlineExceededError, QueueFullError, ServingClosedError,
    occupancy_summary_from,
)
from deeplearning4j_tpu.serving.sampling import greedy_sampler, stream_rng

__all__ = ["SequenceRequest", "SequenceScheduler", "GenerationRequest",
           "PagedSequenceScheduler", "greedy_onehot_feedback"]

#: unique default metric label for anonymous schedulers
_SCHED_SEQ = itertools.count(1)

#: default slot-count buckets: one executable per bucket, ever
DEFAULT_SLOT_BUCKETS = (1, 2, 4, 8)

#: chunked-prefill chaos seam (PagedSequenceScheduler): fires before
#: each prompt chunk dispatch, so a ChaosPlan can fail/wedge/corrupt a
#: prefill exactly where production would (runtime/chaos.py)
PREFILL_SEAM = register_seam("sequence.prefill")

#: a decode step dispatched and not yet collected: its ids, its logits
#: rows for the host (None where no slot wants one; the whole [S, V]
#: block, or the rows of ``row_of``'s slots gathered on the device) and
#: its expert counts (None for a model without experts) on the device,
#: its requests in slot order, its bucket, and its ``sequence.step``
#: span's (start, duration, id, args), recorded when the step is
#: collected, with what the counts say
_Step = namedtuple("_Step", "ids rows row_of counts reqs S span")

#: most logits rows a decode step gathers on the device for the host;
#: where more of its slots want theirs, or the bucket is no larger, the
#: step copies its whole [S, V] block. At a vocabulary of 154,880 and 64
#: slots the block is 39.6 MB a step, its copy and fresh host pages
#: longer than the device's step
ROWS_GATHERED = 8

#: the registry families both scheduler classes record into (and
#: release per-instance series from at close()), one row a family:
#: key of the instrument set -> (kind, family, help)
_SEQ_FAMILIES = {
    "sequences": ("counter", "dl4j_seq_sequences_total",
                  "sequences accepted into the sequence queue"),
    "completed": ("counter", "dl4j_seq_completed_total",
                  "sequences completed (all steps served)"),
    "dispatches": ("counter", "dl4j_seq_dispatches_total",
                   "slot-batched decode-step dispatches"),
    "slot_steps": ("counter", "dl4j_seq_slot_steps_total",
                   "live slot-steps served (occupancy x dispatches)"),
    "expired": ("counter", "dl4j_seq_expired_total",
                "sequences failed by a per-step deadline expiry (504)"),
    "rejected": ("counter", "dl4j_seq_rejected_total",
                 "sequences rejected on a full queue (429)"),
    "errors": ("counter", "dl4j_seq_errors_total",
               "sequences failed by a dispatch error"),
    "refills": ("counter", "dl4j_seq_refills_total",
                "mid-sequence slot refills (admissions while other "
                "slots were mid-flight)"),
    "depth": ("gauge", "dl4j_seq_queue_depth",
              "sequences waiting for a slot"),
    "active": ("gauge", "dl4j_seq_active_slots",
               "slots occupied by live sequences"),
    "wait": ("histogram", "dl4j_seq_queue_wait_seconds",
             "enqueue-to-first-step wait per sequence"),
    "occupancy": ("histogram", "dl4j_seq_slot_occupancy",
                  "live-slots/bucket fill fraction per decode step"),
}

#: the stats keys the dict view carries: the counters
_STAT_KEYS = tuple(k for k, row in _SEQ_FAMILIES.items()
                   if row[0] == "counter")


def _seq_metrics(reg, name):
    """The dl4j_seq_* instrument set, labelled for one scheduler
    instance — shared by the carry-slot and KV-slot schedulers so both
    report through the same families (docs/OBSERVABILITY.md)."""
    out = {}
    for key, (kind, family, text) in _SEQ_FAMILIES.items():
        # a fill fraction is binned by its quartiles
        kw = {"buckets": (0.25, 0.5, 0.75, 1.0)} \
            if key == "occupancy" else {}
        out[key] = getattr(reg, kind)(
            family, text, labels=("model",), **kw).labels(model=name)
    return out


def _note_warm(report, name, warmed):
    """Enter one executable's (key, status, seconds) into a ``warm()``
    report under `name`; a status of None is not reported."""
    key, status, secs = warmed
    if status is not None:
        report[name] = {"key": key, "status": status,
                        "seconds": round(secs, 3)}


def greedy_onehot_feedback(vocab):
    """feedback closure for one-hot token models: argmax the output
    row, feed the matching one-hot back as the next input (greedy
    char-rnn sampling — deterministic, so generation tests stay
    bitwise)."""
    eye = np.eye(int(vocab), dtype=np.float32)

    def feedback(out_row):
        return eye[int(np.argmax(out_row))]

    return feedback


class _SlotRequest:
    """The waiting half of a request, whatever its slot holds: when it
    was enqueued, its deadline, when a slot was granted, and the event
    its caller waits on. ``wait`` follows the serving tier's one
    release contract — see ``queue.InferenceRequest.wait`` (dispatch
    failure, per-step deadline expiry, or caller-timeout release while
    the scheduler is mid-step)."""

    __slots__ = ("enqueued_at", "deadline", "started_at", "result",
                 "error", "_event")

    def __init__(self, enqueued_at, deadline=None):
        self.enqueued_at = float(enqueued_at)
        self.deadline = None if deadline is None else float(deadline)
        self.started_at = None              # a slot was granted
        self.result = None
        self.error = None
        self._event = threading.Event()

    @property
    def done(self):
        return self._event.is_set()

    def fail(self, exc):
        self.error = exc
        self._event.set()

    def wait(self, timeout=None):
        """Block for the result (``finish`` of the subclass makes it).
        Release rules are the serving tier's single wait contract —
        ``queue.InferenceRequest.wait``."""
        if not self._event.wait(timeout):
            raise DeadlineExceededError(f"no result within {timeout:.3f}s")
        if self.error is not None:
            raise self.error
        return self.result


class _SlotScheduler:
    """What the two slot schedulers share (module docstring): the
    queue, the slot table, deadlines, admission, the drivers, the one
    ending of a request, the ``dl4j_seq_*`` series and the shutdown.

    A subclass validates what is submitted and makes the request
    (``submit`` -> ``_enqueue``), prepares a slot at admission
    (``_admit_locked``), does one iteration (``_iterate_locked``),
    shapes a staging entry (``_new_staging``) and has its ``warm``; one
    whose slots hold something to give back extends ``_end_req`` and
    ``close``. Its constructor makes its own state after this one and
    calls ``_open`` last.

    slot_buckets: slot-count executable buckets; max(slot_buckets) is
                  the table capacity.
    queue_limit:  bound on WAITING sequences (QueueFullError past it).
    clock/start_thread/name: the MicroBatcher test seam — inject
                  ManualClock and drive ``poll()``/``drain()`` with no
                  thread for deterministic tests.
    """

    def __init__(self, model, *, slot_buckets, queue_limit, clock, name):
        if int(queue_limit) < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.model = model
        buckets = slot_buckets or DEFAULT_SLOT_BUCKETS
        self.slot_buckets = tuple(sorted(int(b) for b in buckets))
        if self.slot_buckets[0] < 1:
            raise ValueError(f"slot buckets must be >= 1, got {buckets}")
        self.max_slots = self.slot_buckets[-1]
        self.queue_limit = int(queue_limit)
        # one clock for every program span: the registry's, unless a
        # test injects its own (docs/OBSERVABILITY.md "One clock")
        self.clock = clock if clock is not None \
            else telemetry.get_registry().clock
        self._cond = threading.Condition()
        # one iteration at a time: the background loop and a concurrent
        # close(drain=True)/poll() caller must never both snapshot the
        # slot table and double-step a sequence
        self._step_lock = threading.Lock()
        self._pending = deque()
        self._active = []                   # the slot table
        self._staging = {}          # (S, half) -> reused buffers
        self._closed = False
        self.name = str(name) if name else f"seq{next(_SCHED_SEQ)}"
        #: (live slots, bucket) per decode dispatch — the occupancy
        #: record
        self.occupancy = []
        self._registry = telemetry.get_registry()
        self._thread = None

    def _open(self, start_thread):
        """Register this instance's series and start the loop: the last
        statement of a subclass's constructor, because the loop may run
        an iteration at once and that needs the subclass's state."""
        self._m = _seq_metrics(self._registry, self.name)
        if start_thread:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    # -- submit ---------------------------------------------------------
    def _enqueue(self, make_req, wait, timeout):
        """The queue's half of ``submit``: `make_req(now)` builds the
        validated request once the scheduler is known to be open and
        the queue to have room."""
        with self._cond:
            if self._closed:
                raise ServingClosedError("sequence scheduler is closed")
            if len(self._pending) >= self.queue_limit:
                self._m["rejected"].inc()
                raise QueueFullError(
                    f"sequence queue full ({len(self._pending)} waiting, "
                    f"queueLimit={self.queue_limit})")
            req = make_req(self.clock())
            self._pending.append(req)
            self._m["sequences"].inc()
            self._m["depth"].set(len(self._pending))
            self._cond.notify()
        if wait:
            return req.wait(timeout)
        return req

    # -- scheduling core (lock held) ------------------------------------
    def _end_req(self, req, exc=None):
        """The one place a request ends, done (exc None) or failed:
        every path of both classes comes through here."""
        if exc is None:
            req.finish()
        else:
            req.fail(exc)

    def _expire_locked(self, now):
        """Fail every request — queued or MID-FLIGHT — whose deadline
        has passed: the per-step deadline contract. A mid-flight expiry
        frees its slot this same iteration. Returns how many."""
        keep = deque()
        n = len(self._pending) + len(self._active)
        for req in self._pending:
            if req.deadline is not None and now >= req.deadline:
                self._m["expired"].inc()
                self._end_req(req, DeadlineExceededError(
                    f"deadline passed {now - req.deadline:.3f}s before "
                    "a slot was granted"))
            else:
                keep.append(req)
        self._pending = keep
        live = []
        for req in self._active:
            if req.deadline is not None and now >= req.deadline:
                self._m["expired"].inc()
                self._end_req(req, DeadlineExceededError(
                    f"deadline passed at {req.progress()} — slot "
                    "released mid-sequence"))
            else:
                live.append(req)
        self._active = live
        self._m["depth"].set(len(self._pending))
        self._m["active"].set(len(self._active))
        return n - len(keep) - len(live)

    def _refill_locked(self, now):
        """Admit queued requests into free slots, at every iteration
        boundary: slots freed by early exit or expiry are re-used
        MID-SEQUENCE. Returns (how many were admitted, what
        ``_admit_locked`` handed back for them, for the caller to
        process OUTSIDE this lock)."""
        midrun = bool(self._active)
        admitted, handed = 0, []
        while self._pending and len(self._active) < self.max_slots:
            req = self._pending.popleft()
            req.started_at = now
            admitted += 1
            back = self._admit_locked(req, now)
            self._active.append(req)
            self._m["wait"].observe(now - req.enqueued_at)
            if midrun:
                self._m["refills"].inc()
            if back is not None:
                handed.append(back)
        self._m["depth"].set(len(self._pending))
        self._m["active"].set(len(self._active))
        return admitted, handed

    def _fail_active(self, reqs, exc):
        """Fail mid-flight requests with `exc` and free their slots."""
        with self._cond:
            self._m["errors"].inc(len(reqs))
            for req in reqs:
                self._end_req(req, exc)
            self._active = [r for r in self._active if r not in reqs]
            self._m["active"].set(len(self._active))

    def bucket_for(self, n):
        """Smallest slot bucket >= n live slots (the executable that
        serves this iteration)."""
        for b in self.slot_buckets:
            if n <= b:
                return b
        return self.slot_buckets[-1]

    def _staging_for(self, S, half=0):
        """Per-bucket staging buffers (``_new_staging`` of the subclass
        shapes them), allocated once and reused: a fresh np.zeros per
        array per step was pure allocator churn. A dispatch
        may read its numpy arguments in place instead of copying them
        (the CPU backend does), so a set is refilled only once the step
        that read it has delivered its outputs: the carry scheduler
        waits for each step before it builds the next, and the paged
        one, which keeps a step queued ahead of the host, alternates
        two sets a bucket (`half` 0 or 1)."""
        st = self._staging.get((S, half))
        if st is None:
            st = self._staging[(S, half)] = self._new_staging(S)
        return st

    # -- drivers --------------------------------------------------------
    def _step_once(self):
        """One scheduler iteration (``_iterate_locked`` of the
        subclass); returns its progress count (0 = idle). Serialized by
        the step lock — concurrent drivers (background loop vs a
        draining close) take turns instead of double-stepping a
        sequence."""
        with self._step_lock:
            return self._iterate_locked()  # fault-ok[FLT04]: the step lock is the scheduler's own serialization contract — a seam firing under it IS the wedged-scheduler fault the harness injects, and waiters are released by deadline expiry (the wait contract), never by this lock

    def poll(self):
        """One synchronous scheduler iteration (the thread-less test
        seam): expire, refill, one step. Returns the progress count —
        0 means idle (nothing queued or active). Deterministic under
        ManualClock: no sleeps, no background thread."""
        return self._step_once()

    def drain(self):
        """Run iterations until the table AND queue are empty (ignores
        nothing — deadlines still expire per step on the clock)."""
        while self._step_once():
            pass
        return self

    def _wait_for_work(self):
        """Block the background loop until something is queued or
        active (True), or the scheduler is closed with nothing left
        (False). An idle period — from the first wait that found
        nothing to the wake-up that finds work, or to close() — is ONE
        ``sequence.idle`` span, not one per 50 ms poll: the device idle
        for want of demand reads apart from the device idle with work
        pending."""
        idle_since = None
        with self._cond:
            while not self._pending and not self._active \
                    and not self._closed:
                if idle_since is None:
                    idle_since = self.clock()
                self._cond.wait(0.05)
            work = bool(self._pending or self._active)
        if idle_since is not None:
            self._registry.add_span("sequence.idle", "serving", idle_since,
                                    self.clock() - idle_since)
        return work

    def _loop(self):
        while self._wait_for_work():
            try:
                self._step_once()
            except Exception as e:
                # defensive: an unexpected scheduler bug must release
                # every waiter, never leave them blocked on a dead
                # thread; the loop stays up for new submits
                self._fail_all(e)

    def _clear_locked(self, queued_exc, active_exc):
        """End everything queued and everything in a slot, failed, and
        leave the table empty."""
        while self._pending:
            self._end_req(self._pending.popleft(), queued_exc)
        for req in self._active:
            self._end_req(req, active_exc)
        self._active = []
        self._m["depth"].set(0)
        self._m["active"].set(0)

    def _fail_all(self, exc):
        """Fail every queued + active request with `exc` and clear the
        table (the scheduler-bug escape hatch)."""
        with self._cond:
            n = len(self._pending) + len(self._active)
            if n:
                self._m["errors"].inc(n)
            self._clear_locked(exc, exc)

    # -- introspection / lifecycle --------------------------------------
    @property
    def depth(self):
        """Sequences waiting for a slot."""
        with self._cond:
            return len(self._pending)

    @property
    def active_slots(self):
        with self._cond:
            return len(self._active)

    @property
    def stats(self):
        """Dict view over the registry counters (dl4j_seq_*)."""
        return {k: int(self._m[k].value) for k in _STAT_KEYS}

    def occupancy_summary(self):
        """Mean live-slots/bucket + quartile histogram over every
        decode step so far (the 'is the table sized right' signal —
        docs/SERVING.md)."""
        return occupancy_summary_from(self.occupancy, "mean_live_slots")

    def close(self, drain=True):
        """Stop accepting. drain=True serves everything already queued
        or mid-flight to completion; drain=False fails them with
        ServingClosedError."""
        with self._cond:
            self._closed = True
            if not drain:
                self._clear_locked(
                    ServingClosedError("scheduler closed before a slot "
                                       "was granted"),
                    ServingClosedError("scheduler closed mid-sequence"))
            self._cond.notify_all()
        if drain:
            self.drain()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        # release this instance's registry series (MicroBatcher.close
        # precedent: per-instance series must not accumulate forever)
        for _, metric, _ in _SEQ_FAMILIES.values():
            fam = self._registry.get(metric)
            if fam is not None:
                fam.remove(model=self.name)
        return self


class SequenceRequest(_SlotRequest):
    """One sequence: prompt features [T, F] consumed one timestep per
    scheduler iteration, plus optional generation steps.

    total steps = T + extra_steps; step t consumes ``features[t]`` for
    t < T and ``feedback(outputs[t-1])`` after. The result is the
    stacked per-step output [total, O]."""

    __slots__ = ("features", "steps", "extra_steps", "feedback",
                 "steps_done", "outputs", "carry")

    def __init__(self, features, enqueued_at, deadline=None,
                 extra_steps=0, feedback=None):
        super().__init__(enqueued_at, deadline)
        self.features = features            # [T, F] float32
        self.steps = int(features.shape[0]) + int(extra_steps)
        self.extra_steps = int(extra_steps)
        self.feedback = feedback
        self.steps_done = 0
        self.outputs = []                   # per-step [O] rows
        self.carry = None                   # per-layer {key: [H]} rows

    def next_input(self):
        """The feature row this sequence consumes at its next step."""
        t = self.steps_done
        if t < self.features.shape[0]:
            return self.features[t]
        if self.feedback is None:
            raise RuntimeError(
                "generation step with no feedback fn (extra_steps > 0 "
                "needs a request- or scheduler-level feedback)")
        return np.asarray(self.feedback(self.outputs[-1]),
                          np.float32)

    def progress(self):
        """How far the sequence is, for an error's text."""
        return f"step {self.steps_done}/{self.steps}"

    def finish(self):
        self.result = np.stack(self.outputs, axis=0)
        self._event.set()


class SequenceScheduler(_SlotScheduler):
    """Iteration-level slot scheduler over one recurrent model: a slot
    holds the sequence's h/c carries (module docstring).

    model:        an initialized MultiLayerNetwork with >=1 recurrent
                  layer (validated eagerly via ``rnnCarrySpec``).
    feedback:     scheduler-level generation feedback
                  (out_row [O]) -> next input row [F]; a request's own
                  feedback overrides it.
    slot_buckets, queue_limit, clock, start_thread, name: the base's.
    """

    def __init__(self, model, *, slot_buckets=None, queue_limit=64,
                 feedback=None, clock=None, start_thread=True, name=None):
        self._spec = model.rnnCarrySpec()   # validates the net, eagerly
        super().__init__(model, slot_buckets=slot_buckets,
                         queue_limit=queue_limit, clock=clock, name=name)
        # carries cross the jit boundary UNCAST (unlike x, which
        # _entry casts in-graph): host-side slot state must live in
        # the model's compute dtype or per-step outputs diverge from
        # serial rnnTimeStep on non-f32 policies
        self._carry_dtype = np.dtype(model._compute_dtype)
        self.feedback = feedback
        #: per-step feature width the submit contract validates
        self.feature_size = int(model.conf.inputType.size)
        self._open(start_thread)

    # -- submit ---------------------------------------------------------
    def submit(self, features, deadline=None, extra_steps=0,
               feedback=None, wait=True, timeout=None):
        """Enqueue one sequence of per-step features [T, F] (T >= 1).

        deadline: absolute time on this scheduler's clock; checked at
        every STEP boundary, queued or mid-flight. extra_steps: closed-
        loop generation steps past the prompt (needs a feedback fn).
        wait=True blocks for the stacked [T+extra, O] result; False
        returns the SequenceRequest.
        """
        features = np.asarray(features, np.float32)
        if features.ndim != 2 or features.shape[0] < 1:
            raise ValueError(
                f"features must be [steps, {self.feature_size}] with "
                f"steps >= 1, got shape {features.shape}")
        if features.shape[1] != self.feature_size:
            raise ValueError(
                f"per-step feature width {features.shape[1]} does not "
                f"match the model's {self.feature_size}")
        fb = feedback if feedback is not None else self.feedback
        if int(extra_steps) > 0 and fb is None:
            raise ValueError(
                "extra_steps > 0 needs a feedback fn (request- or "
                "scheduler-level) to close the generation loop")
        return self._enqueue(
            lambda now: SequenceRequest(features, now, deadline,
                                        extra_steps=extra_steps,
                                        feedback=fb),
            wait, timeout)

    # -- what a slot holds ----------------------------------------------
    def _zero_carries(self, *lead):
        """Per-layer {key: zeros [*lead, H]} in the compute dtype."""
        return [{k: np.zeros(lead + (int(self.model.layers[li].nOut),),
                             self._carry_dtype) for k in keys}
                for li, keys in enumerate(self._spec)]

    def _admit_locked(self, req, now):
        req.carry = self._zero_carries()

    def _new_staging(self, S):
        return (np.zeros((S, self.feature_size), np.float32),
                self._zero_carries(S))

    # -- one iteration (dispatch outside the lock) ----------------------
    def _gather(self, batch, S, rows):
        """Stack the batch's validated next-input rows + carries into
        the fixed [S, ...] bucket signature (zero rows pad the empty
        slots). Buffers come from the per-bucket staging pool; rows
        past the live batch are re-zeroed so a previous iteration's
        occupancy can never leak into the padding."""
        n = len(rows)
        x, carries = self._staging_for(S)
        for i, row in enumerate(rows):
            x[i] = row
        x[n:] = 0.0
        for li, keys in enumerate(self._spec):
            d = carries[li]
            for k in keys:
                col = d[k]
                for i, req in enumerate(batch):
                    col[i] = req.carry[li][k]
                col[n:] = 0
        return x, carries

    def _iterate_locked(self):
        """One iteration: expire -> refill -> gather -> dispatch ONE
        slot-batched decode step -> scatter. Returns the number of live
        slots stepped (0 = idle)."""
        # *_locked: called with the STEP lock held (one driver at a
        # time); the condition lock is still taken around each shared-
        # state section below
        with self._cond:
            now = self.clock()
            self._expire_locked(now)
            self._refill_locked(now)
            batch = list(self._active)
        if not batch:
            return 0
        # pull next-input rows BEFORE the padded gather: a raising (or
        # wrong-width) feedback fails ITS request and frees the slot —
        # it must never kill the scheduler thread (the wait contract:
        # no path leaves a caller blocked on a dead dispatcher)
        rows, bad = [], []
        for req in batch:
            try:
                row = np.asarray(req.next_input(),
                                 dtype=np.float32).reshape(-1)
                if row.shape[0] != self.feature_size:
                    raise ValueError(
                        f"feedback row has width {row.shape[0]}, "
                        f"model feature size is {self.feature_size}")
                rows.append(row)
            except Exception as e:
                bad.append(req)
                self._fail_active([req], e)
        if bad:
            batch = [r for r in batch if r not in bad]
            if not batch:
                return len(bad)     # progress: drain must not stall
        S = self.bucket_for(len(batch))
        x, carries = self._gather(batch, S, rows)
        t0 = self.clock()
        self._m["dispatches"].inc()
        self._m["slot_steps"].inc(len(batch))
        self._m["occupancy"].observe(len(batch) / S)
        self.occupancy.append((len(batch), S))
        try:
            # chaos seam INSIDE the step-failure try: an injected raise
            # fails this slot batch the way an organic step error does
            # (runtime/chaos.py)
            x = _chaos_fault_point("sequence.step", x)
            out, new_carries = self.model.rnnStepBatched(x, carries)
            out = np.asarray(out)
            # ONE device->host pull per carry array per iteration; the
            # per-slot scatter below then slices host rows (a per-slot
            # np.asarray of a jax row would pay S separate transfers)
            new_carries = [{k: np.asarray(v) for k, v in d.items()}
                           for d in new_carries]
        except Exception as e:
            self._fail_active(batch, e)
            return 0
        finally:
            self._registry.add_span(
                "sequence.step", "serving", t0, self.clock() - t0,
                model=self.name, slots=len(batch), bucket=S)
        # scatter: per-slot output row + refreshed carry rows
        finished = []
        with self._cond:
            for i, req in enumerate(batch):
                if req.done:        # expired/failed between gather+now
                    continue
                req.outputs.append(out[i])
                req.carry = [{k: new_carries[li][k][i] for k in keys}
                             for li, keys in enumerate(self._spec)]
                req.steps_done += 1
                if req.steps_done >= req.steps:
                    finished.append(req)
            if finished:
                self._active = [r for r in self._active
                                if r not in finished]
                self._m["completed"].inc(len(finished))
                self._m["active"].set(len(self._active))
        for req in finished:        # release waiters outside the lock
            self._end_req(req)
        return len(batch)

    @telemetry.phase("warm")
    def warm(self, cache=None):
        """Precompile the decode-step executable for EVERY slot bucket
        (hits are free) so a serving process steps its first sequence
        hot. Returns {bucket: {key, status, seconds}}. The warm
        signature mirrors the live dispatch EXACTLY (host-numpy
        carries, like _gather builds) — a mismatched container type
        would change the AOT signature and demote the first real step
        to a fresh compile."""
        import jax.numpy as jnp

        report = {}
        for S in self.slot_buckets:
            x = jnp.asarray(np.zeros((S, self.feature_size), np.float32))
            _note_warm(report, int(S), self.model._jit_rnn_step.warm(
                self.model._params,
                self.model._strip_carries(self.model._states),
                self._zero_carries(S), x, cache=cache))
        return report


def _take_rows(logits, idx):
    """The rows `idx` of a decode step's logits (the gather that spares
    the host the rest)."""
    return logits[idx]


class GenerationRequest(_SlotRequest):
    """One token-prompt generation request on the KV-slot path.

    The prompt is consumed in page-sized prefill chunks; generation
    then appends one token per decode iteration until ``max_new``
    tokens have been sampled. ``pages``/``block_row``/``seq_len`` are
    the slot's KV state (owned page ids, logical-block -> physical-page
    row, KV rows written by every step dispatched so far, a queued one's
    included). The result is the sampled token ids [max_new]
    (int64); where the request asked for them (``want_logits``, the
    default), ``logits`` is the float32 row each was sampled from,
    [max_new, V], whole once the request is done, and None otherwise:
    such a request keeps no block and its decode rows never leave the
    device for it.
    ``device_pick`` says the request's sampler marks itself the greedy
    pick (``sampling.greedy_sampler``), so its decode tokens are the
    step's own argmax.

    The request's timeline, seconds on the scheduler's clock, always
    set (telemetry on or off) and part of the API: ``enqueued_at``
    (submit), ``started_at`` (a slot was granted), ``first_chunk_at``
    (its first prompt chunk was dispatched, or its prompt was adopted
    whole), ``first_token_at``, ``token_times`` (one per sampled token,
    the first included) and ``finished_at`` (done or failed). Time to
    first token is ``first_token_at - enqueued_at``; the gaps between
    tokens are the differences of ``token_times``. ``clock`` is the
    scheduler's, which ``wait`` reads for the waiter's wake-up."""

    __slots__ = ("tokens", "max_new", "sampler", "rng", "stream_id",
                 "first_chunk_at", "first_token_at", "token_times",
                 "finished_at", "chunks", "prefilled",
                 "seq_len", "pages", "block_row", "out_tokens",
                 "device_pick", "want_logits", "_rows", "logits",
                 "_clock", "_woken")

    def __init__(self, tokens, enqueued_at, deadline=None, max_new=1,
                 sampler=None, rng=None, stream_id=0, want_logits=True,
                 clock=None):
        super().__init__(enqueued_at, deadline)
        self._clock = clock if clock is not None \
            else telemetry.get_registry().clock
        self._woken = False
        self.tokens = tokens                # [T] int32 prompt
        self.max_new = int(max_new)
        self.want_logits = bool(want_logits)
        self.sampler = sampler
        self.device_pick = bool(getattr(sampler, "picks_argmax", False))
        self.rng = rng
        self.stream_id = int(stream_id)
        self.first_chunk_at = None
        self.first_token_at = None
        self.token_times = []               # one clock read a token
        self.finished_at = None
        self.chunks = 0                     # prompt chunks dispatched
        self.prefilled = 0                  # prompt tokens with KV live
        self.seq_len = 0                    # total KV rows live
        self.pages = []                     # owned page ids (in order)
        self.block_row = None               # [MP] int32
        self.out_tokens = []                # sampled tokens, in order
        self._rows = None                   # fp32 [max_new, V], made once
        self.logits = None                  # the block, at finish

    def progress(self):
        """How far the generation is, for an error's text."""
        return f"{len(self.out_tokens)}/{self.max_new} tokens"

    def put_row(self, k, row):
        """Keep the float32 logits row the k-th token was sampled from,
        in the request's one block: ``finish`` copies nothing. Nothing
        is kept where the request asked for no logits."""
        if not self.want_logits:
            return
        if self._rows is None:
            self._rows = np.empty((self.max_new, row.shape[-1]),
                                  np.float32)
        self._rows[k] = row

    def finish(self):
        self.logits = self._rows
        self.result = np.asarray(self.out_tokens, np.int64)
        self._event.set()

    def wait(self, timeout=None):
        """The base's ``wait``. Its first return with a result records
        ``sequence.wake`` on the waiter's thread: from ``finished_at``,
        when the scheduler released the waiter, to this return."""
        out = super().wait(timeout)
        if self._woken:
            return out
        self._woken = True
        if telemetry.enabled():
            telemetry.get_registry().add_span(
                "sequence.wake", "serving", self.finished_at,
                self._clock() - self.finished_at, rid=self.stream_id)
        return out


class PagedSequenceScheduler(_SlotScheduler):
    """Iteration-level KV-slot scheduler over one paged-attention LM
    (``nn.transformer.CausalTransformerLM`` or any ``kind ==
    "paged_lm"`` twin).

    The carry-slot scheduler above gathers/scatters h/c rows; here the
    per-slot state is KV in a bounded ``PagedKVCache`` instead, and
    every iteration interleaves at most ONE prefill pass of one slot —
    the next chunk of ``nn.transformer.prefill_plan``, up to
    ``PREFILL_CHUNK_PAGES[-1]`` KV pages of the prompt (bounded work —
    a long prompt can never stall the running batch) — with one
    slot-batched decode step over every fully-prefilled slot.
    Admission, buckets, per-step deadlines, ManualClock/poll()/drain(),
    and the dl4j_seq_* metric families are the base's, shared with
    ``SequenceScheduler``; pool exhaustion surfaces as the typed
    ``KVCacheFullError`` (429), never a hang. Prefix sharing
    (``prefix_sharing=True``) adopts a registered prompt's pages
    copy-on-write at admission.

    A request's sampler decides where its decode tokens are picked. One
    that marks itself the greedy pick (``sampling.greedy_sampler``, the
    default) takes the argmax ``_decode_paged`` returns beside the
    logits. While every live slot is such, one decode step is kept
    queued ahead of the host: an iteration dispatches step n+1 on step
    n's ids while they are still on the device (``_decode_paged``'s
    ``prev_ids`` and ``src``), then waits for step n's ids and rows,
    appends the tokens, writes the rows into the requests' blocks and
    ends the requests whose last token that was, while the device runs
    step n+1. A request's end is known a step ahead from ``max_new``,
    so one whose last token is step n's is left out of step n+1; one
    that expires or fails while a step is queued for it has that step's
    row dropped. A prompt that completes while a step is queued joins
    the step after it: its first token is fetched behind that step's
    dispatch. Where no step is queued (the first decode of a batch),
    an iteration dispatches step n on the host's tokens and step n+1
    ahead of it, so every ``poll()`` still yields one token a decoding
    slot. Any other callable is called on the host,
    ``sampler(logits_row, rng) -> token`` with a per-request
    ``stream_rng(sampler_seed, stream_id)`` stream, stream ids assigned
    in submit order — deterministic per (seed, stream), so the
    bitwise-vs-serial gate holds with temperature sampling too; a step
    with such a slot is dispatched, waited for and
    sampled in one iteration, with nothing queued behind it. A prompt's
    first token is sampled on the host from the prefill's row either
    way. A request that asks for its logits (``submit(logits=True)``,
    the default) keeps every row: its ``logits`` holds one for each
    token, whole in the ``poll()`` of its last token. A decode step's
    rows leave the device only where one of its slots asked for them
    or samples on the host: up to ``ROWS_GATHERED`` such slots have
    their rows gathered on the device first (one small executable a
    bucket, warmed), more take the step's whole block.

    Spans (cat ``serving``, on this scheduler's clock; the tree is in
    docs/OBSERVABILITY.md): every iteration that found work is one
    ``sequence.iteration`` whose children are ``sequence.admit``,
    ``sequence.prefill`` and ``sequence.prefill_finish`` (rid = the
    request's ``stream_id``; the finish's own children, with the same
    rid, are ``_prefill_finish``'s parts), ``sequence.decode_prep`` and
    ``sequence.step`` for each decode dispatch, then
    ``sequence.fetch`` (the wait for the ids, its child
    ``sequence.fetch_wait``, then the rows where the step fetches them
    and the expert counts where the model has experts, of the step the
    iteration collects, the one dispatched before; ``bytes``),
    ``sequence.land`` (its ``rows`` written into their requests'
    blocks) and ``sequence.sample`` (tokens appended, requests ended);
    a request that ends, done or failed, leaves one instant
    ``sequence.request`` with its whole timeline, and its waiter a
    ``sequence.wake`` (``GenerationRequest.wait``).
    ``sequence.prefill`` carries the pass: ``chunk`` prompt tokens in a
    chunk of ``bucket`` tokens (the executable's length).
    ``sequence.step`` carries ``device_picked``, the live slots whose
    token was the device's, ``ahead``: 1 where the step took tokens
    from the previous step's ids on the device, dispatched before those
    ids reached the host, and ``kv_tokens``, the cache rows its live
    slots attend (the new ones included). A step of a model with routed
    experts (nn/latent_moe.py) also carries what its counts say:
    ``experts_touched``, the (layer, expert) pairs that took a live
    token, of ``experts_total``, and ``expert_tokens_max``, the most
    live tokens one expert of one layer took. A step's span is recorded
    when the step is collected, or let go unread, so that it carries
    them: nothing waits for the device to record it. ``sequence.step`` and ``sequence.prefill``
    say what the dispatcher chose for their attention: ``attend``
    (``"pallas"`` or ``"reference"``, the model's ``attend_impl()``),
    ``pages_visited`` (the live pages of the step's live slots, or of
    the chunk's query tiles of one page, on the kernel path, their
    whole tables on the reference path: derived by
    ``ops.pallas_attention.paged_pages_visited`` from the kernels' own
    rule, not counted on the device) and ``pages_table`` (live slots,
    or query tiles, x table width).
    """

    def __init__(self, model, *, num_pages, slot_buckets=None,
                 queue_limit=64, sampler=None, sampler_seed=0,
                 prefix_sharing=True, clock=None, start_thread=True,
                 name=None):
        if getattr(model, "kind", None) != "paged_lm":
            raise ValueError(
                "PagedSequenceScheduler needs a paged-LM step twin "
                f"(kind == 'paged_lm'), got {type(model).__name__}")
        super().__init__(model, slot_buckets=slot_buckets,
                         queue_limit=queue_limit, clock=clock, name=name)
        self.vocab = int(model.vocab)
        self.sampler = sampler if sampler is not None else greedy_sampler()
        self.sampler_seed = int(sampler_seed)
        self.prefix_sharing = bool(prefix_sharing)
        self.cache = PagedKVCache(
            n_layers=model.n_layers, page_shapes=model.page_shapes(),
            page_size=model.page_size, num_pages=num_pages,
            dtype=model._compute_dtype, model=self.name)
        self._mp = int(model.max_pages_per_slot)
        impl = getattr(model, "attend_impl", None)
        self._attend = impl() if impl is not None else "reference"
        self._stream_ids = itertools.count(0)
        #: the decode step dispatched ahead and not yet collected
        self._ahead = None
        self._half = 0                      # the staging set last filled
        self._zero_ids = {}                 # S -> device zeros [S] int32
        from deeplearning4j_tpu.runtime import aot

        self._take_rows = aot.cached_jit(_take_rows, entry="take_rows",
                                         fingerprint="take-rows")
        self._open(start_thread)

    # -- submit ---------------------------------------------------------
    def submit(self, tokens, deadline=None, max_new_tokens=1,
               sampler=None, wait=True, timeout=None, logits=True):
        """Enqueue one token prompt [T] (T >= 1, ids in [0, vocab)).

        max_new_tokens >= 1 tokens are generated (the first is sampled
        from the prompt's final logits, so KV grows by T + max_new - 1
        rows, bounded by the model's max_context). deadline: absolute
        time on this scheduler's clock, checked per step. wait=True
        blocks for the sampled token ids; False returns the
        GenerationRequest. logits=False keeps no logits rows for the
        request (``GenerationRequest.logits`` stays None). A prompt that
        could NEVER fit the pool is rejected up front with
        KVCacheFullError (429)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.shape[0] < 1:
            raise ValueError("prompt must have >= 1 token")
        if np.any(tokens < 0) or np.any(tokens >= self.vocab):
            raise ValueError(
                f"prompt token ids must be in [0, {self.vocab})")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = tokens.shape[0] + max_new - 1
        if total > self.model.max_context:
            raise ValueError(
                f"prompt + generation needs {total} KV rows, model "
                f"max_context is {self.model.max_context}")
        if self.cache.pages_for(total) > self.cache.capacity:
            raise KVCacheFullError(
                f"sequence needs {self.cache.pages_for(total)} pages, "
                f"pool capacity is {self.cache.capacity} — unservable "
                f"at any load")

        def make_req(now):      # under the queue's lock: ids in submit order
            sid = next(self._stream_ids)
            return GenerationRequest(
                tokens, now, deadline, max_new=max_new,
                sampler=sampler if sampler is not None else self.sampler,
                rng=stream_rng(self.sampler_seed, sid), stream_id=sid,
                want_logits=logits, clock=self.clock)

        return self._enqueue(make_req, wait, timeout)

    # -- what a slot holds ----------------------------------------------
    def _admit_locked(self, req, now):
        """A block row for the slot; prefix sharing adopts registered
        pages copy-on-write here. An exact-prompt adoption may complete
        the prompt outright — its first token is sampled from the
        registered logits, which are handed back with the request."""
        req.block_row = np.zeros((self._mp,), np.int32)
        if not self.prefix_sharing:
            return None
        pages, n_shared, logits = self.cache.match_prefix(req.tokens)
        if pages:
            req.pages = list(pages)
            req.block_row[:len(pages)] = pages
            req.prefilled = req.seq_len = int(n_shared)
        if logits is None:
            return None
        req.first_chunk_at = now            # adopted whole: no chunk
        return req, logits

    def _end_req(self, req, exc=None):
        """A request ends, done (exc None) or failed: its pages go back
        to the pool, ``finished_at`` is stamped and its timeline left as
        one instant ``sequence.request`` before its waiter is released.
        An instant and not a span as long as the request: such a span
        would cover every device-idle gap of a busy scheduler and take,
        in an idle-gap attribution, the time that belongs to no span.
        The pages go back at once even while a queued step still writes
        the request's row into one of them: whoever gets a page next
        writes it from a step dispatched later, on the same device
        stream, so after that row."""
        if req.pages:
            self.cache.release(req.pages)
            req.pages = []
        req.finished_at = now = self.clock()
        self._registry.event(
            "sequence.request", "serving", ts=now, rid=req.stream_id,
            prompt_tokens=int(req.tokens.shape[0]),
            new_tokens=len(req.out_tokens), chunks=req.chunks,
            enqueued_at=req.enqueued_at, started_at=req.started_at,
            first_chunk_at=req.first_chunk_at,
            first_token_at=req.first_token_at, finished_at=now,
            token_times=tuple(req.token_times),
            error=None if exc is None else type(exc).__name__)
        super()._end_req(req, exc)

    def _first_token(self, req, last_logits):
        """The prompt is fully in KV: sample the first generated token
        from its final-position logits. Returns True if that is the
        request's last (max_new == 1)."""
        row = np.asarray(last_logits, np.float32)
        req.put_row(0, row)
        req.out_tokens.append(int(req.sampler(row, req.rng)))
        req.first_token_at = self.clock()
        req.token_times.append(req.first_token_at)
        return len(req.out_tokens) >= req.max_new

    def _finish_req(self, req):
        with self._cond:
            self._active = [r for r in self._active if r is not req]
            self._m["completed"].inc()
            self._m["active"].set(len(self._active))
        self._end_req(req)

    def _prefill_one(self, req, parent=None):
        """Dispatch ONE prefill pass for one slot, the next of the
        prompt's ``prefill_plan``: allocate the pages its tokens fill,
        append their K/V, attend causally over the table so far.
        Returns the pass's logits (on the device) where it completes the
        prompt, for ``_prefill_finish``, else None; a pool-exhausted or
        chaos-injected failure fails THIS request only (typed, 429 at
        the HTTP tier). `parent` is the id of the iteration's span."""
        import jax.numpy as jnp

        page = self.model.page_size
        T = int(req.tokens.shape[0])
        t0, n_valid, C = prefill_plan(T, req.prefilled, page, self._mp)[0]
        t0c = self.clock()
        if req.first_chunk_at is None:
            req.first_chunk_at = t0c
        req.chunks += 1
        try:
            # all of the pass's pages or none
            pages = self.cache.alloc(self.cache.pages_for(n_valid))
            req.pages.extend(pages)
            req.block_row[t0 // page:t0 // page + len(pages)] = pages
            chunk = np.zeros((C,), np.int32)
            chunk[:n_valid] = req.tokens[t0:t0 + n_valid]
            # chaos seam INSIDE the failure try: an injected raise
            # fails this prefill like an organic dispatch error
            chunk = _chaos_fault_point("sequence.prefill", chunk)
            logits, *pools = self.model._jit_prefill(
                self.model._params, chunk, jnp.asarray(t0, jnp.int32),
                jnp.asarray(n_valid, jnp.int32), *self.cache.pools,
                req.block_row)
            self.cache.pools = tuple(pools)
        except Exception as e:
            self._fail_active([req], e)
            return None
        finally:
            t1c = self.clock()
            # what each query tile of one page sees: its own page and
            # no later one
            tiles = np.minimum(t0 + n_valid,
                               t0 + page * np.arange(1, C // page + 1))
            self._registry.add_span(
                "sequence.prefill", "serving", t0c, t1c - t0c,
                parent=parent, rid=req.stream_id, model=self.name,
                chunk=n_valid, bucket=C, attend=self._attend,
                pages_visited=self._pages_visited(tiles),
                pages_table=len(tiles) * self._mp)
        req.prefilled += n_valid
        req.seq_len = req.prefilled
        return logits if req.prefilled >= T else None

    def _prefill_finish(self, req, logits, parent=None):
        """The prompt is whole in KV: wait out the pass on the device,
        fetch its last row, register the prompt for prefix sharing,
        sample the first token and, where that was the last, end the
        request. One ``sequence.prefill_finish`` span whose children,
        in that order, are ``sequence.prefill_wait``,
        ``sequence.prefill_copy``, ``sequence.prefix_register`` (prefix
        sharing on), ``sequence.first_token`` and
        ``sequence.request_end`` (``max_new`` 1)."""
        reg, rid = self._registry, req.stream_id
        fid = reg.new_span_id()
        marks = [("sequence.prefill_wait", self.clock())]
        logits.block_until_ready()
        marks.append(("sequence.prefill_copy", self.clock()))
        last = np.asarray(logits)
        if self.prefix_sharing:
            marks.append(("sequence.prefix_register", self.clock()))
            self.cache.register_prefix(req.tokens, req.pages, last)
        marks.append(("sequence.first_token", self.clock()))
        if self._first_token(req, last):
            marks.append(("sequence.request_end", self.clock()))
            self._finish_req(req)
        marks.append((None, self.clock()))
        for (name, t), (_, t1) in zip(marks, marks[1:]):
            reg.add_span(name, "serving", t, t1 - t, parent=fid, rid=rid)
        t0, t_end = marks[0][1], marks[-1][1]
        reg.add_span("sequence.prefill_finish", "serving", t0, t_end - t0,
                     parent=parent, rid=rid, span_id=fid)

    def _pages_visited(self, lengths):
        """Pages one step's attention reads for live slots of KV
        `lengths` (an int or an array), by the kernels' own rule."""
        return paged_pages_visited(self._attend, lengths,
                                   self.model.page_size, self._mp)

    def _new_staging(self, S):
        """Decode staging of one bucket: tokens, seq lens, block
        tables, and where each slot's token comes from (``src``)."""
        return (np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                np.zeros((S, self._mp), np.int32),
                np.full((S,), -1, np.int32))

    def _no_ids(self, S):
        """``prev_ids`` of a step whose tokens all come from the host:
        zeros on the device, one array a bucket, so that such a step
        has the signature of one queued on another step's ids."""
        ids = self._zero_ids.get(S)
        if ids is None:
            import jax.numpy as jnp

            ids = self._zero_ids[S] = jnp.zeros((S,), jnp.int32)
        return ids

    def _members(self, batch, queued):
        """The slots of the next decode step, in `batch` order: every
        request whose first token is sampled and that still owes a
        token once `queued` (a step not yet collected, or None) has
        given it its own. No row is computed that nobody wants."""
        owed = set() if queued is None else set(queued.reqs)
        return [r for r in batch
                if not r.done and r.out_tokens
                and len(r.out_tokens) + (r in owed) < r.max_new]

    def _dispatch(self, members, queued, parent):
        """Dispatch one slot-batched decode step over `members` and
        return it (``_Step``), or None where none went out. Each slot's
        page is prepared first (a fresh page at a page boundary, the
        copy-on-write fork of a shared one; a pool-exhausted slot fails
        alone), and its ``seq_len`` counts the row the step writes. A
        slot of `queued` takes its token from that step's ids on the
        device, any other its last sampled token from the host; a step
        queued on another's ids runs at that step's bucket, the shape
        of the ids. `parent` is the id of the iteration's span."""
        reg = self._registry
        t_prep = self.clock()
        ready = []
        for req in members:
            try:
                idx = req.seq_len // self.model.page_size
                if req.seq_len % self.model.page_size == 0 \
                        and req.block_row[idx] == 0:
                    pg = self.cache.alloc(1)[0]
                    req.pages.append(pg)
                    req.block_row[idx] = pg
                else:
                    old = int(req.block_row[idx])
                    pg = self.cache.ensure_private(old)
                    if pg != old:
                        req.block_row[idx] = pg
                        req.pages = [pg if p == old else p
                                     for p in req.pages]
                ready.append(req)
            except Exception as e:
                self._fail_active([req], e)
        if not ready:
            return None
        n = len(ready)
        S = self.bucket_for(n) if queued is None else queued.S
        self._half ^= 1
        tok, sls, bts, src = self._staging_for(S, self._half)
        at = {} if queued is None else \
            {r: i for i, r in enumerate(queued.reqs)}
        for i, req in enumerate(ready):
            src[i] = at.get(req, -1)
            tok[i] = req.out_tokens[-1] if src[i] < 0 else 0
            sls[i] = req.seq_len
            bts[i] = req.block_row
        tok[n:] = 0
        sls[n:] = 0
        bts[n:] = 0
        src[n:] = -1
        prev = self._no_ids(S) if queued is None else queued.ids
        picked = sum(req.device_pick for req in ready)
        want = [i for i, req in enumerate(ready)
                if req.want_logits or not req.device_pick]
        t0c = self.clock()
        reg.add_span("sequence.decode_prep", "serving", t_prep,
                     t0c - t_prep, parent=parent, slots=n)
        self._m["dispatches"].inc()
        self._m["slot_steps"].inc(n)
        self._m["occupancy"].observe(n / S)
        self.occupancy.append((n, S))
        # the span's id is drawn at the dispatch: spans ordered by (ts,
        # id) are in dispatch order, whenever each was recorded
        sid = reg.new_span_id()
        span = {"parent": parent, "model": self.name, "slots": n,
                "bucket": S, "device_picked": picked,
                "ahead": int(np.any(src[:n] >= 0)), "attend": self._attend,
                "pages_visited": self._pages_visited(sls[:n] + 1),
                "pages_table": n * self._mp,
                "kv_tokens": int(np.sum(sls[:n] + 1))}
        try:
            tok = _chaos_fault_point("sequence.step", tok)
            out, *pools = self.model._jit_decode(
                self.model._params, tok, *self.cache.pools, bts, sls, prev,
                src)
            self.cache.pools = tuple(pools)
            ids, rows, row_of = out[0], None, None
            if len(want) > ROWS_GATHERED or (want and S <= ROWS_GATHERED):
                rows = out[1]
            elif want:
                idx = np.zeros((ROWS_GATHERED,), np.int32)
                idx[:len(want)] = want
                rows = self._take_rows(out[1], idx)
                row_of = {i: j for j, i in enumerate(want)}
            counts = out[2] if len(out) > 2 else None
            # the copies start as the step ends: they land while the
            # step after runs
            for a in (ids, rows, counts):
                if a is not None:
                    a.copy_to_host_async()
        except Exception as e:
            self._record_step((t0c, self.clock() - t0c, sid, span))
            self._fail_active(ready, e)
            return None
        dur = self.clock() - t0c
        for req in ready:
            req.seq_len += 1
        return _Step(ids, rows, row_of, counts, ready, S,
                     (t0c, dur, sid, span))

    def _record_step(self, span, counts=None):
        """Record a step's ``sequence.step`` span (``_Step.span``), with
        what its expert counts [layers, E] say where they were read."""
        t0, dur, sid, args = span
        if counts is not None:
            args = dict(args, experts_touched=int(np.count_nonzero(counts)),
                        experts_total=int(counts.size),
                        expert_tokens_max=int(counts.max()))
        self._registry.add_span("sequence.step", "serving", t0, dur,
                                span_id=sid, **args)

    def _collect(self, step, parent):
        """Wait for a dispatched step's ids and rows, write each live
        slot's row into its request's block, append its token (the
        device's pick, or its sampler's draw on the row) and end the
        requests whose last token it was; a request that expired or
        failed since the dispatch gets nothing. Returns the step's
        slots. `parent` is the id of the iteration's span."""
        reg = self._registry
        t_f = self.clock()
        try:
            # the ids wait out the step (``sequence.fetch_wait``); the
            # copies, queued at the dispatch, are the rest of the fetch
            step.ids.block_until_ready()
            t_w = self.clock()
            ids, rows, counts = (None if a is None else np.asarray(a)
                                 for a in (step.ids, step.rows,
                                           step.counts))
        except Exception as e:
            self._record_step(step.span)
            self._fail_active([r for r in step.reqs if not r.done], e)
            return len(step.reqs)
        t_l = self.clock()
        self._record_step(step.span, counts)
        fid = reg.new_span_id()
        reg.add_span("sequence.fetch_wait", "serving", t_f, t_w - t_f,
                     parent=fid)
        reg.add_span("sequence.fetch", "serving", t_f, t_l - t_f,
                     parent=parent, span_id=fid, bytes=sum(
                         a.nbytes for a in (ids, rows, counts)
                         if a is not None))
        live = [(i, req) for i, req in enumerate(step.reqs)
                if not req.done]
        if step.row_of is not None:         # rows gathered on the device
            rows = {i: rows[j] for i, j in step.row_of.items()}
        landed = [(i, req) for i, req in live if req.want_logits]
        for i, req in landed:
            req.put_row(len(req.out_tokens), rows[i])
        t_s = self.clock()
        if landed:
            reg.add_span("sequence.land", "serving", t_l, t_s - t_l,
                         parent=parent, rows=len(landed),
                         bytes=len(landed) * rows[landed[0][0]].nbytes)
        finished = []
        for i, req in live:
            if req.device_pick:
                token = ids[i]
            else:
                token = req.sampler(rows[i], req.rng)
            req.out_tokens.append(int(token))
            req.token_times.append(self.clock())
            if len(req.out_tokens) >= req.max_new:
                finished.append(req)
        for req in finished:
            self._finish_req(req)
        reg.add_span("sequence.sample", "serving", t_s,
                     self.clock() - t_s, parent=parent,
                     slots=len(step.reqs), finished=len(finished))
        return len(step.reqs)

    def _decode(self, batch, parent):
        """The iteration's decode: the step queued by the iteration
        before, or one dispatched now on the host's tokens, is
        collected; before that, where every slot of the step after
        takes the device's pick and it fits the same bucket, the step
        after is queued on the collected step's ids (class docstring).
        Returns the collected step's slots (0: none)."""
        step, self._ahead = self._ahead, None
        if step is None:
            members = self._members(batch, None)
            step = self._dispatch(members, None, parent) if members \
                else None
            if step is None:
                return 0
        nxt = self._members(batch, step)
        if nxt and all(r.device_pick for r in nxt) \
                and self.bucket_for(len(nxt)) == step.S:
            ahead = self._dispatch(nxt, step, parent)
        else:
            ahead = None
        slots = self._collect(step, parent)
        self._ahead = ahead
        return slots

    def _settle(self):
        """Wait out a queued step whose requests have all ended since
        (a drain's last poll, ``close``), before the pools it writes
        can be let go."""
        step, self._ahead = self._ahead, None
        if step is not None:
            step.ids.block_until_ready()
            self._record_step(step.span, None if step.counts is None
                              else np.asarray(step.counts))

    def _iterate_locked(self):
        """One iteration: expire -> refill (prefix adoption) -> at most
        ONE prefill pass -> the decode (``_decode``). Returns the
        progress count (0 = idle). An iteration that found anything to
        do is one ``sequence.iteration`` span, its parts the children
        (class docstring)."""
        reg = self._registry
        it_id = reg.new_span_id()
        t_it = self.clock()
        with self._cond:
            now = self.clock()
            expired = self._expire_locked(now)
            admitted, adopted = self._refill_locked(now)
        progress = 0
        for req, logits in adopted:       # exact-prefix admissions
            if self._first_token(req, logits):
                self._finish_req(req)
            progress += 1
        with self._cond:
            batch = list(self._active)
            pending = len(self._pending)
        if not (batch or admitted or expired):
            self._settle()
            return progress               # an empty poll: no span
        reg.add_span("sequence.admit", "serving", t_it,
                     self.clock() - t_it, parent=it_id,
                     admitted=admitted, adopted=len(adopted))
        pre = next((r for r in batch
                    if not r.done and r.prefilled < r.tokens.shape[0]),
                   None)
        last = None
        if pre is not None:
            last = self._prefill_one(pre, it_id)
            progress += 1
            if last is not None and self._ahead is None:
                # no step queued: the slot joins this iteration's
                self._prefill_finish(pre, last, it_id)
                last = None
        slots = self._decode(batch, it_id)
        if last is not None:
            # behind the step queued ahead: the slot joins the next
            self._prefill_finish(pre, last, it_id)
        reg.add_span("sequence.iteration", "serving", t_it,
                     self.clock() - t_it, span_id=it_id,
                     active=len(batch), pending=pending,
                     pages_in_use=self.cache.take_pages_peak(),
                     prefill=int(pre is not None), decode_slots=slots)
        return progress + slots

    @telemetry.phase("warm")
    def warm(self, cache=None):
        """Precompile the decode executable for EVERY slot bucket plus
        the (bucket-independent) prefill executable of every chunk
        length ``prefill_plan`` can return, so a serving process
        generates its first token hot. Returns {bucket: {...},
        "prefill": {...} (one page), "prefill<n>": {...} (n pages)} for
        fresh compiles. Signatures mirror the live dispatch EXACTLY
        (host-numpy staging arrays, the live pool handles and the ids
        on the device)."""
        import jax.numpy as jnp

        import jax

        report = {}
        for S in self.slot_buckets:
            tok, sls, bts, src = self._new_staging(S)
            _note_warm(report, int(S), self.model._jit_decode.warm(
                self.model._params, tok, *self.cache.pools, bts, sls,
                self._no_ids(S), src, cache=cache))
            if S > ROWS_GATHERED:
                self._take_rows.warm(
                    jax.ShapeDtypeStruct((S, self.vocab), np.float32),
                    np.zeros((ROWS_GATHERED,), np.int32), cache=cache)
        bt = np.zeros((self._mp,), np.int32)
        for n in PREFILL_CHUNK_PAGES:
            if n > self._mp:
                break
            chunk = np.zeros((n * self.model.page_size,), np.int32)
            _note_warm(
                report, "prefill" if n == 1 else f"prefill{n}",
                self.model._jit_prefill.warm(
                    self.model._params, chunk, jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32), *self.cache.pools, bt,
                    cache=cache))
        return report

    def close(self, drain=True):
        """Stop accepting. drain=True serves everything queued or
        mid-flight to completion; drain=False fails them with
        ServingClosedError and frees their pages. A step still queued
        is waited out; then the pool's prefix registry and series
        go."""
        super().close(drain)
        self._settle()
        self.cache.close()
        return self
