"""The slot scheduler's contract, once, over both kinds of slot
(serving/sequence.py: ``_SlotScheduler`` under ``SequenceScheduler``
and ``PagedSequenceScheduler``).

Everything here is written once in the base class, so every case runs
on a carry scheduler over test_sequence_serving's recurrent net and on a
paged one over test_paged_serving's tiny LM: the queue's bound, the
closed scheduler, deadlines queued and mid-flight, both ways to close,
the escape hatch that fails everything, the series an instance leaves
and takes away, a raise at the ``sequence.step`` seam, the loop that
outlives a scheduler bug, buckets, refills and the counts callers read.
Under ``ManualClock`` with no thread unless a case says so. What a slot
holds is checked where it matters: a paged slot's pages are back in the
pool whenever its request has ended.
"""

import pytest

from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.chaos import ChaosError, ChaosPlan
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, ManualClock, PagedSequenceScheduler,
    QueueFullError, SequenceScheduler, ServingClosedError,
)
from deeplearning4j_tpu.serving.sequence import _SEQ_FAMILIES

from test_paged_serving import _lm, _prompts
from test_sequence_serving import _rnn_net, _seqs

#: a request that outlives every case's polls, and one that does not
LONG, SHORT = 30, 2


class Kind:
    """One kind of slot: how to build its scheduler, how to submit
    `work` units to it (steps of a sequence; tokens of a generation
    behind a four-token prompt) and whether its slots' state is all
    given back."""

    def __init__(self, name, model):
        self.name, self.model = name, model

    def make(self, start_thread=False, **kw):
        kw.setdefault("slot_buckets", (2,))
        if not start_thread:
            kw.setdefault("clock", ManualClock())
        if self.name == "paged":
            kw.setdefault("num_pages", 48)
            kw.setdefault("prefix_sharing", False)
            return PagedSequenceScheduler(
                self.model, start_thread=start_thread, **kw)
        return SequenceScheduler(self.model, start_thread=start_thread,
                                 **kw)

    def submit(self, s, work, seed=0, wait=False, **kw):
        if self.name == "paged":
            prompt = _prompts((4,), self.model.vocab, seed=seed)[0]
            return s.submit(prompt, max_new_tokens=work, wait=wait, **kw)
        return s.submit(_seqs([work], seed=seed)[0], wait=wait, **kw)

    def whole(self, s):
        return self.name != "paged" or s.cache.pages_in_use == 0


@pytest.fixture(scope="module")
def models():
    return {"carry": _rnn_net(), "paged": _lm()}


@pytest.fixture(params=["carry", "paged"])
def kind(request, models):
    return Kind(request.param, models[request.param])


def _series(name):
    """This instance's child of each dl4j_seq_* family, or None."""
    reg = telemetry.get_registry()
    return {fam: reg.get(fam).labels_get(model=name)
            for _, fam, _ in _SEQ_FAMILIES.values()}


# ----------------------------------------------------------------------
# the queue
# ----------------------------------------------------------------------

def test_constructor_validates_queue_and_buckets(kind):
    with pytest.raises(ValueError, match="queue_limit"):
        kind.make(queue_limit=0)
    with pytest.raises(ValueError, match="slot buckets"):
        kind.make(slot_buckets=(0, 2))


def test_queue_full_raises_and_counts_rejected(kind):
    s = kind.make(queue_limit=2)
    kind.submit(s, SHORT)
    kind.submit(s, SHORT, seed=1)
    with pytest.raises(QueueFullError, match="queueLimit=2"):
        kind.submit(s, SHORT, seed=2)
    assert s.stats["rejected"] == 1 and s.stats["sequences"] == 2
    assert s.depth == 2
    s.drain()                       # the bound is on WAITING requests
    kind.submit(s, SHORT, seed=2)
    s.close()


def test_submit_after_close_raises(kind):
    s = kind.make()
    s.close()
    with pytest.raises(ServingClosedError, match="closed"):
        kind.submit(s, SHORT)


def test_depth_and_active_slots_follow_queue_and_table(kind):
    s = kind.make()
    reqs = [kind.submit(s, LONG, seed=i) for i in range(3)]
    assert (s.depth, s.active_slots) == (3, 0)
    s.poll()
    assert (s.depth, s.active_slots) == (1, 2)
    assert [r.started_at is not None for r in reqs] == [True, True, False]
    s.close(drain=False)
    assert (s.depth, s.active_slots) == (0, 0)


def test_caller_timeout_releases_the_waiter_not_the_request(kind):
    s = kind.make()
    req = kind.submit(s, SHORT)
    with pytest.raises(DeadlineExceededError, match="no result within"):
        req.wait(0.0)
    assert not req.done             # the scheduler still owns it
    s.drain()
    assert req.wait(0.0) is not None and req.error is None
    s.close()


# ----------------------------------------------------------------------
# deadlines
# ----------------------------------------------------------------------

def test_queued_deadline_expires_without_a_slot(kind):
    s = kind.make(slot_buckets=(1,))
    clk = s.clock
    hog = kind.submit(s, 4)
    doomed = kind.submit(s, SHORT, seed=1, deadline=clk() + 0.5)
    s.poll()
    clk.advance(1.0)
    s.drain()
    assert hog.done and hog.error is None
    assert isinstance(doomed.error, DeadlineExceededError)
    assert "before a slot" in str(doomed.error)
    assert doomed.started_at is None
    st = s.stats
    assert st["expired"] == 1 and st["completed"] == 1
    # the doomed request never cost a dispatch: the hog's steps only
    assert st["slot_steps"] == sum(n for n, _ in s.occupancy)
    assert all(n == 1 for n, _ in s.occupancy)
    s.close()


def test_midflight_deadline_frees_the_slot_at_the_next_boundary(kind):
    s = kind.make(slot_buckets=(1,))
    clk = s.clock
    doomed = kind.submit(s, LONG, deadline=clk() + 0.5)
    queued = kind.submit(s, SHORT, seed=1)
    assert s.poll() >= 1            # doomed holds the only slot
    assert doomed.started_at is not None and not doomed.done
    assert queued.started_at is None
    if kind.name == "paged":        # what the expiry has to give back
        assert s.cache.pages_in_use > 0
    clk.advance(1.0)                # the deadline passes MID-FLIGHT
    assert s.poll() >= 1            # expiry freed the slot; queued was
    #                                 admitted the SAME tick
    assert isinstance(doomed.error, DeadlineExceededError)
    assert "mid-sequence" in str(doomed.error)
    assert queued.started_at is not None
    s.drain()
    assert queued.done and queued.error is None
    st = s.stats
    assert st["expired"] == 1 and st["completed"] == 1
    assert kind.whole(s)
    s.close()


# ----------------------------------------------------------------------
# the two ways to close, and the escape hatch
# ----------------------------------------------------------------------

def test_close_without_drain_fails_queued_and_active(kind):
    s = kind.make(slot_buckets=(1,))
    active = kind.submit(s, LONG)
    queued = kind.submit(s, LONG, seed=1)
    s.poll()
    s.close(drain=False)
    with pytest.raises(ServingClosedError, match="mid-sequence"):
        active.wait(0.0)
    with pytest.raises(ServingClosedError, match="before a slot"):
        queued.wait(0.0)
    assert kind.whole(s)


def test_close_with_drain_finishes_queued_and_active(kind):
    s = kind.make(slot_buckets=(1,))
    active = kind.submit(s, 4)
    queued = kind.submit(s, 3, seed=1)
    s.poll()
    s.close()                       # drain=True is the default
    for req, work in ((active, 4), (queued, 3)):
        assert req.error is None and len(req.wait(0.0)) == work
    assert kind.whole(s)
    with pytest.raises(ServingClosedError):
        kind.submit(s, SHORT)


def test_fail_all_releases_every_waiter_and_zeroes_the_gauges(kind):
    s = kind.make(slot_buckets=(2,))
    reqs = [kind.submit(s, LONG, seed=i) for i in range(4)]
    s.poll()
    series = _series(s.name)
    assert series["dl4j_seq_queue_depth"].value == 2
    assert series["dl4j_seq_active_slots"].value == 2
    boom = RuntimeError("scheduler bug")
    s._fail_all(boom)
    for req in reqs:
        assert req.done and req.error is boom
    assert (s.depth, s.active_slots) == (0, 0)
    assert series["dl4j_seq_queue_depth"].value == 0
    assert series["dl4j_seq_active_slots"].value == 0
    assert s.stats["errors"] == 4
    assert kind.whole(s)
    assert s.poll() == 0            # nothing left, and still usable
    again = kind.submit(s, SHORT)
    s.drain()
    assert again.error is None
    s.close()


def test_close_removes_the_instances_series(kind):
    s = kind.make(name=f"contract-{kind.name}-series")
    kind.submit(s, SHORT)
    s.drain()
    assert all(child is not None for child in _series(s.name).values())
    assert len(_series(s.name)) == 12
    s.close()
    assert all(child is None for child in _series(s.name).values())


# ----------------------------------------------------------------------
# failures inside an iteration
# ----------------------------------------------------------------------

def test_raise_at_the_step_seam_fails_live_slots_and_nothing_queued(kind):
    s = kind.make(slot_buckets=(2,))
    reqs = [kind.submit(s, LONG, seed=i) for i in range(3)]
    with ChaosPlan().raise_n("sequence.step", times=1) as plan:
        while not plan.fired("sequence.step"):
            s.poll()
    live = [r for r in reqs if r.error is not None]
    assert live and reqs[2] not in live
    for req in live:
        assert isinstance(req.error, ChaosError)
    assert s.stats["errors"] == len(live)
    assert not reqs[2].done         # queued then: untouched, served on
    s.poll()
    assert reqs[2].started_at is not None
    s.close(drain=False)
    assert kind.whole(s)


def test_loop_outlives_an_exception_out_of_an_iteration(kind):
    """With the thread: a scheduler bug releases every waiter with the
    error and the loop stays up for the next submit."""
    s = kind.make(start_thread=True)
    try:
        def bug():
            raise RuntimeError("scheduler bug")

        s._iterate_locked = bug     # shadows the class's method
        doomed = kind.submit(s, SHORT)
        with pytest.raises(RuntimeError, match="scheduler bug"):
            doomed.wait(30.0)
        assert s._thread.is_alive()
        del s._iterate_locked
        out = kind.submit(s, 3, seed=1, wait=True, timeout=60.0)
        assert len(out) == 3
        assert s.stats["errors"] == 1 and s.stats["completed"] == 1
    finally:
        s.close(drain=False)
    assert s._thread is None


# ----------------------------------------------------------------------
# buckets, refills, counts
# ----------------------------------------------------------------------

def test_bucket_for_is_the_smallest_that_fits(kind):
    s = kind.make(slot_buckets=(4, 1, 2))
    assert s.slot_buckets == (1, 2, 4) and s.max_slots == 4
    assert [s.bucket_for(n) for n in (1, 2, 3, 4, 9)] == [1, 2, 4, 4, 4]
    s.close()


def test_freed_slot_is_refilled_mid_sequence_and_counted(kind):
    s = kind.make(slot_buckets=(2,))
    stays = kind.submit(s, LONG)
    leaves = kind.submit(s, 1, seed=1)
    third = kind.submit(s, SHORT, seed=2)
    while not leaves.done:
        s.poll()
    assert s.stats["refills"] == 0 and third.started_at is None
    s.poll()                        # the freed slot goes to `third`
    assert third.started_at is not None and not stays.done
    assert s.stats["refills"] == 1
    s.close(drain=False)


def test_counts_agree_with_the_occupancy_record(kind):
    s = kind.make(slot_buckets=(2, 4))
    reqs = [kind.submit(s, work, seed=i)
            for i, work in enumerate((5, 2, 7, 3))]
    s.drain()
    assert all(r.error is None for r in reqs)
    st = s.stats
    assert st["sequences"] == st["completed"] == 4
    assert st["dispatches"] == len(s.occupancy)
    assert st["slot_steps"] == sum(n for n, _ in s.occupancy)
    assert {b for _, b in s.occupancy} <= {2, 4}
    assert all(n <= b for n, b in s.occupancy)
    summary = s.occupancy_summary()
    assert summary["dispatches"] == len(s.occupancy)
    assert 0 < summary["mean_occupancy"] <= 1
    # one staging set a bucket (and half), the same object each time
    assert s._staging and all(s._staging_for(*k) is st
                              for k, st in list(s._staging.items()))
    assert kind.whole(s)
    s.close()
