"""Spans with a cause and a request (runtime/telemetry.py, ISSUE 26).

What must hold:

- nested ``span()`` blocks set ``parent`` by themselves, one stack per
  thread; ``add_span(parent=, rid=, span_id=)`` takes them explicitly;
  the ring counts what it drops and ``clear()`` forgets the count;
- a paged scheduler leaves, for every iteration that found work, the
  tree of docs/OBSERVABILITY.md, and for every request that ends, done
  or failed, one instant ``sequence.request`` with its timeline; the
  request's timestamps are set with telemetry off too;
- a prompt's last pass splits its ``sequence.prefill_finish`` into its
  parts, a decode collect its ``sequence.fetch`` into the wait and the
  copies, a waiter records its wake-up, and a ``CachedJit`` call served
  from its table its signature and its executable's call;
- an idle scheduler loop is ONE ``sequence.idle`` per idle period;
- both schedulers default to the registry's clock;
- ``fit(iterator)`` records one of each trainer span a step, children
  inside parents, and compiles nothing more for it;
- ``telemetry.phase`` feeds ``dl4j_setup_seconds{phase}``, which
  survives ``trace.clear()`` and ``host.close()``.
"""

import glob
import math
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.transformer import (PREFILL_CHUNK_PAGES,
                                               CausalTransformerLM,
                                               prefill_plan)
from deeplearning4j_tpu.runtime import aot, telemetry
from deeplearning4j_tpu.runtime.telemetry import MetricsRegistry
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, KVCacheFullError, ManualClock, ModelHost,
    PagedSequenceScheduler, SequenceScheduler, ServingClosedError,
)


class TickClock(ManualClock):
    """Every read moves the clock on by one tick, so that intervals taken
    from it nest strictly and repeat exactly."""

    def __call__(self):
        self.now += 0.001
        return self.now


def _lm(**kw):
    return CausalTransformerLM(vocab=23, d_model=32, n_heads=2, n_layers=2,
                               max_context=64, page_size=8, seed=3, **kw)


def _prompt(n, vocab=23, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _paged(model, clock=None, **kw):
    kw.setdefault("num_pages", 48)
    kw.setdefault("slot_buckets", (2,))
    kw.setdefault("prefix_sharing", False)
    kw.setdefault("start_thread", False)
    return PagedSequenceScheduler(model, clock=clock or TickClock(), **kw)


@pytest.fixture
def ring():
    """The process-wide ring, emptied before and after."""
    trace = telemetry.get_registry().trace
    trace.clear()
    yield trace
    trace.clear()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


# ----------------------------------------------------------------------
# what a span records
# ----------------------------------------------------------------------
class TestSpanRecord:
    def test_nested_span_sets_parent(self):
        reg = MetricsRegistry(clock=TickClock())
        with reg.span("outer", "c"):
            with reg.span("inner", "c", rid=7, k=1):
                assert reg.current_span_id() is not None
            with reg.span("second", "c"):
                pass
        assert reg.current_span_id() is None
        by = {s["name"]: s for s in reg.trace.spans()}
        assert by["outer"]["parent"] is None
        assert by["inner"]["parent"] == by["outer"]["id"]
        assert by["second"]["parent"] == by["outer"]["id"]
        assert by["inner"]["rid"] == 7 and by["inner"]["args"] == {"k": 1}
        assert len({s["id"] for s in by.values()}) == 3
        assert _inside(by["inner"], by["outer"])

    def test_stack_unwinds_on_error(self):
        reg = MetricsRegistry()
        with pytest.raises(KeyError):
            with reg.span("outer"):
                with reg.span("inner"):
                    raise KeyError("x")
        assert reg.current_span_id() is None
        assert [s["name"] for s in reg.trace.spans()] == ["inner", "outer"]

    def test_threads_keep_separate_stacks(self):
        reg = MetricsRegistry()
        inside, release = threading.Event(), threading.Event()

        def other():
            with reg.span("other-thread"):
                inside.set()
                release.wait(5)

        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(5)
        with reg.span("main-thread"):
            pass                    # opened while the other is open
        release.set()
        t.join(5)
        assert not t.is_alive()
        by = {s["name"]: s for s in reg.trace.spans()}
        assert by["main-thread"]["parent"] is None
        assert by["other-thread"]["parent"] is None
        assert by["main-thread"]["tid"] != by["other-thread"]["tid"]

    def test_add_span_takes_parent_rid_and_a_drawn_id(self):
        reg = MetricsRegistry()
        pid = reg.new_span_id()
        kid = reg.add_span("child", "c", 1.0, 0.5, parent=pid, rid=3, n=2)
        got = reg.add_span("parent", "c", 0.5, 2.0, span_id=pid)
        assert got == pid and kid != pid
        child, parent = reg.trace.spans()
        assert (child["parent"], child["rid"], child["args"]) == \
            (pid, 3, {"n": 2})
        assert (parent["id"], parent["parent"], parent["rid"]) == \
            (pid, None, None)

    def test_event_carries_rid_and_its_own_time(self):
        reg = MetricsRegistry(clock=ManualClock(5.0))
        reg.event("e", "c", ts=2.5, rid=9, why="x")
        reg.event("now", "c")
        a, b = reg.trace.spans()
        assert (a["ph"], a["ts"], a["rid"], a["args"]) == \
            ("i", 2.5, 9, {"why": "x"})
        assert b["ts"] == 5.0 and b["rid"] is None

    def test_ring_counts_what_it_drops_and_clear_forgets(self):
        reg = MetricsRegistry(trace_capacity=4)
        for k in range(10):
            reg.add_span(f"s{k}", "c", float(k), 1.0)
        assert reg.trace.dropped == 6
        assert [s["name"] for s in reg.trace.spans()] == \
            ["s6", "s7", "s8", "s9"]
        reg.trace.clear()
        assert reg.trace.dropped == 0 and reg.trace.spans() == []
        assert MetricsRegistry().trace.capacity == 131072

    def test_chrome_trace_carries_the_links(self):
        reg = MetricsRegistry()
        with reg.span("outer", rid=4):
            with reg.span("inner"):
                pass
        inner, outer = reg.chrome_trace()["traceEvents"]
        assert inner["parent"] == outer["id"] and outer["rid"] == 4
        assert "parent" not in outer and "rid" not in inner

    def test_disabled_records_nothing(self):
        reg = MetricsRegistry()
        telemetry.set_enabled(False)
        try:
            with reg.span("a"):
                assert reg.current_span_id() is None
            assert reg.add_span("b", "c", 0.0, 1.0) is None
            reg.event("c")
        finally:
            telemetry.set_enabled(True)
        assert reg.trace.spans() == []


# ----------------------------------------------------------------------
# the paged scheduler's tree and the request's timeline
# ----------------------------------------------------------------------
class TestPagedSchedulerSpans:
    def test_one_prompt_two_passes_four_tokens(self, ring):
        """44 prompt tokens at a page of 8 are six pages, which the plan
        takes in two passes of three; the second iteration finishes the
        prompt and decodes, two more decode. The request is greedy, so
        the second iteration dispatches a step on the host's token and
        the next one ahead of it on the device's id before it collects
        the first; the third queues the fourth token's step before it
        collects the third's, and the last collects without queueing:
        its request ends there. Every collect waits for ids and rows
        and lands the rows itself."""
        s = _paged(_lm())
        assert prefill_plan(44, 0, 8, s._mp) == [(0, 24, 24), (24, 20, 24)]
        req = s.submit(_prompt(44), max_new_tokens=4, wait=False)
        s.drain()
        assert req.wait(1.0).shape == (4,)
        spans = ring.spans()
        by = _by_name(spans)
        its = by["sequence.iteration"]
        assert len(its) == 4
        assert all(i["parent"] is None and i["rid"] is None for i in its)
        assert [i["args"]["prefill"] for i in its] == [1, 1, 0, 0]
        assert [i["args"]["decode_slots"] for i in its] == [0, 1, 1, 1]
        assert [i["args"]["pages_in_use"] for i in its] == [3, 6, 6, 6]
        assert all(i["args"]["active"] == 1 and i["args"]["pending"] == 0
                   for i in its)
        kids = {}
        for sp in spans:
            if sp["parent"] is not None:
                kids.setdefault(sp["parent"], []).append(sp)
        tree = [[k["name"] for k in sorted(kids[i["id"]],
                                           key=lambda k: k["ts"])]
                for i in its]
        step = ["sequence.decode_prep", "sequence.step"]
        collect = ["sequence.fetch", "sequence.land", "sequence.sample"]
        assert tree == [
            ["sequence.admit", "sequence.prefill"],
            ["sequence.admit", "sequence.prefill",
             "sequence.prefill_finish"] + step + step + collect,
            ["sequence.admit"] + step + collect,
            ["sequence.admit"] + collect]
        for i in its:
            assert all(_inside(k, i) for k in kids[i["id"]])
        ids, row = 2 * 4, 23 * 4    # a bucket's int32 ids; a float32 row
        steps = by["sequence.step"]
        for step, ahead in zip(steps, (0, 1, 1)):
            assert "sequence.step" not in {k["name"] for k in
                                           kids.get(step["id"], ())}
            # the accepted readers' args, as before, whose token the
            # slots took, whether it was the step before's id on the
            # device, and what the attention read: the CPU takes
            # paged_attend, whole tables; and the rows it attends, the
            # 44 of the prompt and one a token so far
            assert step["args"] == {
                "model": s.name, "slots": 1, "bucket": 2,
                "device_picked": 1, "ahead": ahead, "attend": "reference",
                "pages_visited": s._mp, "pages_table": s._mp,
                "kv_tokens": 45 + steps.index(step)}
        # a collect waits for a bucket's ids and rows, behind the
        # dispatch of the step queued after it where there is one
        fetches = by["sequence.fetch"]
        assert [f["args"] for f in fetches] == [{"bytes": ids + 2 * row}] * 3
        assert [f["parent"] for f in fetches] == [i["id"] for i in its[1:]]
        for f, step in zip(fetches, steps[1:]):
            assert f["ts"] >= step["ts"] + step["dur"]
        lands = by["sequence.land"]
        assert [x["args"] for x in lands] == [{"rows": 1, "bytes": row}] * 3
        assert [x["parent"] for x in lands] == [i["id"] for i in its[1:]]
        for x, f in zip(lands, fetches):
            assert x["ts"] >= f["ts"] + f["dur"]
        assert req.logits.shape == (4, 23) and s._ahead is None
        # a pass: its tokens, the chunk it ran in, and a whole table
        # for each of the chunk's query tiles of one page
        assert [p["args"] for p in by["sequence.prefill"]] == [
            {"model": s.name, "chunk": n_valid, "bucket": C,
             "attend": "reference", "pages_visited": C // 8 * s._mp,
             "pages_table": C // 8 * s._mp}
            for n_valid, C in ((24, 24), (20, 24))]
        assert by["sequence.admit"][0]["args"] == {"admitted": 1,
                                                   "adopted": 0}
        assert [x["args"]["finished"] for x in by["sequence.sample"]] == \
            [0, 0, 1]
        # one rid on every span that belongs to the request
        mine = [sp for sp in spans if sp["rid"] is not None]
        assert {sp["rid"] for sp in mine} == {req.stream_id}
        assert sorted(sp["name"] for sp in mine) == sorted(
            ["sequence.prefill"] * 2 + [
                "sequence.prefill_finish", "sequence.prefill_wait",
                "sequence.prefill_copy", "sequence.first_token",
                "sequence.request", "sequence.wake"])
        s.close()

    def test_request_timeline(self, ring):
        s = _paged(_lm())
        req = s.submit(_prompt(44), max_new_tokens=4, wait=False)
        s.drain()
        assert (req.enqueued_at <= req.started_at <= req.first_chunk_at
                < req.first_token_at < req.finished_at)
        assert len(req.token_times) == 4 and req.chunks == 2
        assert req.token_times[0] == req.first_token_at
        assert req.token_times == sorted(req.token_times)
        assert req.token_times[-1] <= req.finished_at
        (ev,) = _by_name(ring.spans())["sequence.request"]
        assert ev["ph"] == "i" and ev["ts"] == req.finished_at
        assert ev["args"] == {
            "prompt_tokens": 44, "new_tokens": 4, "chunks": 2,
            "enqueued_at": req.enqueued_at, "started_at": req.started_at,
            "first_chunk_at": req.first_chunk_at,
            "first_token_at": req.first_token_at,
            "finished_at": req.finished_at,
            "token_times": tuple(req.token_times), "error": None}
        s.close()

    def test_failed_request_still_leaves_its_event(self, ring):
        """The pool runs dry mid-generation: the victim's event names
        the error's class, the survivor's names none."""
        s = _paged(_lm(), num_pages=5)
        a = s.submit(_prompt(4, seed=1), max_new_tokens=14, wait=False)
        b = s.submit(_prompt(4, seed=2), max_new_tokens=14, wait=False)
        s.drain()
        errors = {}
        for r in (a, b):
            try:
                r.wait(1.0)
                errors[r.stream_id] = None
            except KVCacheFullError:
                errors[r.stream_id] = "KVCacheFullError"
        assert sorted(errors.values(), key=str) == ["KVCacheFullError",
                                                    None]
        events = _by_name(ring.spans())["sequence.request"]
        assert {e["rid"]: e["args"]["error"] for e in events} == errors
        victim = next(r for r in (a, b) if errors[r.stream_id])
        assert victim.finished_at is not None
        assert len(victim.token_times) == len(victim.out_tokens) < 14
        s.close()

    def test_expired_and_closed_requests_leave_events(self, ring):
        clk = ManualClock()
        s = _paged(_lm(), clock=clk)
        late = s.submit(_prompt(4), max_new_tokens=2, deadline=1.0,
                        wait=False)
        clk.advance(2.0)
        s.poll()
        with pytest.raises(DeadlineExceededError):
            late.wait(0.1)
        cut = s.submit(_prompt(44), max_new_tokens=2, wait=False)
        s.poll()                            # one pass of two, then closed
        s.close(drain=False)
        with pytest.raises(ServingClosedError):
            cut.wait(0.1)
        events = _by_name(ring.spans())["sequence.request"]
        assert [(e["rid"], e["args"]["error"], e["args"]["chunks"])
                for e in events] == [
            (late.stream_id, "DeadlineExceededError", 0),
            (cut.stream_id, "ServingClosedError", 1)]
        assert late.first_chunk_at is None and late.finished_at == 2.0

    def test_adopted_prompt_has_a_whole_timeline(self, ring):
        """An exact-prefix adoption runs no chunk: first_chunk_at is the
        grant, the first token comes in the admit."""
        s = _paged(_lm(), prefix_sharing=True)
        p = _prompt(16)
        s.submit(p, max_new_tokens=1, wait=False)
        s.drain()
        ring.clear()
        again = s.submit(p, max_new_tokens=1, wait=False)
        s.drain()
        assert again.chunks == 0
        assert again.started_at == again.first_chunk_at \
            < again.first_token_at <= again.finished_at
        by = _by_name(ring.spans())
        assert "sequence.prefill" not in by
        assert by["sequence.admit"][0]["args"] == {"admitted": 1,
                                                   "adopted": 1}
        s.close()

    def test_empty_poll_leaves_no_span(self, ring):
        s = _paged(_lm())
        ring.clear()                        # the set-up phases' spans
        assert s.poll() == 0
        assert ring.spans() == []
        s.close()

    def test_disabled_no_span_but_timestamps_set(self, ring):
        s = _paged(_lm())
        ring.clear()                        # the set-up phases' spans
        telemetry.set_enabled(False)
        try:
            req = s.submit(_prompt(20), max_new_tokens=4, wait=False)
            s.drain()
        finally:
            telemetry.set_enabled(True)
        assert ring.spans() == []
        assert (req.enqueued_at <= req.started_at <= req.first_chunk_at
                < req.first_token_at < req.finished_at)
        assert len(req.token_times) == 4
        s.close()

    def test_pages_peak_is_counted_where_pages_are_allotted(self):
        s = _paged(_lm())
        cache = s.cache
        got = cache.alloc(3)
        cache.release(got[:2])
        assert cache.pages_in_use == 1
        assert cache.take_pages_peak() == 3     # the peak, not the level
        assert cache.take_pages_peak() == 1     # nothing allotted since
        cache.release(got[2:])
        s.close()


# ----------------------------------------------------------------------
# the host's time around a prompt's first token, and a decode's fetch
# ----------------------------------------------------------------------
FINISH_PARTS = ["sequence.prefill_wait", "sequence.prefill_copy",
                "sequence.prefix_register", "sequence.first_token",
                "sequence.request_end"]


class TestFirstTokenSpans:
    @pytest.mark.parametrize("sharing,max_new,n", [
        (True, 1, 20), (False, 1, 20), (True, 3, 20), (False, 3, 44)])
    def test_last_pass_finish_has_its_parts_in_order(self, ring, sharing,
                                                      max_new, n):
        """Only a prompt's last pass (one of one at 20 tokens, the second
        of two at 44) has a finish: its children follow one another from
        its start to its end, under its id and with the request's rid;
        the registry's part only where prefix sharing is on, the
        request's end only where the first token is the last."""
        s = _paged(_lm(), prefix_sharing=sharing)
        req = s.submit(_prompt(n), max_new_tokens=max_new, wait=False)
        s.drain()
        spans = ring.spans()
        by = _by_name(spans)
        assert len(by["sequence.prefill"]) == req.chunks == \
            len(prefill_plan(n, 0, 8, s._mp))
        (fin,) = by["sequence.prefill_finish"]
        (it,) = [i for i in by["sequence.iteration"]
                 if i["id"] == fin["parent"]]
        assert _inside(fin, it) and fin["rid"] == req.stream_id
        kids = sorted((sp for sp in spans if sp["parent"] == fin["id"]),
                      key=lambda sp: sp["ts"])
        assert [k["name"] for k in kids] == [
            n for n in FINISH_PARTS
            if (sharing or n != "sequence.prefix_register")
            and (max_new == 1 or n != "sequence.request_end")]
        assert all(k["rid"] == req.stream_id and k["args"] == {}
                   for k in kids)
        assert kids[0]["ts"] == fin["ts"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-12)
        assert kids[-1]["ts"] + kids[-1]["dur"] == pytest.approx(
            fin["ts"] + fin["dur"], abs=1e-12)
        first = next(k for k in kids if k["name"] == "sequence.first_token")
        assert first["ts"] < req.first_token_at < first["ts"] + first["dur"]
        if max_new == 1:
            assert kids[-1]["ts"] < req.finished_at \
                < kids[-1]["ts"] + kids[-1]["dur"]
        s.close()

    def test_wake_is_recorded_on_the_waiters_thread(self, ring):
        """From ``finished_at`` to the wait's return, on the waiter's
        thread, with the request's rid; a second wait records none."""
        s = _paged(_lm(), clock=telemetry.get_registry().clock)
        req = s.submit(_prompt(12), max_new_tokens=2, wait=False)
        seen = {}

        def waiter():
            seen["tid"] = threading.get_ident()
            seen["out"] = req.wait(5.0)
            seen["at"] = telemetry.get_registry().clock()

        t = threading.Thread(target=waiter)
        t.start()
        s.drain()
        t.join(5.0)
        assert seen["out"].shape == (2,)
        req.wait(1.0)
        (wake,) = _by_name(ring.spans())["sequence.wake"]
        assert wake["tid"] == seen["tid"] != threading.get_ident()
        assert (wake["rid"], wake["parent"]) == (req.stream_id, None)
        assert wake["ts"] == req.finished_at
        assert 0 <= wake["dur"] <= seen["at"] - req.finished_at
        s.close()

    def test_no_wake_for_a_failed_request_or_with_telemetry_off(self, ring):
        clk = ManualClock()
        s = _paged(_lm(), clock=clk)
        late = s.submit(_prompt(4), max_new_tokens=2, deadline=1.0,
                        wait=False)
        clk.advance(2.0)
        s.poll()
        with pytest.raises(DeadlineExceededError):
            late.wait(0.1)
        req = s.submit(_prompt(4), max_new_tokens=2, wait=False)
        s.drain()
        telemetry.set_enabled(False)
        try:
            req.wait(1.0)
        finally:
            telemetry.set_enabled(True)
        req.wait(1.0)                   # not the first return: nothing
        assert "sequence.wake" not in _by_name(ring.spans())
        s.close()

    def test_fetch_wait_opens_each_fetch(self, ring):
        """Each decode collect's wait for the ids is the first part of
        its ``sequence.fetch``; the copies are the rest."""
        s = _paged(_lm())
        s.submit(_prompt(44), max_new_tokens=4, wait=False)
        s.drain()
        by = _by_name(ring.spans())
        fetches, waits = by["sequence.fetch"], by["sequence.fetch_wait"]
        assert len(fetches) == 3
        assert [w["parent"] for w in waits] == [f["id"] for f in fetches]
        for w, f in zip(waits, fetches):
            assert w["ts"] == f["ts"] and w["dur"] < f["dur"]
            assert _inside(w, f) and w["rid"] is None and w["args"] == {}
        s.close()


class TestDispatchSpans:
    @pytest.fixture
    def double(self):
        f = aot.cached_jit(lambda x: x * 2.0, entry="double",
                           fingerprint="span-tree-double")
        return f.pin_cache(aot.ExecutableCache()), jnp.ones((4,), jnp.float32)

    def test_one_sign_and_one_call_per_table_served_call(self, ring,
                                                         double):
        f, x = double
        f(x)                                # first seen: a compile
        by = _by_name(ring.spans())
        assert len(by["aot.compile"]) == 1
        assert "aot.sign" not in by and "aot.call" not in by
        ring.clear()
        f(x)
        f(x)
        by = _by_name(ring.spans())
        assert "aot.compile" not in by
        signs, calls = by["aot.sign"], by["aot.call"]
        assert len(signs) == len(calls) == 2
        for sg, cl in zip(signs, calls):
            assert sg["args"] == cl["args"] == {"entry": "double"}
            assert sg["cat"] == cl["cat"] == "compile"
            assert sg["tid"] == cl["tid"] == threading.get_ident()
            assert sg["parent"] is None and cl["parent"] is None
            assert cl["ts"] == pytest.approx(sg["ts"] + sg["dur"],
                                             abs=1e-12)

    def test_a_call_inside_a_span_block_is_its_child(self, ring, double):
        f, x = double
        f(x)
        reg = telemetry.get_registry()
        ring.clear()
        with reg.span("outer"):
            f(x)
        by = _by_name(ring.spans())
        (outer,) = by["outer"]
        for name in ("aot.sign", "aot.call"):
            (sp,) = by[name]
            assert sp["parent"] == outer["id"] and _inside(sp, outer)

    def test_telemetry_off_records_nothing_and_reads_no_clock(
            self, ring, double, monkeypatch):
        f, x = double
        f(x)
        reg = telemetry.get_registry()
        reads = []
        real = reg.clock
        monkeypatch.setattr(reg, "clock",
                            lambda: reads.append(1) or real())
        ring.clear()
        telemetry.set_enabled(False)
        try:
            out = f(x)
        finally:
            telemetry.set_enabled(True)
        assert np.asarray(out).tolist() == [2.0] * 4
        assert reads == [] and ring.spans() == []


# ----------------------------------------------------------------------
# the program's spans, read by the yardstick's readers
# ----------------------------------------------------------------------
#: every reader under perfbench/metrics/ that takes a span, an event or a
#: series of the paged scheduler (the others read the device trace, the
#: trainers or set-up)
SCHEDULER_READERS = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        os.path.dirname(__file__), os.pardir, "perfbench", "metrics",
        "*.py"))
    if os.path.basename(p).startswith(("seq.", "kv.pages_in_use_max",
                                       "paged_attend.", "aot.prefill_")))


class TestReadersTakeTheProgramsSpans:
    """tests/test_perfbench_readers.py feeds the readers hand-made
    spans; here they get what a served scheduler really left, so that a
    renamed span, argument or family fails in tier-1 and does not cost a
    metric on the chip."""

    @pytest.fixture(scope="class")
    def run(self):
        from test_perfbench_readers import StubRun

        reg = telemetry.get_registry()
        reg.trace.clear()
        # on the registry's clock, which the AOT layer's spans read, so
        # that a pass holds its executable's call; the suite's session
        # cache serves a pass whose chunk length it has seen
        s = _paged(_lm(), name="join", clock=reg.clock)
        t0 = s.clock()
        # two prompts of two passes each, five tokens each: a pass that
        # is not the last, decode steps with one and two live slots
        assert [len(prefill_plan(n, 0, 8, s._mp)) for n in (44, 30)] == \
            [2, 2]
        reqs = [s.submit(_prompt(n, seed=n), max_new_tokens=5, wait=False)
                for n in (44, 30)]
        s.drain()
        assert all(r.wait(1.0).shape == (5,) for r in reqs)

        class Run(StubRun):
            window = {"t0": t0, "t1": s.clock()}
            # as perfbench/kinds/serve_generate.py fills it, before the
            # scheduler closes and takes its series away
            counters = {"queue_wait_p50_s": telemetry.get_registry().get(
                "dl4j_seq_queue_wait_seconds").labels_get(
                    model=s.name).percentile(50)}

        yield Run()
        s.close()
        reg.trace.clear()

    def test_there_is_a_reader_for_each_layer_metric(self):
        assert len(SCHEDULER_READERS) == 24

    @pytest.mark.parametrize("name", SCHEDULER_READERS)
    def test_reader_gives_a_finite_number(self, name, run):
        from perfbench import harness

        value = harness.load_module("metrics", name).read(run)
        assert value is not None and math.isfinite(value), name


# ----------------------------------------------------------------------
# the idle loop and the clock
# ----------------------------------------------------------------------
def _carry_net():
    from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration,
                                       RnnOutputLayer, Sgd)
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(5).updater(Sgd(0.1))
            .list()
            .layer(LSTM(nOut=6, activation="tanh"))
            .layer(RnnOutputLayer(nOut=3, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(4)).build())
    return MultiLayerNetwork(conf).init()


def _make_scheduler(kind, **kw):
    if kind == "paged":
        return PagedSequenceScheduler(_lm(), num_pages=16,
                                      slot_buckets=(2,), **kw)
    return SequenceScheduler(_carry_net(), slot_buckets=(2,), **kw)


class TestIdleAndClock:
    @pytest.mark.parametrize("kind", ["paged", "carry"])
    def test_default_clock_is_the_registrys(self, kind):
        s = _make_scheduler(kind, start_thread=False)
        assert s.clock is telemetry.get_registry().clock
        s.close()
        clk = ManualClock()
        s = _make_scheduler(kind, start_thread=False, clock=clk)
        assert s.clock is clk               # an injected clock wins
        s.close()

    @pytest.mark.parametrize("kind", ["paged", "carry"])
    def test_idle_loop_is_one_span_per_idle_period(self, kind, ring):
        s = _make_scheduler(kind, start_thread=True)
        time.sleep(0.13)                    # more than two 50 ms polls
        s.close()
        by = _by_name(ring.spans())
        (idle,) = by["sequence.idle"]
        assert idle["dur"] >= 0.1 and idle["parent"] is None
        assert "sequence.iteration" not in by

    def test_work_ends_an_idle_period(self, ring):
        s = _make_scheduler("paged", start_thread=True)
        time.sleep(0.06)
        s.submit(_prompt(4), max_new_tokens=2, wait=True, timeout=60.0)
        s.close()
        by = _by_name(ring.spans())
        idles = sorted(by["sequence.idle"], key=lambda x: x["ts"])
        assert 1 <= len(idles) <= 2         # before the request, after it
        first_it = min(i["ts"] for i in by["sequence.iteration"])
        assert idles[0]["ts"] + idles[0]["dur"] <= first_it
        # the loop's clock is the registry's: the spans of two layers
        # order on one axis
        assert idles[0]["ts"] < first_it


# ----------------------------------------------------------------------
# trainers
# ----------------------------------------------------------------------
def _tiny_mln():
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(7)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=4, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(8)).build())
    return MultiLayerNetwork(conf).init()


def _tiny_graph():
    from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer,
                                       InputType, NeuralNetConfiguration,
                                       Nesterovs, OutputLayer)

    conf = (NeuralNetConfiguration.Builder().seed(7)
            .updater(Nesterovs(0.1, 0.9)).graphBuilder()
            .addInputs("in")
            .addLayer("d", DenseLayer(nOut=8, activation="relu"), "in")
            .addLayer("out", OutputLayer(nOut=4, activation="softmax",
                                         lossFunction="mcxent"), "d")
            .setOutputs("out")
            .setInputTypes(InputType.feedForward(8)).build())
    return ComputationGraph(conf).init()


def _batches(n):
    from deeplearning4j_tpu.data.dataset import DataSetIterator

    rng = np.random.RandomState(0)
    x = rng.randn(8 * n, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8 * n)]
    return DataSetIterator(x, y, 8)


TRAIN_SPANS = ("train.data_wait", "train.prepare", "train.step",
               "train.dispatch", "train.sync", "train.listeners")


class TestTrainerSpans:
    @pytest.mark.parametrize("build", [_tiny_graph, _tiny_mln],
                             ids=["graph", "multilayer"])
    def test_fit_iterator_records_one_of_each_a_step(self, build, ring):
        from deeplearning4j_tpu.analysis.retrace import RetraceSentinel

        net = build()
        seen = []
        net._listeners.append(type("L", (), {
            "iterationDone": lambda self, m, it, ep: seen.append(it)})())
        sentinel = RetraceSentinel(max_compiles=1).install(net)
        net.fit(_batches(3))
        assert sentinel.compiles("train_step") == 1   # none added
        assert seen == [1, 2, 3]
        by = _by_name(ring.spans())
        for name in TRAIN_SPANS:
            assert [s["args"] for s in by[name]] == \
                [{"iteration": k} for k in range(3)], name
            assert all(s["cat"] == "train" for s in by[name])
        for k in range(3):
            step = by["train.step"][k]
            disp, sync = by["train.dispatch"][k], by["train.sync"][k]
            assert disp["parent"] == sync["parent"] == step["id"]
            assert _inside(disp, step) and _inside(sync, step)
            assert disp["ts"] == step["ts"]
            assert disp["ts"] + disp["dur"] == sync["ts"]
            assert sync["ts"] + sync["dur"] == pytest.approx(
                step["ts"] + step["dur"])
            for name in ("train.data_wait", "train.prepare", "train.step",
                         "train.listeners"):
                assert by[name][k]["parent"] is None
            # in the order the host does them, none overlapping
            order = [by[n][k] for n in ("train.data_wait", "train.prepare",
                                        "train.step", "train.listeners")]
            for a, b in zip(order, order[1:]):
                assert a["ts"] + a["dur"] <= b["ts"]

    def test_disabled_fit_records_no_span(self, ring):
        net = _tiny_mln()
        ring.clear()                        # init()'s setup.weights_init
        telemetry.set_enabled(False)
        try:
            net.fit(_batches(2))
        finally:
            telemetry.set_enabled(True)
        assert ring.spans() == [] and net._iteration == 2


# ----------------------------------------------------------------------
# set-up phases
# ----------------------------------------------------------------------
def _phase_seconds(phase):
    fam = telemetry.get_registry().get("dl4j_setup_seconds")
    child = None if fam is None else fam.labels_get(phase=phase)
    return 0.0 if child is None else child.value


class TestSetupPhases:
    def test_phase_is_a_span_and_a_counter(self, ring):
        before = _phase_seconds("unit_test_phase")
        with telemetry.phase("unit_test_phase"):
            inner = telemetry.get_registry().add_span(
                "by-hand", "c", 0.0, 0.0,
                parent=telemetry.get_registry().current_span_id())
        assert inner is not None
        by = {s["name"]: s for s in ring.spans()}
        ph = by["setup.unit_test_phase"]
        assert ph["cat"] == "setup" and by["by-hand"]["parent"] == ph["id"]
        assert _phase_seconds("unit_test_phase") - before >= ph["dur"] > 0

    @pytest.mark.parametrize("build,phase", [
        (_tiny_graph, "weights_init"), (_tiny_mln, "weights_init"),
        (_lm, "weights_init")], ids=["graph", "multilayer", "causal_lm"])
    def test_construction_feeds_weights_init(self, build, phase, ring):
        before = _phase_seconds(phase)
        build()
        assert _phase_seconds(phase) > before
        assert "setup." + phase in _by_name(ring.spans())

    def test_counter_survives_clear_and_close(self, ring):
        from deeplearning4j_tpu.runtime import aot

        before = {p: _phase_seconds(p)
                  for p in ("weights_init", "warm")}
        host = ModelHost()
        prev, aot._SESSION = aot._SESSION, aot.ExecutableCache()
        try:
            host.register_sequence("lm", _lm(), slotBuckets=(2,),
                                   numPages=16)
        finally:
            aot._SESSION = prev
        after = {p: _phase_seconds(p) for p in before}
        assert all(after[p] > before[p] for p in before), (before, after)
        by = _by_name(ring.spans())
        (warm,) = by["setup.warm"]
        # a fresh cache: the decode bucket and every chunk length compile
        compiles = by["aot.compile"]
        assert len(compiles) == 1 + len(PREFILL_CHUNK_PAGES)
        assert all(c["parent"] == warm["id"] for c in compiles)
        ring.clear()
        host.close()
        assert {p: _phase_seconds(p) for p in before} == after
        assert "dl4j_setup_seconds" in telemetry.get_registry().prometheus()

    def test_trainer_warm_feeds_warm(self, ring):
        before = _phase_seconds("warm")
        _tiny_mln().precompile(batchSize=8, entries=("train",))
        assert _phase_seconds("warm") > before
        assert "setup.warm" in _by_name(ring.spans())
