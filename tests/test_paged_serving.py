"""Paged KV-cache serving gates (serving/sequence.py
``PagedSequenceScheduler``, nn/transformer.py, serving/kvcache.py,
docs/SERVING.md "Paged KV cache").

What must hold (the ISSUE 19 serving acceptance):

- parity: within a fixed slot bucket, paged generation — tokens AND
  per-step logits — is BITWISE the serial dense-cache trajectory
  (``dense_serial_trajectory``), ragged prompts, chunked prefill,
  prefix sharing and temperature sampling included (both paths run the
  same ``paged_attend`` core, so parity is structural);
- scheduling: at most ONE prefill pass of one slot per iteration
  interleaves with the decode batch (a long prompt never stalls
  running generations), ManualClock + thread-less poll()/drain() is
  deterministic (deadlines and close(drain=False) giving the pages
  back: tests/test_slot_scheduler_contract.py, with the rest of the
  base's contract);
- the plan (ISSUE 31): ``prefill_plan`` is a pure function that covers
  a prompt exactly once in chunks of ``PREFILL_CHUNK_PAGES`` pages,
  largest first but for a single page left behind, never past the
  block table's end; the scheduler, its warm-up and the dense oracle
  take the same passes;
- bounded HBM: pool exhaustion fails the victim request with the typed
  ``KVCacheFullError`` (submit-time when unservable at any load,
  per-slot mid-flight otherwise) while other slots keep generating;
  paged residency at >= 75 % ragged occupancy is <= 0.6x the dense
  twin's reservation (the bench A/B's correctness anchor);
- compile discipline: ``warm()`` precompiles every slot bucket + every
  prefill chunk length and a whole ragged serve pays ZERO further
  compiles;
- sampling: deterministic per (sampler_seed, stream), streams assigned
  in submit order;
- the device's pick (ISSUE 35): a request whose sampler marks itself the
  greedy pick steps on the argmax ``_decode_paged`` returns, any other
  callable on its float32 row on the host, and either way the tokens
  and the logits are the serial oracle's, every row there when
  ``wait()`` returns;
- the HTTP tier: ``:generate`` accepts ``{"tokens": ...}`` and maps
  KVCacheFullError to 429.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.nn.transformer import (
    PREFILL_CHUNK_PAGES, CausalTransformerLM, dense_serial_trajectory,
    prefill_plan,
)
from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, KVCacheFullError, ManualClock, ModelHost,
    PagedSequenceScheduler, greedy_sampler,
    stream_rng, temperature_sampler,
)


# this module traces many model/bucket step twins; the shared hygiene
# fixture drops jax's global caches at module teardown
from conftest import drop_jax_caches_fixture

_drop_jax_caches_after_module = drop_jax_caches_fixture()


@pytest.fixture
def fresh_cache():
    """Fresh MEMORY-ONLY session cache (hermetic miss counting)."""
    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


def _lm(vocab=23, max_context=64, page_size=8, seed=3, **kw):
    return CausalTransformerLM(vocab=vocab, d_model=32, n_heads=2,
                               n_layers=2, max_context=max_context,
                               page_size=page_size, seed=seed, **kw)


def _sched(model, **kw):
    kw.setdefault("num_pages", 48)
    kw.setdefault("slot_buckets", (4,))
    clk = kw.pop("clock", None) or ManualClock()
    return PagedSequenceScheduler(model, clock=clk, start_thread=False,
                                  **kw), clk


def _prompts(lens, vocab, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lens]


def _passes(ring):
    """Prefill passes dispatched: one ``sequence.prefill`` span each."""
    return sum(sp["name"] == "sequence.prefill" for sp in ring.spans())


# ----------------------------------------------------------------------
# bitwise parity vs the serial dense trajectory
# ----------------------------------------------------------------------

class TestBitwiseVsSerial:
    def test_ragged_batch_bitwise_vs_serial_dense(self):
        """Four ragged prompts generated CONCURRENTLY through the
        paged scheduler produce, per request, bitwise the tokens AND
        logits of the serial dense-slab trajectory at the same bucket —
        chunked prefill, block-table scatter and mid-batch finishes
        included."""
        m = _lm()
        s, _ = _sched(m)
        prompts = _prompts((5, 11, 3, 16), m.vocab)
        reqs = [s.submit(p, max_new_tokens=6, wait=False)
                for p in prompts]
        s.drain()
        for i, p in enumerate(prompts):
            got = reqs[i].wait(1.0)
            toks, logits = dense_serial_trajectory(
                m, p, 6, greedy_sampler(), stream_rng(0, i), bucket=4)
            assert got.tolist() == toks
            assert np.array_equal(reqs[i].logits.view(np.uint8),
                                  logits.view(np.uint8))
        s.close()

    def test_temperature_sampling_bitwise_vs_serial(self):
        """The same holds under temperature/top-k sampling: the serial
        oracle replays the identical (seed, stream) rng, so the drawn
        trajectories coincide token for token."""
        m = _lm()
        smp = temperature_sampler(0.8, top_k=5)
        s, _ = _sched(m, sampler=temperature_sampler(0.8, top_k=5),
                      sampler_seed=42)
        prompts = _prompts((6, 9), m.vocab, seed=5)
        reqs = [s.submit(p, max_new_tokens=5, wait=False)
                for p in prompts]
        s.drain()
        for i, p in enumerate(prompts):
            toks, _ = dense_serial_trajectory(
                m, p, 5, smp, stream_rng(42, i), bucket=4)
            assert reqs[i].wait(1.0).tolist() == toks
        s.close()

    @pytest.mark.parametrize("n", [13, 59])
    def test_prefix_adoption_stays_bitwise(self, n):
        """A resubmitted prompt adopts the registered pages (no
        prefill pass paid, whether the first paid one or three) and still
        generates bitwise the serial trajectory — shared full pages are
        immutable and the tail page forks copy-on-write before the
        first append."""
        m = _lm()
        s, _ = _sched(m)
        p = _prompts((n,), m.vocab, seed=9)[0]
        ring = telemetry.get_registry().trace
        ring.clear()
        first = s.submit(p, max_new_tokens=4, wait=False)
        s.drain()
        assert _passes(ring) == len(prefill_plan(n, 0, 8, 8)) == first.chunks
        ring.clear()
        again = s.submit(p, max_new_tokens=4, wait=False)
        s.drain()
        assert _passes(ring) == 0           # exact adopt: no pass
        assert again.chunks == 0
        assert again.wait(1.0).tolist() == first.wait(1.0).tolist()
        toks, _ = dense_serial_trajectory(
            m, p, 4, greedy_sampler(), stream_rng(0, 1), bucket=4)
        assert again.result.tolist() == toks
        s.close()


# ----------------------------------------------------------------------
# ISSUE 31: the plan of a prompt's prefill passes
# ----------------------------------------------------------------------

PAGE, MP = 8, 8          # _lm()'s page and table width (max_context 64)
#: the issue's lengths: page-1, page, page+1, 2p+3, 4p, 4p+1, 7p, the
#: whole context
PLAN_LENGTHS = [7, 8, 9, 19, 32, 33, 56, 64]


class TestPrefillPlan:
    def test_chunk_lengths_are_the_page_and_two_more(self):
        assert PREFILL_CHUNK_PAGES[0] == 1
        assert list(PREFILL_CHUNK_PAGES) == sorted(set(PREFILL_CHUNK_PAGES))
        assert len(PREFILL_CHUNK_PAGES) <= 3    # each is an executable

    @pytest.mark.parametrize("n,want", [
        (7, [(0, 7, 8)]),
        (8, [(0, 8, 8)]),
        (9, [(0, 9, 16)]),
        (19, [(0, 19, 24)]),
        (32, [(0, 16, 16), (16, 16, 16)]),
        (33, [(0, 24, 24), (24, 9, 16)]),
        (56, [(0, 24, 24), (24, 16, 16), (40, 16, 16)]),
        (64, [(0, 24, 24), (24, 24, 24), (48, 16, 16)]),
    ])
    def test_passes_largest_first_and_no_single_page_behind(self, n, want):
        assert prefill_plan(n, 0, PAGE, MP) == want

    @pytest.mark.parametrize("n_live,want", [
        (8, [(8, 24, 24), (32, 16, 16), (48, 16, 16)]),
        (16, [(16, 24, 24), (40, 24, 24)]),
        (32, [(32, 16, 16), (48, 16, 16)]),
        (56, [(56, 8, 8)]),
        (64, []),
    ])
    def test_resumes_behind_adopted_pages_inside_the_table(self, n_live,
                                                           want):
        """Behind an adopted prefix the plan is the plan of the pages
        still to fill, from the first of them: its chunks hold no page
        without a prompt token, so none runs past the table's end."""
        assert prefill_plan(64, n_live, PAGE, MP) == want

    @pytest.mark.parametrize("page,mp", [(8, 8), (8, 3), (16, 5), (128, 16)])
    def test_covers_every_prompt_exactly_once(self, page, mp):
        lengths = set(PREFILL_CHUNK_PAGES)
        for n in range(1, page * mp + 1):
            for n_live in range(0, n, page):
                plan = prefill_plan(n, n_live, page, mp)
                at = n_live
                for t0, n_valid, C in plan:
                    assert t0 == at and t0 % page == 0
                    assert C // page in lengths and C % page == 0
                    assert 0 < n_valid <= C
                    assert t0 // page + C // page <= mp    # the table's end
                    at += n_valid
                assert at == n
                # only a prompt's last pass is short of its chunk, and
                # by less than a page; one page alone is a whole prompt
                assert all(nv == C for _, nv, C in plan[:-1])
                assert all(C - nv < page for _, nv, C in plan)
                if n - n_live > page:
                    assert all(C > page for _, _, C in plan)
            assert prefill_plan(n, n, page, mp) == []

    def test_resumes_on_a_page_boundary_only(self):
        with pytest.raises(ValueError, match="page boundary"):
            prefill_plan(40, 13, PAGE, MP)
        assert prefill_plan(13, 13, PAGE, MP) == []     # adopted whole

    @pytest.mark.parametrize("n", PLAN_LENGTHS)
    def test_scheduler_and_oracle_take_the_same_passes(self, n):
        """The scheduler serves a prompt of every length of the plan's
        table bitwise as the dense oracle does, in the plan's passes:
        one `sequence.prefill` a pass, pages allotted a pass at a
        time."""
        from deeplearning4j_tpu.runtime import telemetry

        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        p = _prompts((n,), m.vocab, seed=n)[0]
        n_new = min(3, m.max_context - n + 1)
        ring = telemetry.get_registry().trace
        ring.clear()
        req = s.submit(p, max_new_tokens=n_new, wait=False)
        s.drain()
        got = req.wait(1.0)
        plan = prefill_plan(n, 0, PAGE, MP)
        passes = [sp["args"] for sp in ring.spans()
                  if sp["name"] == "sequence.prefill"]
        assert [(a["chunk"], a["bucket"]) for a in passes] == \
            [(n_valid, C) for _, n_valid, C in plan]
        assert req.chunks == len(passes) == len(plan)
        toks, logits = dense_serial_trajectory(
            m, p, n_new, greedy_sampler(), stream_rng(0, 0), bucket=4)
        assert got.tolist() == toks
        assert np.array_equal(req.logits.view(np.uint8),
                              logits.view(np.uint8))
        assert s.cache.pages_in_use == 0
        s.close()

    def test_partial_adoption_plans_from_the_shared_pages(self):
        """A prompt that a registered one prefixes adopts its two full
        pages, prefills the rest in the plan's passes from there, and serves
        the oracle's tokens (its chunks differ from the oracle's, so
        the logits agree to float32 rounding, not bitwise)."""
        m = _lm()
        s, _ = _sched(m)
        base = _prompts((20,), m.vocab, seed=4)[0]
        s.submit(base, max_new_tokens=1, wait=False)
        s.drain()
        p = base + _prompts((18,), m.vocab, seed=6)[0]
        ring = telemetry.get_registry().trace
        ring.clear()
        req = s.submit(p, max_new_tokens=3, wait=False)
        s.drain()
        assert prefill_plan(38, 16, PAGE, MP) == [(16, 22, 24)]
        assert req.chunks == _passes(ring) == 1
        toks, logits = dense_serial_trajectory(
            m, p, 3, greedy_sampler(), stream_rng(0, 1), bucket=4)
        assert req.wait(1.0).tolist() == toks
        np.testing.assert_allclose(req.logits, logits, rtol=0, atol=1e-5)
        s.close()


# ----------------------------------------------------------------------
# ISSUE 35: the greedy token picked inside the decode step
# ----------------------------------------------------------------------

def _argmax_plus_one(row, rng):
    """What a user's own function may do: unmarked, so called on the
    host with the row (the tier-1 twin of perfbench/tests/
    test_faults.py::test_altered_token_is_not_correct)."""
    return (int(np.argmax(row)) + 1) % row.shape[0]


def _steps(ring):
    """The decode steps' args in dispatch order (a step's span is
    recorded when it is collected, after the step queued behind it
    went out; its id is drawn at the dispatch)."""
    return [sp["args"] for sp in sorted(ring.spans(),
                                        key=lambda sp: (sp["ts"], sp["id"]))
            if sp["name"] == "sequence.step"]


def _bitwise(req, toks, logits):
    assert req.wait(1.0).tolist() == toks
    assert req.logits.dtype == np.float32
    assert np.array_equal(req.logits.view(np.uint8), logits.view(np.uint8))


class TestDevicePick:
    @pytest.fixture
    def ring(self):
        trace = telemetry.get_registry().trace
        trace.clear()
        yield trace
        trace.clear()

    def test_samplers_say_whether_they_are_the_greedy_pick(self):
        assert greedy_sampler().picks_argmax is True
        assert temperature_sampler(0).picks_argmax is True
        assert temperature_sampler(0, top_k=3).picks_argmax is True
        for fn in (temperature_sampler(0.8), temperature_sampler(1e-9),
                   _argmax_plus_one,
                   lambda row, rng: greedy_sampler()(row, rng)):
            assert not getattr(fn, "picks_argmax", False)

    def test_decode_entry_returns_three_with_ids_and_logits_first(self):
        """`perfbench/tests/test_faults.py` wraps `_jit_decode` and
        unpacks three: the ids ride with the logits in the first."""
        m = _lm()
        s, _ = _sched(m)
        tok, sls, bts, src = s._new_staging(4)
        bts[0, 0], tok[0] = s.cache.alloc(1)[0], 5
        got = m._jit_decode(m._params, tok, *s.cache.pools, bts, sls,
                            s._no_ids(4), src)
        assert len(got) == 3
        (ids, logits), *pools = got
        s.cache.pools = tuple(pools)
        assert ids.shape == (4,) and ids.dtype == np.int32
        assert logits.shape == (4, m.vocab) and logits.dtype == np.float32
        assert np.array_equal(np.asarray(ids),
                              np.argmax(np.asarray(logits), axis=-1))
        s.close()

    def test_greedy_by_the_device_is_bitwise_the_serial_oracle(self, ring):
        """Four greedy requests that end in different iterations (1, 2,
        5 and 7 new tokens): every decode slot took the device's id,
        and tokens and logits are the dense oracle's bit for bit."""
        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        prompts = _prompts((5, 11, 3, 16), m.vocab)
        news = (1, 2, 5, 7)
        reqs = [s.submit(p, max_new_tokens=n, wait=False)
                for p, n in zip(prompts, news)]
        assert all(r.device_pick for r in reqs)
        s.drain()
        steps = _steps(ring)
        assert steps and all(a["device_picked"] == a["slots"]
                             for a in steps)
        # no row nobody wants: a slot-step for each decoded token
        assert sum(a["slots"] for a in steps) == sum(n - 1 for n in news)
        # the two-token request's one step, and the first of the batch
        # that follows, are on the host's tokens; every later one is
        # queued on the ids of the step before
        assert [a["ahead"] for a in steps] == [0, 0] + [1] * (len(steps) - 2)
        for i, (p, n) in enumerate(zip(prompts, news)):
            _bitwise(reqs[i], *dense_serial_trajectory(
                m, p, n, greedy_sampler(), stream_rng(0, i), bucket=4))
        assert s._ahead is None and s.cache.pages_in_use == 0
        s.close()

    def test_mixed_batch_gives_each_request_what_it_gets_alone(self, ring):
        """A greedy and a temperature request in one batch: each has
        the oracle's tokens and logits for its own (seed, stream), the
        draw from numpy's stream on the host, and `device_picked`
        counts the greedy slot only."""
        m = _lm()
        hot = temperature_sampler(0.8)
        s, _ = _sched(m, sampler_seed=42, prefix_sharing=False)
        prompts = _prompts((6, 9, 4), m.vocab, seed=5)
        samplers = (None, hot, temperature_sampler(0))
        reqs = [s.submit(p, max_new_tokens=6, sampler=smp, wait=False)
                for p, smp in zip(prompts, samplers)]
        assert [r.device_pick for r in reqs] == [True, False, True]
        s.drain()
        steps = _steps(ring)
        # the three are prefilled in turn: the first decodes alone, its
        # steps queued ahead, until the second's first token; from then
        # on every step holds the temperature slot, so none is queued
        # ahead and all three end together at the last. The second
        # request's slot is never the device's
        assert [(a["slots"], a["device_picked"], a["ahead"])
                for a in steps] == [
            (1, 1, 0), (1, 1, 1), (1, 1, 1), (3, 2, 0), (3, 2, 0),
            (2, 1, 0), (2, 1, 0), (2, 1, 0)]
        for i, (p, smp) in enumerate(zip(prompts, samplers)):
            _bitwise(reqs[i], *dense_serial_trajectory(
                m, p, 6, smp or greedy_sampler(), stream_rng(42, i),
                bucket=4))
        s.close()

    def test_an_unmarked_callable_is_obeyed(self, ring):
        m = _lm()
        s, _ = _sched(m, sampler=_argmax_plus_one)
        p = _prompts((7,), m.vocab)[0]
        req = s.submit(p, max_new_tokens=5, wait=False)
        s.drain()
        assert all(a["device_picked"] == 0 for a in _steps(ring))
        _bitwise(req, *dense_serial_trajectory(
            m, p, 5, _argmax_plus_one, stream_rng(0, 0), bucket=4))
        greedy, _ = dense_serial_trajectory(
            m, p, 5, greedy_sampler(), stream_rng(0, 0), bucket=4)
        assert req.result.tolist() != greedy
        assert req.result[0] == (np.argmax(req.logits[0]) + 1) % m.vocab
        s.close()

    @pytest.mark.parametrize("n_new", [1, 2, 9])
    def test_every_row_is_there_the_instant_wait_returns(self, n_new):
        """The scheduler's own thread serves; the caller reads `logits`
        straight after `wait()`: one row a token, none still on its
        way."""
        m = _lm()
        s = PagedSequenceScheduler(m, num_pages=48, slot_buckets=(4,))
        try:
            prompts = _prompts((5, 12), m.vocab, seed=n_new)
            reqs = [s.submit(p, max_new_tokens=n_new, wait=False)
                    for p in prompts]
            for i, (req, p) in enumerate(zip(reqs, prompts)):
                toks = req.wait(30.0)
                assert req.done and len(req.out_tokens) == n_new
                assert req.logits.shape == (n_new, m.vocab)
                _bitwise(req, *dense_serial_trajectory(
                    m, p, n_new, greedy_sampler(), stream_rng(0, i),
                    bucket=4))
                assert toks.tolist() == req.out_tokens
        finally:
            s.close()

    def test_expired_mid_generation_leaves_no_landing_pending(self):
        """A greedy request's deadline passes between two decode steps,
        with the rows of its last step still on their way: its error is
        raised, its pages go back, and nothing is left to land."""
        clk = ManualClock()
        m = _lm()
        s, _ = _sched(m, clock=clk, prefix_sharing=False)
        late = s.submit(_prompts((5,), m.vocab)[0], max_new_tokens=8,
                        deadline=1.0, wait=False)
        stays = s.submit(_prompts((6,), m.vocab, seed=2)[0],
                         max_new_tokens=8, wait=False)
        for _ in range(3):
            s.poll()
        assert len(late.out_tokens) == 4 and late in s._ahead.reqs
        clk.advance(2.0)
        s.poll()
        with pytest.raises(DeadlineExceededError):
            late.wait(0.1)
        assert late.pages == [] and late.logits is None
        assert len(late.out_tokens) == 4
        s.drain()
        assert s._ahead is None
        _bitwise(stays, *dense_serial_trajectory(
            m, _prompts((6,), m.vocab, seed=2)[0], 8, greedy_sampler(),
            stream_rng(0, 1), bucket=4))
        assert s.cache.pages_in_use == 0
        s.close()

    def test_failed_mid_generation_leaves_no_landing_pending(self):
        """The pool runs dry between two steps for one of two greedy
        slots: the victim has its typed error and no logits, its pages
        are back, the other ends with the oracle's rows, and no rows
        wait for anyone."""
        m = _lm()
        s, _ = _sched(m, num_pages=5, prefix_sharing=False,
                      slot_buckets=(2,))      # capacity 4: two each
        prompts = _prompts((4, 4), m.vocab)
        reqs = [s.submit(p, max_new_tokens=14, wait=False)
                for p in prompts]
        s.drain()
        (victim,) = [r for r in reqs if r.error is not None]
        with pytest.raises(KVCacheFullError):
            victim.wait(0.1)
        assert victim.pages == [] and victim.logits is None
        assert 1 < len(victim.out_tokens) < 14
        (ok,) = [r for r in reqs if r.error is None]
        i = reqs.index(ok)
        _bitwise(ok, *dense_serial_trajectory(
            m, prompts[i], 14, greedy_sampler(), stream_rng(0, i),
            bucket=2))
        assert s._ahead is None and s.cache.pages_in_use == 0
        s.close()


# ----------------------------------------------------------------------
# one greedy decode step queued ahead of the host
# ----------------------------------------------------------------------

#: scenario -> (prompt lengths, max_new each, submitted at poll k, a
#: deadline on one request, sampler of each (None: the greedy default))
AHEAD_SCENARIOS = {
    "steady": ((5, 11, 3, 16), (6, 6, 6, 6), (0, 0, 0, 0), None, None),
    "ends_mid_batch": ((5, 11, 3, 16), (3, 9, 5, 7), (0, 0, 0, 0), None,
                       None),
    "joins_running_batch": ((5, 11, 3, 16), (9, 9, 6, 5), (0, 0, 4, 6),
                            None, None),
    "deadline_while_queued": ((5, 11, 3, 16), (9, 9, 9, 9), (0, 0, 0, 0),
                              1, None),
    "one_temperature_slot": ((5, 11, 3, 16), (6, 6, 6, 6), (0, 0, 0, 0),
                             None, (None, 0.8, None, None)),
}


class Recorded:
    """`_jit_decode` that keeps the numpy operands of its last call and
    a copy of them, and at each call records whether the last call's
    still equal their copy; `warm` and the rest pass through."""

    def __init__(self, real):
        self._real, self.last, self.held = real, None, []

    def __call__(self, *args):
        if self.last is not None:
            self.held.append(all(np.array_equal(a, b)
                                 for a, b in zip(*self.last)))
        arrays = [a for a in args if isinstance(a, np.ndarray)]
        self.last = (arrays, [a.copy() for a in arrays])
        return self._real(*args)

    def __getattr__(self, name):
        return getattr(self._real, name)


class TestDispatchAhead:
    @pytest.fixture
    def ring(self):
        trace = telemetry.get_registry().trace
        trace.clear()
        yield trace
        trace.clear()

    @pytest.mark.parametrize("scenario", sorted(AHEAD_SCENARIOS))
    def test_tokens_and_rows_are_the_serial_oracles(self, scenario, ring):
        """Greedy requests stepped one step ahead of the host have the
        dense oracle's tokens and logits bit for bit: in a steady batch,
        with requests ending mid-batch, with prompts joining a running
        batch, with a deadline passing while a step is queued for its
        request, and beside a temperature slot, whose steps are waited
        for in their own iteration. After every poll a request is done
        exactly when it has its last token, its rows whole."""
        lens, news, at, doomed, temps = AHEAD_SCENARIOS[scenario]
        clk = ManualClock()
        m = _lm()
        s, _ = _sched(m, clock=clk, prefix_sharing=False, sampler_seed=7)
        prompts = _prompts(lens, m.vocab)
        samplers = [None if t is None else temperature_sampler(t)
                    for t in (temps or (None,) * len(lens))]
        reqs = [None] * len(lens)
        polls = 0
        while polls == 0 or s.active_slots or s.depth or \
                None in reqs or s._ahead is not None:
            for i, k in enumerate(at):
                if k == polls and reqs[i] is None:
                    reqs[i] = s.submit(
                        prompts[i], max_new_tokens=news[i],
                        sampler=samplers[i], wait=False,
                        deadline=5.0 if i == doomed else None)
            if doomed is not None and polls == 6:
                late = reqs[doomed]
                assert not late.done and late in s._ahead.reqs
                clk.advance(10.0)
            s.poll()
            polls += 1
            for r in filter(None, reqs):
                assert r.done == (r.error is not None
                                  or len(r.out_tokens) == r.max_new)
                if r.done and r.error is None:
                    assert r.logits.shape == (r.max_new, m.vocab)
            assert polls < 60
        steps = _steps(ring)
        assert any(a["ahead"] for a in steps)
        for a in steps:         # a host sampler's step is never ahead
            assert a["ahead"] == 0 or a["device_picked"] == a["slots"]
        for i, r in enumerate(reqs):
            if i == doomed:
                with pytest.raises(DeadlineExceededError):
                    r.wait(0.0)
                assert r.pages == [] and len(r.out_tokens) < r.max_new
                continue
            _bitwise(r, *dense_serial_trajectory(
                m, prompts[i], news[i], samplers[i] or greedy_sampler(),
                stream_rng(7, i), bucket=4))
        assert s.cache.pages_in_use == 0
        s.close()

    def test_a_failed_step_ahead_fails_its_own_requests_only(self, ring):
        """The fifth dispatch is queued on the fourth's ids for the
        second request alone, the first ending at the fourth: it raises
        at the step seam. The second request fails with it, the first
        ends with the oracle's tokens and rows, and nothing is left
        queued or allotted."""
        from deeplearning4j_tpu.runtime.chaos import ChaosError, ChaosPlan

        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        prompts = _prompts((5, 6), m.vocab)
        with ChaosPlan().raise_n("sequence.step", at=4) as plan:
            a = s.submit(prompts[0], max_new_tokens=5, wait=False)
            b = s.submit(prompts[1], max_new_tokens=9, wait=False)
            s.drain()
        assert plan.fired("sequence.step") == 1
        fifth = _steps(ring)[4]
        assert (fifth["slots"], fifth["ahead"]) == (1, 1)
        with pytest.raises(ChaosError):
            b.wait(0.0)
        _bitwise(a, *dense_serial_trajectory(
            m, prompts[0], 5, greedy_sampler(), stream_rng(0, 0),
            bucket=4))
        assert s.stats["errors"] == 1 and s.stats["completed"] == 1
        assert s._ahead is None and s.cache.pages_in_use == 0
        s.close()

    @pytest.mark.parametrize("drain", [True, False])
    def test_close_leaves_no_step_queued(self, drain):
        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        reqs = [s.submit(p, max_new_tokens=12, wait=False)
                for p in _prompts((5, 7), m.vocab)]
        for _ in range(4):
            s.poll()
        assert s._ahead is not None and s.cache.pages_in_use > 0
        s.close(drain=drain)
        assert s._ahead is None and s.cache.pages_in_use == 0
        assert all(r.done for r in reqs)
        assert all((r.error is None) == drain for r in reqs)

    def test_warm_primes_the_live_dispatch(self, fresh_cache):
        """The executable table has what `warm()` put there and nothing
        more once steps on the host's tokens and steps queued on the
        device's ids have both run."""
        m = _lm()
        s, _ = _sched(m, slot_buckets=(2, 4))
        s.warm()
        decode, prefill = dict(m._jit_decode._table), \
            dict(m._jit_prefill._table)
        assert len(decode) == 2
        reqs = [s.submit(p, max_new_tokens=6, wait=False)
                for p in _prompts((3, 9, 17, 6, 40), m.vocab)]
        s.drain()
        assert all(r.error is None for r in reqs)
        assert s.stats["dispatches"] > len(reqs)
        assert m._jit_decode._table == decode
        assert m._jit_prefill._table == prefill
        s.close()

    def test_refilled_staging_leaves_the_queued_steps_inputs(self, ring):
        """Each dispatch finds the host arrays the step before it was
        handed as they were at that dispatch: the staging of a step
        queued ahead is not the staging refilled for the next."""
        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        m._jit_decode = rec = Recorded(m._jit_decode)
        reqs = [s.submit(p, max_new_tokens=8, wait=False)
                for p in _prompts((5, 11, 3), m.vocab)]
        s.drain()
        assert all(r.error is None for r in reqs)
        assert sum(a["ahead"] for a in _steps(ring)) >= 3
        assert len(rec.held) == s.stats["dispatches"] - 1
        assert all(rec.held)
        s.close()


# ----------------------------------------------------------------------
# scheduling: interleave, deadlines, determinism seams
# ----------------------------------------------------------------------

class TestScheduling:
    def test_prefill_interleaves_without_stalling_decode(self):
        """A 9-page prompt prefills ONE pass per iteration (three
        pages each) while an already-running generation keeps
        producing a token every iteration — the short request finishes
        while the long prompt is still mid-prefill (bounded prefill
        work per step)."""
        m = _lm(max_context=128, page_size=8)
        s, _ = _sched(m, slot_buckets=(2,), prefix_sharing=False)
        short = s.submit(_prompts((4,), m.vocab)[0], max_new_tokens=3,
                         wait=False)
        s.poll()   # short: prefill + first decode -> 2 tokens
        long = s.submit(_prompts((72,), m.vocab, seed=2)[0],
                        max_new_tokens=2, wait=False)
        s.poll()   # long pass 1 of 3; short token 3 -> done
        assert short.done and not long.done
        assert long.prefilled == 24 < 72
        assert len(prefill_plan(72, 0, 8, 16)) == 3
        s.drain()
        assert long.wait(1.0).shape == (2,)
        s.close()

    def test_sampling_streams_deterministic_per_seed(self):
        """Same (sampler_seed, submit order) -> identical draws across
        scheduler instances; a different seed diverges."""
        m = _lm()
        smp = temperature_sampler(1.0)
        outs = []
        for seed in (7, 7, 8):
            s, _ = _sched(m, sampler=temperature_sampler(1.0),
                          sampler_seed=seed, prefix_sharing=False)
            r = s.submit(_prompts((8,), m.vocab)[0],
                         max_new_tokens=12, wait=False)
            s.drain()
            outs.append(r.wait(1.0).tolist())
            s.close()
        assert outs[0] == outs[1]
        assert outs[0] != outs[2]

    def test_staging_buffers_reused_across_iterations(self):
        """Decode staging (tokens/lens/block tables) is allocated once
        per bucket and half and reused every iteration: a second
        request's steps fill the same objects as the first's."""
        m = _lm()
        s, _ = _sched(m)
        s.submit(_prompts((4,), m.vocab)[0], max_new_tokens=8,
                 wait=False)
        s.drain()
        first = dict(s._staging)
        assert len(first) == 2              # both halves of the bucket
        s.submit(_prompts((6,), m.vocab, seed=1)[0], max_new_tokens=8,
                 wait=False)
        s.drain()
        assert s._staging.keys() == first.keys()
        assert all(s._staging[k] is first[k] for k in first)
        assert all(s._staging_for(*k) is first[k] for k in first)
        s.close()


# ----------------------------------------------------------------------
# bounded HBM: exhaustion + the residency anchor
# ----------------------------------------------------------------------

class TestBoundedHBM:
    def test_unservable_prompt_rejected_at_submit(self):
        m = _lm(max_context=32, page_size=8)
        s, _ = _sched(m, num_pages=3)   # capacity 2 pages = 16 rows
        with pytest.raises(KVCacheFullError):
            s.submit(_prompts((17,), m.vocab)[0], max_new_tokens=1)
        s.close()

    def test_midflight_exhaustion_fails_victim_only(self):
        """When the pool runs dry mid-generation, the slot that needed
        the page fails with the typed error; the other slot keeps its
        pages and completes."""
        m = _lm()
        s, _ = _sched(m, num_pages=5, prefix_sharing=False,
                      slot_buckets=(2,))
        # 2 pages each after prefill+early decode; both need a 3rd at
        # the seq_len-16 boundary and the capacity-4 pool has none left
        p = _prompts((4, 4), m.vocab)
        a = s.submit(p[0], max_new_tokens=14, wait=False)
        b = s.submit(p[1], max_new_tokens=14, wait=False)
        s.drain()
        results = []
        for r in (a, b):
            try:
                results.append(r.wait(1.0).tolist())
            except KVCacheFullError:
                results.append("full")
        assert results.count("full") == 1
        done = [r for r in results if r != "full"]
        assert len(done) == 1 and len(done[0]) == 14
        assert s.stats["errors"] == 1 and s.stats["completed"] == 1
        s.close()

    def test_exhaustion_between_passes_returns_the_victims_pages(self):
        """The second pass of a prompt finds the pool dry: that request
        alone fails, the three pages of its first pass go back, and the
        other slot generates to its end."""
        m = _lm()
        s, _ = _sched(m, num_pages=7, prefix_sharing=False,
                      slot_buckets=(2,))          # capacity 6
        a = s.submit(_prompts((9,), m.vocab)[0], max_new_tokens=6,
                     wait=False)                  # 2 pages, 14 rows
        b = s.submit(_prompts((40,), m.vocab, seed=3)[0],
                     max_new_tokens=1, wait=False)
        s.poll()                                  # a: its one pass
        s.poll()                                  # b: 3 pages of 5
        assert b.prefilled == 24 and s.cache.pages_in_use == 5
        s.poll()                                  # b: two more, one is there
        with pytest.raises(KVCacheFullError):
            b.wait(1.0)
        assert b.pages == [] and s.cache.pages_in_use == 2
        s.drain()
        assert a.wait(1.0).shape == (6,)
        assert s.stats["errors"] == 1 and s.stats["completed"] == 1
        assert s.cache.pages_in_use == 0
        s.close()

    def test_a_pass_takes_all_its_pages_or_none(self):
        """A pass of three pages with two free takes none of them:
        the request fails alone and the free list is whole."""
        m = _lm()
        s, _ = _sched(m, num_pages=6, prefix_sharing=False,
                      slot_buckets=(2,))          # capacity 5
        a = s.submit(_prompts((17,), m.vocab)[0], max_new_tokens=4,
                     wait=False)                  # 3 pages, 20 rows
        b = s.submit(_prompts((24,), m.vocab, seed=3)[0],
                     max_new_tokens=1, wait=False)
        s.poll()
        assert s.cache.pages_in_use == 3
        s.poll()                                  # b wants 3 of the 2 left
        with pytest.raises(KVCacheFullError):
            b.wait(1.0)
        assert b.pages == [] and not np.any(b.block_row)
        assert s.cache.pages_in_use == 3 and len(s.cache._free) == 2
        s.drain()
        assert a.wait(1.0).shape == (4,)
        assert s.cache.pages_in_use == 0
        s.close()

    def test_residency_le_60pct_of_dense_at_75pct_occupancy(self):
        """The acceptance anchor: with >= 75 % of the bucket's slots
        live at RAGGED lengths, the paged pool's live bytes are
        <= 0.6x what the dense twin reserves for the same bucket
        (slots x max_context, paid regardless of load)."""
        m = _lm(max_context=64, page_size=8)
        s, _ = _sched(m, slot_buckets=(8,), num_pages=64,
                      prefix_sharing=False)
        lens = (10, 14, 18, 22, 26, 30)     # 6/8 slots = 75 %
        reqs = [s.submit(p, max_new_tokens=24, wait=False)
                for p in _prompts(lens, m.vocab)]
        for _ in range(20):                 # past every prefill pass
            s.poll()
        assert s.active_slots == 6
        assert s.occupancy[-1] == (6, 8)
        paged = s.cache.bytes_in_use()
        dense = m.dense_cache_bytes(8)
        assert paged <= 0.6 * dense, \
            f"paged {paged}B vs dense {dense}B = {paged / dense:.2f}x"
        s.drain()
        for r in reqs:
            assert r.wait(1.0).shape == (24,)
        assert s.cache.pages_in_use == 0    # everything returned
        s.close()


# ----------------------------------------------------------------------
# compile discipline
# ----------------------------------------------------------------------

class TestCompileDiscipline:
    def test_warm_then_zero_steady_state_compiles(self, fresh_cache):
        """warm() precompiles one decode executable per slot bucket
        plus one prefill executable per chunk length of the plan; a
        whole ragged serve afterwards — prefill in chunks of every
        length, decode, prefix adoption, finishes — pays ZERO
        compiles."""
        m = _lm()
        s, _ = _sched(m, slot_buckets=(2, 4))
        rep = s.warm()
        assert sorted(k for k in rep if isinstance(k, str)) == sorted(
            "prefill" if n == 1 else f"prefill{n}"
            for n in PREFILL_CHUNK_PAGES)
        lens = (3, 9, 17, 6, 40)
        assert {C // 8 for n in lens for _, _, C in
                prefill_plan(n, 0, 8, 8)} == set(PREFILL_CHUNK_PAGES)
        with aot.CompileWatch(fresh_cache) as watch:
            reqs = [s.submit(p, max_new_tokens=5, wait=False)
                    for p in _prompts(lens, m.vocab)]
            s.drain()
            for r in reqs:
                r.wait(1.0)
        watch.assert_no_compiles()
        s.close()


# ----------------------------------------------------------------------
# the host + HTTP tier
# ----------------------------------------------------------------------

def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestHostAndServer:
    def test_register_generate_policy(self):
        m = _lm()
        host = ModelHost()
        rep = host.register_sequence("lm", m, slotBuckets=(4,),
                                     numPages=32)
        assert rep["version"] == 1
        pol = host.describe()["lm"]
        assert pol["paged"] and pol["pageSize"] == 8 \
            and pol["numPages"] == 32
        out = host.generate("lm", [1, 2, 3], max_new_tokens=4)
        toks, _ = dense_serial_trajectory(
            m, [1, 2, 3], 4, greedy_sampler(), stream_rng(0, 0),
            bucket=4)
        assert out.tolist() == toks
        # feature-path submit on a paged model is a loud 400-class
        # error, not silent nonsense
        with pytest.raises(ValueError):
            host.submit_sequence("lm", np.zeros((3, 4), np.float32))
        host.close()

    def test_http_generate_tokens_and_429_on_full_pool(self):
        from deeplearning4j_tpu.serving import InferenceServer

        m = _lm()
        host = ModelHost()
        host.register_sequence("lm", m, slotBuckets=(2,), numPages=3)
        srv = InferenceServer(host).start(port=0)
        port = srv.port
        try:
            st, body = _post(port, "/v1/models/lm:generate",
                             {"tokens": [1, 2, 3], "maxNewTokens": 3})
            assert st == 200 and len(body["tokens"]) == 3 \
                and body["steps"] == 3
            # capacity 2 pages = 16 rows; a 17-token prompt can never
            # be admitted -> 429, the same backpressure class as a
            # full queue
            st, body = _post(port, "/v1/models/lm:generate",
                             {"tokens": list(range(17)),
                              "maxNewTokens": 1})
            assert st == 429
            assert "pages" in body.get("error", "")
            st, _ = _post(port, "/v1/models/lm:generate",
                          {"tokens": [9999], "maxNewTokens": 1})
            assert st == 400
        finally:
            srv.stop()
            host.close()
