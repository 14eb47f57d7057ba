"""Paged KV-cache serving gates (serving/sequence.py
``PagedSequenceScheduler``, nn/transformer.py, serving/kvcache.py,
docs/SERVING.md "Paged KV cache").

What must hold (the ISSUE 19 serving acceptance):

- parity: within a fixed slot bucket, paged generation — tokens AND
  per-step logits — is BITWISE the serial dense-cache trajectory
  (``dense_serial_trajectory``), ragged prompts, chunked prefill,
  prefix sharing and temperature sampling included (both paths run the
  same ``paged_attend`` core, so parity is structural);
- scheduling: at most ONE page-sized prefill chunk per iteration
  interleaves with the decode batch (a long prompt never stalls
  running generations), deadlines are honored per step and free pages,
  ManualClock + thread-less poll()/drain() is deterministic;
- bounded HBM: pool exhaustion fails the victim request with the typed
  ``KVCacheFullError`` (submit-time when unservable at any load,
  per-slot mid-flight otherwise) while other slots keep generating;
  paged residency at >= 75 % ragged occupancy is <= 0.6x the dense
  twin's reservation (the bench A/B's correctness anchor);
- compile discipline: ``warm()`` precompiles every slot bucket + the
  prefill chunk and a whole ragged serve pays ZERO further compiles;
- sampling: deterministic per (sampler_seed, stream), streams assigned
  in submit order;
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
    CausalTransformerLM, dense_serial_trajectory,
)
from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, KVCacheFullError, ManualClock, ModelHost,
    PagedSequenceScheduler, ServingClosedError, greedy_sampler,
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

    def test_prefix_adoption_stays_bitwise(self):
        """A resubmitted prompt adopts the registered pages (no
        prefill chunks paid) and still generates bitwise the serial
        trajectory — shared full pages are immutable and the tail page
        forks copy-on-write before the first append."""
        m = _lm()
        s, _ = _sched(m)
        p = _prompts((13,), m.vocab, seed=9)[0]
        first = s.submit(p, max_new_tokens=4, wait=False)
        s.drain()
        chunks_before = s.prefill_chunks
        again = s.submit(p, max_new_tokens=4, wait=False)
        s.drain()
        assert s.prefill_chunks == chunks_before  # exact adopt: zero
        assert again.wait(1.0).tolist() == first.wait(1.0).tolist()
        toks, _ = dense_serial_trajectory(
            m, p, 4, greedy_sampler(), stream_rng(0, 1), bucket=4)
        assert again.result.tolist() == toks
        s.close()


# ----------------------------------------------------------------------
# scheduling: interleave, deadlines, determinism seams
# ----------------------------------------------------------------------

class TestScheduling:
    def test_prefill_interleaves_without_stalling_decode(self):
        """A 4-chunk prompt prefills ONE chunk per iteration while an
        already-running generation keeps producing a token every
        iteration — the short request finishes while the long prompt
        is still mid-prefill (bounded prefill work per step)."""
        m = _lm(max_context=64, page_size=8)
        s, _ = _sched(m, slot_buckets=(2,), prefix_sharing=False)
        short = s.submit(_prompts((4,), m.vocab)[0], max_new_tokens=3,
                         wait=False)
        s.poll()   # short: prefill + first decode -> 2 tokens
        long = s.submit(_prompts((32,), m.vocab, seed=2)[0],
                        max_new_tokens=2, wait=False)
        s.poll()   # long chunk 1 of 4; short token 3 -> done
        assert short.done and not long.done
        assert long.prefilled == 8 < 32
        s.drain()
        assert long.wait(1.0).shape == (2,)
        s.close()

    def test_deadline_mid_generation_frees_pages(self):
        m = _lm()
        s, clk = _sched(m, prefix_sharing=False)
        req = s.submit(_prompts((9,), m.vocab)[0], max_new_tokens=30,
                       deadline=5.0, wait=False)
        s.poll()
        s.poll()
        assert s.cache.pages_in_use > 0 and not req.done
        clk.advance(10.0)
        s.poll()
        with pytest.raises(DeadlineExceededError):
            req.wait(1.0)
        assert s.cache.pages_in_use == 0
        assert s.stats["expired"] == 1
        s.close()

    def test_close_without_drain_fails_and_frees(self):
        m = _lm()
        s, _ = _sched(m, prefix_sharing=False)
        req = s.submit(_prompts((6,), m.vocab)[0], max_new_tokens=20,
                       wait=False)
        s.poll()
        s.close(drain=False)
        with pytest.raises(ServingClosedError):
            req.wait(1.0)
        assert s.cache.pages_in_use == 0

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
        per bucket and reused every iteration — the alloc-churn
        counter the bench decode leg records."""
        m = _lm()
        s, _ = _sched(m)
        s.submit(_prompts((4,), m.vocab)[0], max_new_tokens=8,
                 wait=False)
        s.drain()
        assert s.staging_reuse_bytes > 0
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
        for _ in range(20):                 # past all 18 prefill chunks
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
        plus the prefill chunk; a whole ragged serve afterwards —
        prefill, decode, prefix adoption, finishes — pays ZERO
        compiles."""
        m = _lm()
        s, _ = _sched(m, slot_buckets=(2, 4))
        s.warm()
        with aot.CompileWatch(fresh_cache) as watch:
            reqs = [s.submit(p, max_new_tokens=5, wait=False)
                    for p in _prompts((3, 9, 17, 6), m.vocab)]
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
