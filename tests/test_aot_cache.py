"""AOT compilation + executable cache gates (runtime/aot.py,
runtime/compile_cache.py, docs/COMPILE.md).

What must hold:

- cache keys: a config change or a dtype-policy change is a MISS (two
  different programs must never share an executable), an equal config
  at an equal signature is a HIT;
- parity: a fit through precompiled (cached) executables is BITWISE
  identical to one through the plain donated jit on all three network
  types;
- donation: a cached executable is the donated one — its inputs are
  dead after the step;
- warm start: a SECOND process finds the first one's executables in
  JAX's persistent compilation cache, in the directory
  JAX_COMPILATION_CACHE_DIR names and nowhere else;
- serving buckets: request batches canonicalise to a fixed bucket set,
  one executable per bucket (the RetraceSentinel budget).
"""

import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.runtime import aot


# ----------------------------------------------------------------------
# subjects
# ----------------------------------------------------------------------

def _mln(seed=7, lr=0.1, nout=16, dtype=None):
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)

    b = (NeuralNetConfiguration.Builder().seed(seed)
         .updater(Nesterovs(lr, 0.9)))
    if dtype is not None:
        b = b.dataType(dtype)
    conf = (b.list()
            .layer(DenseLayer(nOut=nout, activation="relu"))
            .layer(OutputLayer(nOut=4, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(8)).build())
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    return MultiLayerNetwork(conf).init()


def _graph(seed=3):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).graphBuilder()
            .addInputs("in")
            .addLayer("d", DenseLayer(nOut=16, activation="relu"), "in")
            .addLayer("out", OutputLayer(nOut=4, activation="softmax",
                                         lossFunction="mcxent"), "d")
            .setOutputs("out")
            .setInputTypes(InputType.feedForward(8)).build())
    return ComputationGraph(conf).init()


def _samediff():
    from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.nn.updaters import Sgd

    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 8, 5)
    y = sd.placeHolder("y", jnp.float64, 8, 1)
    w = sd.var("w", np.zeros((5, 1)))
    sd.loss.meanSquaredError(y, sd.nn.linear(x, w, name="p"), name="l")
    sd.setTrainingConfig(TrainingConfig.Builder()
                         .updater(Sgd(learningRate=0.05))
                         .dataSetFeatureMapping("x")
                         .dataSetLabelMapping("y").build())
    return sd


def _batch():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype("float32")
    y = np.eye(4, dtype="float32")[rng.randint(0, 4, 8)]
    return x, y


@pytest.fixture
def fresh_cache():
    """A fresh cache installed as THE session cache for the test (the
    suite-wide one from conftest is restored after)."""
    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


@pytest.fixture
def no_cache():
    """AOT disabled: the plain donated-jit path (the cold oracle)."""
    prev = aot._SESSION
    aot.disable()
    yield
    aot._SESSION = prev


# ----------------------------------------------------------------------
# keys
# ----------------------------------------------------------------------

class TestKeys:
    def test_equal_config_equal_key_different_config_miss(self,
                                                          fresh_cache):
        r1 = _mln(seed=7, lr=0.1).precompile(batchSize=8)
        r2 = _mln(seed=7, lr=0.1).precompile(batchSize=8)
        r3 = _mln(seed=7, lr=0.05).precompile(batchSize=8)  # lr differs
        assert r1["train_step"]["key"] == r2["train_step"]["key"]
        assert r2["train_step"]["status"] == "warm"
        assert r3["train_step"]["key"] != r1["train_step"]["key"]
        assert r3["train_step"]["status"] == "cold"

    def test_dtype_policy_change_misses(self, fresh_cache):
        from deeplearning4j_tpu.ndarray import DataType

        k32 = _mln(dtype=DataType.FLOAT).precompile(
            batchSize=8)["train_step"]["key"]
        kbf = _mln(dtype=DataType.BFLOAT16).precompile(
            batchSize=8)["train_step"]["key"]
        assert k32 != kbf

    def test_tail_mode_toggle_misses(self, fresh_cache):
        from deeplearning4j_tpu.nn import losses as _losses

        k_compute = _mln().precompile(batchSize=8)["train_step"]["key"]
        old = _losses._TAIL_MODE
        _losses._TAIL_MODE = "wide"
        try:
            k_wide = _mln().precompile(batchSize=8)["train_step"]["key"]
        finally:
            _losses._TAIL_MODE = old
        assert k_compute != k_wide

    def test_every_autotune_knob_separates_keys(self, fresh_cache):
        """ISSUE 12 small-fix regression gate: the cache key must
        incorporate the autotune arbiter's chosen knob values — a tuned
        run and a stock run must NEVER share an executable. Flipping
        EACH registered knob off its current value must change the key
        (companion of TestKeys tail-mode / TestTrainerPrecompile
        sharded-vs-replicated separations)."""
        from deeplearning4j_tpu.runtime import autotune as at

        net = _mln()
        base_key = net.precompile(batchSize=8)["train_step"]["key"]
        for knob in at.KNOBS:
            alt = next(c for c in knob.candidates if c != knob.get())
            with at.applied({knob.name: alt}):
                k = _mln().precompile(batchSize=8)["train_step"]["key"]
            assert k != base_key, (
                f"knob {knob.name}={alt} produced the SAME cache key "
                "as the stock config — tuned and stock runs would "
                "share an executable")

    def test_batch_signature_change_misses(self, fresh_cache):
        k8 = _mln().precompile(batchSize=8)["train_step"]["key"]
        k16 = _mln().precompile(batchSize=16)["train_step"]["key"]
        assert k8 != k16

    def test_shape_dtype_struct_warm_primes_real_calls(self,
                                                       fresh_cache):
        """warm() with ShapeDtypeStructs must land on the SAME key a
        real concrete-array call computes — otherwise the advertised
        abstract precompile silently buys nothing."""
        net = _mln()
        x, y = _batch()
        key = jax.random.fold_in(
            jax.random.key(net.conf.seed ^ 0x5EED), 0)
        sds = lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                             jnp.asarray(a).dtype)
        args_abstract = (
            jax.tree_util.tree_map(sds, net._params),
            jax.tree_util.tree_map(sds, net._upd_states),
            jax.tree_util.tree_map(sds, net._states),
            sds(jnp.asarray(0, jnp.int32)), sds(jnp.asarray(x)),
            sds(jnp.asarray(y)), sds(key), None, None)
        k_abs, status, _ = net._jit_train.warm(*args_abstract)
        assert status == "cold"
        misses = fresh_cache.stats["misses"]
        net.fit(x, y)  # first real call: must hit, not recompile
        assert fresh_cache.stats["misses"] == misses


# ----------------------------------------------------------------------
# parity: warm == cold, bitwise
# ----------------------------------------------------------------------

def _fit_mln(net, steps=4):
    x, y = _batch()
    for _ in range(steps):
        net.fit(x, y)
    return [np.asarray(leaf) for leaf in
            jax.tree_util.tree_leaves(net._params)]


class TestWarmColdParity:
    def test_multilayer_bitwise(self, no_cache):
        cold = _fit_mln(_mln())
        prev = aot._SESSION
        try:
            aot.enable()
            net = _mln()
            net.precompile(batchSize=8)
            warm_first = _fit_mln(net)
            # an equal-config network shares the first one's executable
            net2 = _mln()
            rep = net2.precompile(batchSize=8)
            assert rep["train_step"]["status"] == "warm"
            warm_shared = _fit_mln(net2)
        finally:
            aot._SESSION = prev
        for c, w1, w2 in zip(cold, warm_first, warm_shared):
            np.testing.assert_array_equal(c, w1)
            np.testing.assert_array_equal(c, w2)

    def test_multilayer_fit_dataset_bitwise(self, no_cache):
        from deeplearning4j_tpu.data import DataSetIterator

        rng = np.random.RandomState(2)
        xs = rng.randn(32, 8).astype("float32")
        ys = np.eye(4, dtype="float32")[rng.randint(0, 4, 32)]

        def run(precompiled):
            net = _mln()
            if precompiled:
                net.precompile(batchSize=8, stepsPerSync=2)
            net.fitDataSet(DataSetIterator(xs, ys, 8), stepsPerSync=2)
            return [np.asarray(leaf) for leaf in
                    jax.tree_util.tree_leaves(net._params)]

        cold = run(False)
        prev = aot._SESSION
        try:
            aot.enable()
            warm = run(True)
        finally:
            aot._SESSION = prev
        for c, w in zip(cold, warm):
            np.testing.assert_array_equal(c, w)

    def test_graph_bitwise(self, no_cache):
        x, y = _batch()

        def run():
            g = _graph()
            for _ in range(4):
                g.fit(x, y)
            return [np.asarray(leaf) for leaf in
                    jax.tree_util.tree_leaves(g._params)]

        cold = run()
        prev = aot._SESSION
        try:
            aot.enable()
            _graph().precompile(batchSize=8)   # populate
            warm = run()
        finally:
            aot._SESSION = prev
        for c, w in zip(cold, warm):
            np.testing.assert_array_equal(c, w)

    def test_samediff_bitwise(self, no_cache):
        rng = np.random.RandomState(1)
        X = rng.rand(8, 5)
        Y = X @ np.ones((5, 1))

        def run(precompiled):
            sd = _samediff()
            if precompiled:
                sd.precompile(features=X, labels=Y)
            sd.fit(features=X, labels=Y, epochs=3)
            return np.asarray(sd.getVariable("w").getArr().toNumpy())

        cold = run(False)
        prev = aot._SESSION
        try:
            aot.enable()
            warm = run(True)
            warm_shared = run(True)
        finally:
            aot._SESSION = prev
        np.testing.assert_array_equal(cold, warm)
        np.testing.assert_array_equal(cold, warm_shared)


# ----------------------------------------------------------------------
# cached executables are the donated ones
# ----------------------------------------------------------------------

class TestDonation:
    def test_cached_step_donates_its_inputs(self, fresh_cache):
        """The donated-jit contract callers rely on — input buffers are
        dead after the step — holds for the cached executable too: it
        is compiled from the same donated jit."""
        net = _mln()
        net.precompile(batchSize=8)
        old_leaf = net._params[0]["W"]
        x, y = _batch()
        net.fit(x, y)
        assert old_leaf.is_deleted()

    def test_sentinel_still_counts_with_warm_cache(self, fresh_cache):
        """RetraceSentinel.install bypasses the cache (a hit would hide
        the trace the counter exists to count): exactly one compile is
        still observed even when the cache is hot."""
        from deeplearning4j_tpu.analysis.retrace import RetraceSentinel

        _mln().precompile(batchSize=8)    # hot cache for this program
        net = _mln()
        sent = RetraceSentinel(max_compiles=1).install(net, "step")
        x, y = _batch()
        for _ in range(3):
            net.fit(x, y)
        assert sent.compiles("step") == 1


# ----------------------------------------------------------------------
# second-process warm start (the zero→aha metric)
# ----------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from deeplearning4j_tpu.runtime import compile_cache
    from deeplearning4j_tpu.zoo import LeNet

    print("DIR", compile_cache.configure())
    net = LeNet(numClasses=10, inputShape=(1, 28, 28)).init()
    x = np.zeros((8, 1, 28, 28), np.float32)
    y = np.eye(10, dtype=np.float32)[np.zeros(8, int)]
    with compile_cache.PersistentCacheWatch() as w:
        rep = net.precompile(batchSize=8, entries=("train",))
        old = net._params[0]["W"]
        net.fit(x, y)
    assert rep["train_step"]["status"] == "cold", rep
    assert old.is_deleted()          # the donated step, round-tripped
    assert np.isfinite(net.score())
    print("HITS", w.hits, "MISSES", w.misses)
""")


class TestSecondProcessWarmStart:
    def test_second_process_hits_the_persistent_cache(self, tmp_path):
        """Two fresh interpreters, one JAX_COMPILATION_CACHE_DIR: the
        first compiles a zoo model's donated train step and stores it,
        the second loads it (zero XLA compiles) and steps with it. The
        entries land in the directory the variable names and
        compile_cache.configure() chooses no other."""
        cache_dir = tmp_path / "jaxcc"
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)     # one device: the smallest program

        def child():
            out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                                 capture_output=True, text=True,
                                 timeout=300,
                                 cwd=os.path.dirname(os.path.dirname(
                                     os.path.abspath(__file__))))
            assert out.returncode == 0, (
                f"child failed:\n{out.stdout}\n{out.stderr[-2000:]}")
            assert f"DIR {cache_dir}\n" in out.stdout
            hits, misses = re.search(r"HITS (\d+) MISSES (\d+)",
                                     out.stdout).groups()
            return int(hits), int(misses)

        hits1, misses1 = child()
        assert misses1 > 0 and hits1 == 0
        assert any(cache_dir.iterdir())
        hits2, misses2 = child()
        assert hits2 == misses1 and misses2 == 0


# ----------------------------------------------------------------------
# shape buckets + serving
# ----------------------------------------------------------------------

class TestBuckets:
    def test_bucket_batch_maths(self):
        assert aot.bucket_batch(1) == 1
        assert aot.bucket_batch(3) == 4
        assert aot.bucket_batch(33) == 64
        assert aot.bucket_batch(1024) == 1024
        assert aot.bucket_batch(1500) == 2048  # multiples of the top
        with pytest.raises(ValueError):
            aot.bucket_batch(0)
        assert aot.sentinel_budget((1, 8, 64)) == 3
        assert aot.sentinel_budget((1, 8, 64), entries=2) == 6

    def test_parallel_inference_one_compile_per_bucket(self,
                                                       fresh_cache):
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        from deeplearning4j_tpu.parallel.mesh import build_mesh

        net = _mln()
        mesh = build_mesh({"data": 2})
        pi = ParallelInference(net, mesh=mesh, batchBuckets=(8, 16))
        rep = pi.precompile()
        assert set(rep) == {8, 16}
        misses = fresh_cache.stats["misses"]
        for b in (3, 5, 7, 8):        # all land in the 8-bucket
            out = pi.output(np.zeros((b, 8), np.float32))
            assert out.shape()[0] == b
        for b in (9, 12):             # 16-bucket
            assert pi.output(
                np.zeros((b, 8), np.float32)).shape()[0] == b
        assert fresh_cache.stats["misses"] == misses  # zero new compiles

    def test_httpserve_warmup_gates_readiness(self):
        import json
        import threading
        import time
        import urllib.request

        from deeplearning4j_tpu.clustering.server import (
            NearestNeighborsServer)

        release = threading.Event()
        srv = NearestNeighborsServer(
            np.random.RandomState(0).rand(16, 4))
        srv.start(port=0, warmup=release.wait)
        try:
            url = f"http://127.0.0.1:{srv.port}/healthz"
            try:
                urllib.request.urlopen(url, timeout=5)
                code = 200
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == 503    # not ready until warmup returns
            release.set()
            deadline = time.time() + 5
            while time.time() < deadline:
                try:
                    body = json.load(urllib.request.urlopen(url,
                                                            timeout=5))
                    assert body["status"] == "ok"
                    break
                except urllib.error.HTTPError:
                    time.sleep(0.02)
            else:
                pytest.fail("server never became ready after warmup")
        finally:
            srv.stop()


# ----------------------------------------------------------------------
# trainers
# ----------------------------------------------------------------------

class TestTrainerPrecompile:
    def test_parallel_wrapper_warm_matches_cold(self, no_cache):
        from deeplearning4j_tpu.parallel.mesh import build_mesh
        from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

        mesh = build_mesh({"data": 2})
        x, y = _batch()

        def run(precompiled, wu):
            net = _mln()
            pw = ParallelWrapper(net, mesh=build_mesh({"data": 2}),
                                 weight_update=wu)
            if precompiled:
                rep = pw.precompile(batchSize=8)
                assert rep["pw_train_step"]["status"] in ("cold", "warm")
            for _ in range(3):
                pw.fit(x, y)
            return [np.asarray(leaf) for leaf in
                    jax.tree_util.tree_leaves(net._params)]

        for wu in ("replicated", "sharded"):
            cold = run(False, wu)
            prev = aot._SESSION
            try:
                aot.enable()
                warm = run(True, wu)
            finally:
                aot._SESSION = prev
            for c, w in zip(cold, warm):
                np.testing.assert_array_equal(c, w)

    def test_sharded_vs_replicated_keys_differ(self, fresh_cache):
        from deeplearning4j_tpu.parallel.mesh import build_mesh
        from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

        reps = {}
        for wu in ("replicated", "sharded"):
            pw = ParallelWrapper(_mln(), mesh=build_mesh({"data": 2}),
                                 weight_update=wu)
            reps[wu] = pw.precompile(batchSize=8)["pw_train_step"]["key"]
        assert reps["replicated"] != reps["sharded"]
