"""fitDataSet(iterator, stepsPerSync=k) — the device-staged multi-batch
epoch loop.

The acceptance bar, verified here:

* the k-stack loop follows the SAME trajectory as k sequential fit()
  calls on the same fresh batches — params, updater state, per-step
  scores, iteration counters, and the iteration-keyed dropout RNG
  stream — on MultiLayerNetwork, ComputationGraph and SameDiff;
* ragged final stacks (n % k != 0) run through plain per-batch fit()
  with identical results and NO retrace of the k-loop;
* exactly one jit compile of the k-loop across a whole epoch
  (RetraceSentinel.install_fit_dataset) and exactly ⌈n/k⌉ host syncs;
* sharded parity under the 8-virtual-device mesh (ParallelWrapper);
* ResilientFit(stepsPerSync=k): per-step non-finite skip accounting
  replayed from the block's k-vector, checkpoints at block boundaries,
  and mid-epoch preemption resume landing on the same trajectory.
"""

import math

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.analysis import RetraceSentinel
from deeplearning4j_tpu.data import DataSet, DataSetIterator
from deeplearning4j_tpu.data.iterators import iter_stacks, stack_datasets
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork,
    DenseLayer, OutputLayer, LSTM, RnnOutputLayer,
    Adam, Sgd, WeightInit, BackpropType,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize import CollectScoresListener, TrainingListener


def _mlp(seed=42, dropout=None):
    dense = DenseLayer(nOut=16) if dropout is None else \
        DenseLayer(nOut=16, dropOut=dropout)
    return (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(1e-2)).weightInit(WeightInit.XAVIER)
            .activation("relu").list()
            .layer(dense)
            .layer(OutputLayer(nOut=3, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(4))
            .build())


def _data(n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, n)]
    return x, y


def _iter(n_batches, batch=8, seed=0):
    x, y = _data(n_batches * batch, seed)
    return DataSetIterator(x, y, batch)  # deterministic order


def _assert_tree_close(a, b, rtol=2e-6, atol=2e-6):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=rtol, atol=atol)


class _SyncSpy(TrainingListener):
    def __init__(self):
        self.boundaries = []   # (iteration, k)

    def onSyncBoundary(self, model, iteration, scores):
        self.boundaries.append((iteration, len(scores)))


# ----------------------------------------------------------------------
# staging helpers
# ----------------------------------------------------------------------
class TestStacking:
    def test_iter_stacks_grouping(self):
        groups = [len(g) for g in iter_stacks(_iter(7), 3)]
        assert groups == [3, 3, 1]
        groups = [len(g) for g in iter_stacks(_iter(6), 3)]
        assert groups == [3, 3]

    def test_iter_stacks_plain_iterable(self):
        items = [object() for _ in range(5)]
        groups = [g for g in iter_stacks(items, 2)]
        assert [len(g) for g in groups] == [2, 2, 1]
        assert [x for g in groups for x in g] == items

    def test_stack_shapes_and_missing_masks(self):
        batches = [next(iter(_iter(1, batch=8, seed=s))) for s in range(3)]
        x, y, fm, lm = stack_datasets(batches)
        assert x.shape == (3, 8, 4) and y.shape == (3, 8, 3)
        assert fm is None and lm is None

    def test_mixed_label_mask_synthesized(self):
        # the padded final batch of an epoch carries a labels mask the
        # earlier batches lack — it must still share a stack (all-ones
        # synthesized for the maskless ones)
        x, y = _data(20)
        it = DataSetIterator(x, y, 8)  # 3 batches, last padded+masked
        batches = [it.next() for _ in range(3)]
        _, _, fm, lm = stack_datasets(batches)
        assert fm is None
        assert lm is not None and lm.shape == (3, 8)
        assert lm[0].min() == 1.0 and lm[2].min() == 0.0

    def test_ragged_component_shapes_rejected(self):
        a = DataSet(np.zeros((8, 4), "float32"), np.zeros((8, 3), "float32"))
        b = DataSet(np.zeros((4, 4), "float32"), np.zeros((4, 3), "float32"))
        with pytest.raises(ValueError, match="ragged"):
            stack_datasets([a, b])


# ----------------------------------------------------------------------
# MultiLayerNetwork
# ----------------------------------------------------------------------
class TestFitDataSetMultiLayer:
    def test_matches_sequential_fit(self):
        n, k = 8, 4
        a = MultiLayerNetwork(_mlp()).init()
        b = MultiLayerNetwork(_mlp()).init()
        sa, sb = CollectScoresListener(), CollectScoresListener()
        a.setListeners(sa)
        b.setListeners(sb)
        a.fit(_iter(n))
        b.fitDataSet(_iter(n), stepsPerSync=k)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)
        _assert_tree_close(a._upd_states, b._upd_states)
        assert a._iteration == b._iteration == n
        assert sa.iterations == sb.iterations
        np.testing.assert_allclose(sa.scores, sb.scores,
                                   rtol=2e-5, atol=2e-6)

    def test_dropout_rng_stream(self):
        """The iteration-keyed dropout keys inside the k-loop are the
        SAME stream fit() folds in per batch."""
        n, k = 6, 3
        a = MultiLayerNetwork(_mlp(seed=3, dropout=0.7)).init()
        b = MultiLayerNetwork(_mlp(seed=3, dropout=0.7)).init()
        a.fit(_iter(n))
        b.fitDataSet(_iter(n), stepsPerSync=k)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)

    def test_ragged_tail_parity(self):
        n, k = 10, 4  # 2 full blocks + 2 tail batches through fit()
        a = MultiLayerNetwork(_mlp()).init()
        b = MultiLayerNetwork(_mlp()).init()
        a.fit(_iter(n))
        b.fitDataSet(_iter(n), stepsPerSync=k)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)
        assert b._iteration == n
        assert b._fit_dataset_syncs == math.ceil(n / k) + 1  # 2 blocks + 2 tail

    def test_host_sync_count_and_boundaries(self):
        n, k = 12, 4
        net = MultiLayerNetwork(_mlp()).init()
        spy = _SyncSpy()
        net.setListeners(spy)
        net.fitDataSet(_iter(n), stepsPerSync=k)
        assert net._fit_dataset_syncs == math.ceil(n / k) == 3
        assert [kk for _, kk in spy.boundaries] == [4, 4, 4]
        assert [it for it, _ in spy.boundaries] == [4, 8, 12]

    def test_single_compile_across_epochs(self):
        net = MultiLayerNetwork(_mlp()).init()
        sent = RetraceSentinel(max_compiles=1).install_fit_dataset(net)
        # 3 blocks/epoch x 2 epochs, plus a ragged tail batch: ONE trace
        net.fitDataSet(_iter(13), stepsPerSync=4, epochs=2)
        assert sent.compiles("fit_dataset_loop") == 1
        assert net._iteration == 26 and net._epoch == 2

    def test_steps_per_sync_one_is_fit(self):
        a = MultiLayerNetwork(_mlp()).init()
        b = MultiLayerNetwork(_mlp()).init()
        a.fit(_iter(4))
        b.fitDataSet(_iter(4), stepsPerSync=1)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(), rtol=0, atol=0)
        # the k=1 delegation still records the call's sync count
        assert b._fit_dataset_syncs == 4

    def test_invalid_k_rejected(self):
        net = MultiLayerNetwork(_mlp()).init()
        with pytest.raises(ValueError, match="stepsPerSync"):
            net.fitDataSet(_iter(4), stepsPerSync=0)

    def test_tbptt_rejected(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.05))
                .list()
                .layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=3, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(4, 8))
                .backpropType(BackpropType.TruncatedBPTT).tBPTTLength(4)
                .build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="truncated BPTT"):
            net.fitDataSet(_iter(4), stepsPerSync=2)


# ----------------------------------------------------------------------
# ComputationGraph
# ----------------------------------------------------------------------
class TestFitDataSetGraph:
    def _conf(self, seed=9):
        return (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Adam(1e-2)).graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer(nOut=16, activation="relu"), "in")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax",
                                             lossFunction="mcxent"), "d")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4)).build())

    def test_matches_sequential_fit(self):
        n, k = 9, 3
        a = ComputationGraph(self._conf()).init()
        b = ComputationGraph(self._conf()).init()
        a.fit(_iter(n))
        b.fitDataSet(_iter(n), stepsPerSync=k)
        _assert_tree_close(a._params, b._params)
        _assert_tree_close(a._upd_states, b._upd_states)
        assert a._iteration == b._iteration == n

    def test_multi_input_multidataset_iterator(self):
        from deeplearning4j_tpu.data.multidataset import MultiDataSet
        from deeplearning4j_tpu.nn import MergeVertex

        def conf():
            return (NeuralNetConfiguration.Builder().seed(3)
                    .updater(Sgd(0.1)).graphBuilder()
                    .addInputs("a", "b")
                    .addLayer("da", DenseLayer(nOut=8, activation="tanh"),
                              "a")
                    .addLayer("db", DenseLayer(nOut=8, activation="tanh"),
                              "b")
                    .addVertex("m", MergeVertex(), "da", "db")
                    .addLayer("out", OutputLayer(nOut=2,
                                                 activation="softmax"), "m")
                    .setOutputs("out")
                    .setInputTypes(InputType.feedForward(4),
                                   InputType.feedForward(3)).build())

        rng = np.random.RandomState(0)
        batches = [MultiDataSet(
            [rng.randn(8, 4).astype("float32"),
             rng.randn(8, 3).astype("float32")],
            [np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]])
            for _ in range(5)]

        class _It:
            def __init__(self):
                self.i = 0

            def reset(self):
                self.i = 0

            def hasNext(self):
                return self.i < len(batches)

            def next(self):
                self.i += 1
                return batches[self.i - 1]

        a = ComputationGraph(conf()).init()
        b = ComputationGraph(conf()).init()
        for ds in batches:
            a.fit(ds)
        b.fitDataSet(_It(), stepsPerSync=2)  # 2 blocks + ragged tail
        _assert_tree_close(a._params, b._params)
        assert b._iteration == 5

    def test_per_input_none_features_mask(self):
        """A masked input alongside an unmasked one ([mask, None]
        featuresMasks, supported by plain fit()) must stack — the None
        entry synthesizes all-ones instead of an object-dtype array."""
        from deeplearning4j_tpu.data.multidataset import MultiDataSet
        from deeplearning4j_tpu.nn import MergeVertex

        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(Sgd(0.1)).graphBuilder()
                .addInputs("a", "b")
                .addLayer("da", DenseLayer(nOut=8, activation="tanh"), "a")
                .addLayer("db", DenseLayer(nOut=8, activation="tanh"), "b")
                .addVertex("m", MergeVertex(), "da", "db")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                          "m")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4),
                               InputType.feedForward(3)).build())
        rng = np.random.RandomState(0)
        batches = [MultiDataSet(
            [rng.randn(8, 4).astype("float32"),
             rng.randn(8, 3).astype("float32")],
            [np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]],
            featuresMasks=[np.ones(8, "float32"), None])
            for _ in range(4)]

        class _It:
            def __init__(self):
                self.i = 0

            def reset(self):
                self.i = 0

            def hasNext(self):
                return self.i < len(batches)

            def next(self):
                self.i += 1
                return batches[self.i - 1]

        g = ComputationGraph(conf).init()
        g.fitDataSet(_It(), stepsPerSync=2)
        assert g._iteration == 4
        assert np.isfinite(g.score())


# ----------------------------------------------------------------------
# SameDiff
# ----------------------------------------------------------------------
class TestFitDataSetSameDiff:
    def _make(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig

        rs = np.random.RandomState(7)
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 8, 4)
        y = sd.placeHolder("y", jnp.float32, 8, 3)
        w = sd.var("w", (rs.randn(4, 3) * 0.1).astype("float32"))
        b = sd.var("b", np.zeros(3, dtype="float32"))
        logits = sd.nn.linear(x, w, b, name="logits")
        sd.loss.softmaxCrossEntropy(y, logits, name="loss")
        sd.setTrainingConfig(
            TrainingConfig.Builder().updater(Adam(learningRate=1e-2))
            .dataSetFeatureMapping("x").dataSetLabelMapping("y").build())
        return sd

    def _batches(self, n):
        out = []
        for i in range(n):
            rng = np.random.RandomState(i)
            out.append(DataSet(
                rng.rand(8, 4).astype("float32"),
                np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]))
        return out

    def test_matches_fit_history_and_params(self):
        batches = self._batches(7)  # 2 blocks of 3 + ragged tail of 1
        a, b = self._make(), self._make()
        h1 = a.fit(data=batches)

        class _It:
            def __init__(self):
                self.i = 0

            def reset(self):
                self.i = 0

            def hasNext(self):
                return self.i < len(batches)

            def next(self):
                self.i += 1
                return batches[self.i - 1]

        sent = RetraceSentinel(max_compiles=1).install_fit_dataset(b)
        h2 = b.fitDataSet(_It(), stepsPerSync=3)
        np.testing.assert_allclose(h1, h2, rtol=2e-5, atol=2e-6)
        # a reset-less plain iterable cannot run a second epoch — later
        # epochs would silently train zero batches; must fail loudly
        with pytest.raises(ValueError, match="resettable"):
            b.fitDataSet(iter(batches), stepsPerSync=3, epochs=2)
        np.testing.assert_allclose(np.asarray(a._arrays["w"]),
                                   np.asarray(b._arrays["w"]),
                                   rtol=2e-6, atol=2e-6)
        assert a._iteration == b._iteration == 7
        assert b._fit_dataset_syncs == 3  # 2 blocks + 1 tail batch
        assert sent.compiles("fit_dataset_loop") == 1


# ----------------------------------------------------------------------
# sharded: the 8-virtual-device mesh
# ----------------------------------------------------------------------
class TestFitDataSetSharded:
    def test_parallel_wrapper_parity_with_single_device(self):
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)

        n, k, B = 8, 4, 16  # batch divisible by the 8-device data axis
        a = MultiLayerNetwork(_mlp()).init()
        a.fit(_iter(n, batch=B))
        b = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(b, mesh=data_parallel_mesh())
        sent = RetraceSentinel(max_compiles=1).install_fit_dataset(pw)
        pw.fitDataSet(_iter(n, batch=B), stepsPerSync=k)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)
        assert pw._fit_dataset_syncs == n // k
        assert sent.compiles("fit_dataset_loop") == 1

    def test_int8_compression_runs(self):
        from deeplearning4j_tpu.parallel import (SharedTrainingMaster,
                                                 data_parallel_mesh)

        net = MultiLayerNetwork(_mlp()).init()
        tm = SharedTrainingMaster(net, mesh=data_parallel_mesh())
        tm.fitDataSet(_iter(4, batch=16), stepsPerSync=2)
        assert np.isfinite(net.score())
        assert net._iteration == 4

    def test_threshold_mode_k_loop_matches_per_batch(self):
        """ISSUE 11: the threshold step's error-feedback residual rides
        the donated updater-state carry, so the staged k-loop threads
        it — the k=2 trajectory must match per-batch fit() bitwise."""
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)

        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(),
                             gradient_compression="threshold",
                             threshold=1e-2)
        pw.fitDataSet(_iter(4, batch=16), stepsPerSync=2)
        assert np.isfinite(net.score())
        assert net._iteration == 4
        assert pw._fit_dataset_syncs == 2
        ref = MultiLayerNetwork(_mlp()).init()
        pr = ParallelWrapper(ref, mesh=data_parallel_mesh(),
                             gradient_compression="threshold",
                             threshold=1e-2)
        pr.fit(_iter(4, batch=16))
        for a, b in zip(jax.tree_util.tree_leaves(net._params),
                        jax.tree_util.tree_leaves(ref._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the residual carried out of the k-loop matches too
        for a, b in zip(jax.tree_util.tree_leaves(pw._residual[0]),
                        jax.tree_util.tree_leaves(pr._residual[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_parameter_averaging_rejected(self):
        from deeplearning4j_tpu.parallel import (
            ParameterAveragingTrainingMaster, data_parallel_mesh)

        net = MultiLayerNetwork(_mlp()).init()
        pam = ParameterAveragingTrainingMaster(net,
                                               mesh=data_parallel_mesh())
        with pytest.raises(ValueError, match="stepsPerSync"):
            pam.fitDataSet(_iter(4, batch=16), stepsPerSync=2)

    def test_indivisible_batch_rejected(self):
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)

        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh())
        with pytest.raises(ValueError, match="divisible"):
            pw.fitDataSet(_iter(4, batch=12), stepsPerSync=2)


# ----------------------------------------------------------------------
# ResilientFit(stepsPerSync=k)
# ----------------------------------------------------------------------
class TestFitDataSetResilient:
    pytestmark = pytest.mark.faults

    def test_block_parity_with_per_batch_guarded(self):
        from deeplearning4j_tpu.runtime.resilience import ResilientFit

        a = MultiLayerNetwork(_mlp()).init()
        ResilientFit(a).fit(_iter(8, batch=16), epochs=1)
        b = MultiLayerNetwork(_mlp()).init()
        ResilientFit(b).fit(_iter(8, batch=16), epochs=1, stepsPerSync=4)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)
        assert a._iteration == b._iteration == 8

    def test_skip_accounting_from_k_vector(self):
        from deeplearning4j_tpu.optimize import ResilienceListener
        from deeplearning4j_tpu.runtime.resilience import (FaultInjector,
                                                           ResilientFit)

        net = MultiLayerNetwork(_mlp()).init()
        events = ResilienceListener()
        net.setListeners(events)
        inj = FaultInjector().poisonStep(2).poisonStep(5)
        rf = ResilientFit(net, injector=inj)
        rf.fit(_iter(8, batch=16), epochs=1, stepsPerSync=4)
        assert rf.skippedSteps == 2
        assert [e for e in events.events if e[0] == "skip"] == [
            ("skip", 3, events.events[0][2]),
            ("skip", 6, events.events[1][2])]
        assert net._iteration == 8

    def test_consecutive_bad_aborts_mid_block(self):
        from deeplearning4j_tpu.runtime.resilience import (
            FaultInjector, NonFiniteStepError, ResilientFit)

        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().poisonStep(1, 2, 3)
        rf = ResilientFit(net, injector=inj, maxConsecutiveBadSteps=3)
        with pytest.raises(NonFiniteStepError):
            rf.fit(_iter(8, batch=16), epochs=1, stepsPerSync=4)
        assert rf.skippedSteps == 3

    def test_abort_mid_block_params_match_k1(self):
        """The abort threshold hit MID-block: the k=1 path raises before
        the block's remaining (good) steps ever train, so the device
        loop must freeze the carry from that step on — an aborted k>1
        run's params match the aborted k=1 run bitwise."""
        from deeplearning4j_tpu.runtime.resilience import (
            FaultInjector, NonFiniteStepError, ResilientFit)

        def run(steps_per_sync):
            net = MultiLayerNetwork(_mlp()).init()
            inj = FaultInjector().poisonStep(0, 1, 2)
            rf = ResilientFit(net, injector=inj, maxConsecutiveBadSteps=3)
            with pytest.raises(NonFiniteStepError):
                rf.fit(_iter(8, batch=16), epochs=1,
                       stepsPerSync=steps_per_sync)
            return net

        a, b = run(1), run(4)  # abort at step 3 of 4; step 4 is good
        assert a._iteration == b._iteration == 3
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=0, atol=0)  # bitwise

    def test_resume_mid_epoch_matches_uninterrupted(self, tmp_path):
        from deeplearning4j_tpu.runtime.resilience import (
            FaultInjector, Preemption, ResilientFit, RetryPolicy)

        fast = RetryPolicy(maxRetries=2, initialDelay=1e-4,
                           maxDelay=1e-3)
        # ground truth: uninterrupted k-block run, 2 epochs of 4 batches
        ref = MultiLayerNetwork(_mlp()).init()
        ResilientFit(ref, retryPolicy=fast).fit(
            _iter(4, batch=16), epochs=2, stepsPerSync=2)

        # killed at the block boundary after step 6 (epoch 1, block 1);
        # checkpoints land at block boundaries (saveEvery=2 == k)
        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().killAfterStep(5)
        rf = ResilientFit(net, tmp_path / "ck", saveEveryNIterations=2,
                          retryPolicy=fast, injector=inj)
        with pytest.raises(Preemption):
            rf.fit(_iter(4, batch=16), epochs=2, stepsPerSync=2)
        assert net._iteration == 6

        # restart: resumes from the step-6 checkpoint mid-epoch and
        # finishes on the SAME trajectory
        net2 = MultiLayerNetwork(_mlp()).init()
        rf2 = ResilientFit(net2, tmp_path / "ck", saveEveryNIterations=2,
                           retryPolicy=fast)
        rf2.fit(_iter(4, batch=16), epochs=2, stepsPerSync=2)
        assert net2._iteration == 8
        np.testing.assert_allclose(ref.params().toNumpy(),
                                   net2.params().toNumpy(),
                                   rtol=0, atol=0)  # bitwise
