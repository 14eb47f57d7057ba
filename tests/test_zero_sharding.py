"""ZeRO-style cross-replica weight-update sharding (Xu et al.,
arXiv:2004.13336; parallel.sharding.ZeroShardedUpdate +
ParallelWrapper(weight_update="sharded")).

Four layers of proof on the virtual 8-device CPU mesh:

- trajectory parity: the sharded update trains the SAME trajectory as
  the replicated path on all three network types (MultiLayerNetwork,
  ComputationGraph, SameDiff), including the fitDataSet stepsPerSync
  staged-epoch path — bitwise where the backend reproduces the same
  reductions, and an Sgd power-of-two dryrun that MUST be bitwise (the
  forward/backward program is shared verbatim, so only update-math
  reassociation could ever differ; Sgd has none);
- layout: updater state is physically allocated in 1/dp flat shards,
  with the explicit replicate fallback (never pad) for leaves below
  min_shard_size or with sizes dp does not divide;
- the analytic bill: dp_weight_update_bytes(sharded=True) pinned to
  hand-computed LeNet/resnet_block figures, and the MEASURED collective
  weight_update bin + per-chip updater-state bytes of a compiled dp8
  step within 10% of it (the tier-1 bytes ceiling for the sharded
  path — XLA:CPU lowers the reduce-scatter as all-reduce + local slice,
  which is the 'all_reduce_gather' form of the bill);
- resilience: mid-epoch preemption + resume with sharded updater state
  is bitwise, and checkpoints hold the canonical full-shape layout so a
  sharded-mode save restores into any mode.
"""

import numpy as np
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork,
    DenseLayer, OutputLayer, Adam, Sgd,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.data import DataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.parallel import (
    ParallelWrapper, SharedTrainingMaster, ParameterAveragingTrainingMaster,
    ZeroShardedUpdate, data_parallel_mesh, dp_weight_update_bytes,
)

DP = 8


def _mesh():
    return data_parallel_mesh()


def _mlp(seed=42, nin=32, hidden=64, nout=4, updater=None):
    return (NeuralNetConfiguration.Builder()
            .seed(seed).updater(updater or Adam(1e-2)).activation("relu")
            .list()
            .layer(DenseLayer(nOut=hidden))
            .layer(OutputLayer(nOut=nout, activation="softmax"))
            .setInputType(InputType.feedForward(nin))
            .build())


def _data(n=64, nin=32, nout=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, nin).astype("float32")
    y = np.eye(nout, dtype="float32")[rng.randint(0, nout, n)]
    return x, y


def _leaves(tree):
    return [np.asarray(l) for l in jtu.tree_leaves(tree)]


def _assert_tree_equal(a, b):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(la, lb)


def _assert_tree_close(a, b, rtol=2e-6, atol=1e-7):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(la, lb, rtol=rtol, atol=atol)


# ----------------------------------------------------------------------
# trajectory parity
# ----------------------------------------------------------------------
class TestParityMultiLayer:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_fit_matches_replicated(self):
        x, y = _data()
        net_r = MultiLayerNetwork(_mlp()).init()
        pr = ParallelWrapper(net_r, mesh=_mesh())
        net_s = MultiLayerNetwork(_mlp()).init()
        ps = ParallelWrapper(net_s, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=256)
        for _ in range(4):
            pr.fit(x, y)
            ps.fit(x, y)
        # the forward/backward program is IDENTICAL (same GSPMD step);
        # only update-math reassociation could differ — on this backend
        # the trajectories come out bitwise, and must stay ulp-close
        _assert_tree_close(net_r._params, net_s._params)

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_fit_dataset_steps_per_sync_composes(self):
        X, Y = _data(4 * 16)
        net_r = MultiLayerNetwork(_mlp()).init()
        ParallelWrapper(net_r, mesh=_mesh()).fitDataSet(
            DataSetIterator(X, Y, 16), stepsPerSync=2)
        net_s = MultiLayerNetwork(_mlp()).init()
        ps = ParallelWrapper(net_s, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=256)
        ps.fitDataSet(DataSetIterator(X, Y, 16), stepsPerSync=2)
        assert ps._fit_dataset_syncs == 2          # ⌈4/2⌉ blocks
        assert net_s.getIterationCount() == 4
        _assert_tree_close(net_r._params, net_s._params)
        # the staged k-loop carries the SHARDED updater state
        specs = {str(l.sharding.spec)
                 for l in jtu.tree_leaves(net_s._upd_states)}
        assert "PartitionSpec('data',)" in specs

    def test_power_of_two_sgd_bitwise(self):
        """The ISSUE's exactness bar: with power-of-two values and an
        Sgd update (no reassociable update math) the sharded trajectory
        must be BITWISE the replicated one."""
        rng = np.random.RandomState(3)
        x = (2.0 ** rng.randint(-3, 3, (64, 32))).astype("float32") \
            * rng.choice([-1.0, 1.0], (64, 32)).astype("float32")
        y = np.eye(4, dtype="float32")[rng.randint(0, 4, 64)]
        nets = []
        for mode in ("replicated", "sharded"):
            net = MultiLayerNetwork(_mlp(updater=Sgd(0.5))).init()
            pw = ParallelWrapper(net, mesh=_mesh(), weight_update=mode,
                                 min_shard_size=64)
            for _ in range(3):
                pw.fit(x, y)
            nets.append(net)
        _assert_tree_equal(nets[0]._params, nets[1]._params)


class TestParityGraph:
    def _conf(self, seed=9):
        return (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Adam(1e-2)).activation("relu").graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer(nOut=64), "in")
                .addLayer("out", OutputLayer(nOut=4, activation="softmax",
                                             lossFunction="mcxent"), "d")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(32)).build())

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_fit_and_fit_dataset_match_replicated(self):
        X, Y = _data(4 * 16)
        g_r = ComputationGraph(self._conf()).init()
        ParallelWrapper(g_r, mesh=_mesh()).fitDataSet(
            DataSetIterator(X, Y, 16), stepsPerSync=2)
        g_s = ComputationGraph(self._conf()).init()
        ws = ParallelWrapper(g_s, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=256)
        ws.fitDataSet(DataSetIterator(X, Y, 16), stepsPerSync=2)
        _assert_tree_close(g_r._params, g_s._params)
        specs = {str(l.sharding.spec)
                 for l in jtu.tree_leaves(g_s._upd_states)}
        assert "PartitionSpec('data',)" in specs


class TestParitySameDiff:
    def _make(self):
        from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig

        rs = np.random.RandomState(7)
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 8, 32)
        y = sd.placeHolder("y", jnp.float32, 8, 4)
        w = sd.var("w", (rs.randn(32, 64) * 0.1).astype("float32"))
        b = sd.var("b", np.zeros(64, dtype="float32"))
        w2 = sd.var("w2", (rs.randn(64, 4) * 0.1).astype("float32"))
        h = sd.nn.relu(sd.nn.linear(x, w, b, name="h0"), name="h")
        logits = sd.nn.linear(h, w2, None, name="logits")
        sd.loss.softmaxCrossEntropy(y, logits, name="loss")
        sd.setTrainingConfig(
            TrainingConfig.Builder().updater(Adam(learningRate=1e-2))
            .dataSetFeatureMapping("x").dataSetLabelMapping("y").build())
        return sd

    def _batches(self, n):
        out = []
        for i in range(n):
            r = np.random.RandomState(i)
            out.append(DataSet(
                r.rand(8, 32).astype("float32"),
                np.eye(4, dtype="float32")[r.randint(0, 4, 8)]))
        return out

    class _It:
        def __init__(self, bs):
            self.bs, self.i = bs, 0

        def reset(self):
            self.i = 0

        def hasNext(self):
            return self.i < len(self.bs)

        def next(self):
            b = self.bs[self.i]
            self.i += 1
            return b

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_fit_matches_replicated(self):
        a = self._make()
        h1 = a.fit(data=self._batches(4))
        b = self._make().shardWeightUpdate(_mesh(), min_shard_size=128)
        h2 = b.fit(data=self._batches(4))
        np.testing.assert_allclose(h1, h2, rtol=1e-6)
        _assert_tree_close(
            {n: a._arrays[n] for n in ("w", "b", "w2")},
            {n: b._arrays[n] for n in ("w", "b", "w2")})
        # state allocated sharded from init
        specs = {str(l.sharding.spec)
                 for l in jtu.tree_leaves(b._train_state)}
        assert "PartitionSpec('data',)" in specs

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_fit_dataset_steps_per_sync(self):
        a = self._make()
        h1 = a.fitDataSet(self._It(self._batches(4)), stepsPerSync=2)
        b = self._make().shardWeightUpdate(_mesh(), min_shard_size=128)
        h2 = b.fitDataSet(self._It(self._batches(4)), stepsPerSync=2)
        assert b._fit_dataset_syncs == 2
        np.testing.assert_allclose(h1, h2, rtol=1e-6)
        _assert_tree_close(
            {n: a._arrays[n] for n in ("w", "b", "w2")},
            {n: b._arrays[n] for n in ("w", "b", "w2")})

    def test_updater_state_save_restore_canonical(self, tmp_path):
        from deeplearning4j_tpu.autodiff import SameDiff

        b = self._make().shardWeightUpdate(_mesh(), min_shard_size=128)
        b.fit(data=self._batches(2))
        p = str(tmp_path / "sd.zip")
        b.save(p, saveUpdaterState=True)
        # the checkpoint holds the canonical full-shape layout: restores
        # into a REPLICATED-mode run and continues the same trajectory
        c = SameDiff.load(p, loadUpdaterState=True)
        c.setTrainingConfig(b._tc)
        c._iteration = b._iteration
        h_r = c.fit(data=self._batches(1))
        h_s = b.fit(data=self._batches(1))
        np.testing.assert_allclose(h_r, h_s, rtol=1e-6)


# ----------------------------------------------------------------------
# eligibility / layout edge cases
# ----------------------------------------------------------------------
class TestEligibilityAndLayout:
    def test_eligibility_rule(self):
        z = ZeroShardedUpdate(_mesh(), min_shard_size=64)
        assert z.dp == DP
        assert z.eligible(jnp.zeros((8, 16)))          # 128 % 8 == 0
        assert not z.eligible(jnp.zeros((63,)))        # below min
        assert not z.eligible(jnp.zeros((9, 9)))       # 81 % 8 != 0
        # leading dim NOT divisible by dp is fine — the flat view
        # shards the total element count, not the leading dim
        assert z.eligible(jnp.zeros((5, 64)))          # 320 % 8 == 0

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_indivisible_leaf_replicates_never_pads(self):
        """A large leaf whose SIZE dp does not divide takes the explicit
        replicate fallback: full-shape state, replicated placement, and
        training still matches the replicated path."""
        x, y = _data(nin=9, seed=1)
        # W1 is 9x63 = 567 elems: 567 % 8 != 0 -> replicated fallback
        conf = lambda: _mlp(nin=9, hidden=63)
        net_r = MultiLayerNetwork(conf()).init()
        ParallelWrapper(net_r, mesh=_mesh()).fit(x, y)
        net_s = MultiLayerNetwork(conf()).init()
        ps = ParallelWrapper(net_s, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=64)
        ps.fit(x, y)
        _assert_tree_close(net_r._params, net_s._params)
        w_state = [l for l in jtu.tree_leaves(net_s._upd_states[0])
                   if l.size == 9 * 63]
        assert w_state and all(
            l.shape == (9, 63)
            and str(l.sharding.spec) == "PartitionSpec()"
            for l in w_state)

    def test_vector_leaves_stay_replicated_below_min_shard(self):
        x, y = _data()
        net = MultiLayerNetwork(_mlp()).init()
        ps = ParallelWrapper(net, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=256)
        ps.fit(x, y)
        for s in net._upd_states:
            for leaf in jtu.tree_leaves(s):
                if leaf.size < 256:  # biases (64, 4): replicated
                    assert str(leaf.sharding.spec) == "PartitionSpec()"
                else:                # weight moments: 1/dp flat shards
                    assert leaf.ndim == 1
                    assert str(leaf.sharding.spec) == \
                        "PartitionSpec('data',)"
                    shard = leaf.addressable_shards[0].data
                    assert shard.shape[0] == leaf.size // DP

    def test_state_allocated_sharded_from_init(self):
        """Fresh nets allocate the moments directly in 1/dp shards —
        the measured per-chip bytes match the analytic resident bill
        exactly (this is the big-optimizer HBM win)."""
        net = MultiLayerNetwork(_mlp()).init()
        ps = ParallelWrapper(net, mesh=_mesh(), weight_update="sharded",
                             min_shard_size=256)
        ps._place_replicated()
        z = ps._zero
        measured = z.per_chip_state_bytes(net._upd_states)
        elig = rep = 0
        for p in net._params:
            for leaf in jtu.tree_leaves(p):
                n = int(np.prod(leaf.shape))
                if z.eligible(leaf):
                    elig += n
                else:
                    rep += n
        expected = (2 * elig // DP + 2 * rep) * 4  # Adam: 2 fp32 slots
        assert measured == expected

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_rewrapping_replicated_uninstalls_the_hook(self):
        """A net trained under a sharded-mode wrapper, re-wrapped
        replicated (or by ParameterAveragingTrainingMaster), sheds the
        ZeRO hook and flat-view state instead of silently keeping the
        sharded update against the old mesh — and the trajectory still
        matches an all-replicated twin (the unview is lossless)."""
        x, y = _data()
        net = MultiLayerNetwork(_mlp()).init()
        ParallelWrapper(net, mesh=_mesh(), weight_update="sharded",
                        min_shard_size=256).fit(x, y)
        ParallelWrapper(net, mesh=_mesh()).fit(x, y)
        assert net._update_impl is None
        shapes = {tuple(l.shape)
                  for l in jtu.tree_leaves(net._upd_states)}
        assert (32, 64) in shapes  # canonical, not flat views
        ref = MultiLayerNetwork(_mlp()).init()
        pr = ParallelWrapper(ref, mesh=_mesh())
        pr.fit(x, y)
        pr.fit(x, y)
        _assert_tree_close(net._params, ref._params)
        # PATM on the ex-sharded net trains instead of dying in tracing
        ParameterAveragingTrainingMaster(net, mesh=_mesh()).fit(x, y)

    def test_trainer_rejections(self):
        net = MultiLayerNetwork(_mlp()).init()
        with pytest.raises(ValueError, match="replicated.*sharded"):
            ParallelWrapper(net, mesh=_mesh(), weight_update="zero")
        # ISSUE 11: int8/block_int8 now COMPOSE with the sharded update
        # (compressed reduce-scatter); only threshold cannot
        for gc in ("int8", "block_int8"):
            pw = ParallelWrapper(net, mesh=_mesh(),
                                 weight_update="sharded",
                                 gradient_compression=gc)
            assert pw._zero is not None
        with pytest.raises(ValueError, match="threshold"):
            ParallelWrapper(net, mesh=_mesh(), weight_update="sharded",
                            gradient_compression="threshold")
        with pytest.raises(ValueError, match="ParallelWrapper"):
            ParameterAveragingTrainingMaster(net, mesh=_mesh(),
                                             weight_update="sharded")
        # SharedTrainingMaster: the sharded update keeps the int8
        # default — the two features stack now
        m = SharedTrainingMaster(net, mesh=_mesh(),
                                 weight_update="sharded")
        assert m.gradient_compression == "int8"


# ----------------------------------------------------------------------
# the analytic bill (hand-computed figures) + the measured CI gate
# ----------------------------------------------------------------------
class TestAnalyticBill:
    def test_lenet_hand_computed(self):
        # LeNet (analysis.hbm build_subject): 431,080 params, fp32
        # grads G = 1,724,320 B; Nesterovs: S = G. dp = 8.
        G = 431080 * 4
        rec = dp_weight_update_bytes(G, dp=8, opt_state_bytes=G,
                                     sharded=True)
        assert rec["mode"] == "sharded"
        assert rec["reduce_scatter_bytes"] == 7 * G // 8 == 1508780
        assert rec["all_gather_bytes"] == 1508780
        assert rec["update_bytes"] == 5 * G // 8 == 1077700
        assert rec["opt_state_resident_bytes"] == G // 8 == 215540
        assert rec["collective_wire_bytes"] == 2 * 1508780
        assert rec["hlo_collective_bytes"]["reduce_scatter"] == \
            (G + G // 8) * 2
        assert rec["hlo_collective_bytes"]["all_reduce_gather"] == \
            2 * G + G + G // 8
        # the replicated-vs-sharded saving the ledger's weight_update
        # bin exists to prove
        assert rec["sharding_saves_bytes"] == 5 * G - 5 * G // 8

    def test_resnet_block_hand_computed(self):
        # resnet_block subject: 10,602 params, G = 42,408 B, dp = 4
        G = 10602 * 4
        rec = dp_weight_update_bytes(G, dp=4, opt_state_bytes=G,
                                     sharded=True)
        assert rec["reduce_scatter_bytes"] == 3 * G // 4 == 31806
        assert rec["update_bytes"] == 5 * G // 4 == 53010
        assert rec["opt_state_resident_bytes"] == 10602
        rep = dp_weight_update_bytes(G, dp=4, opt_state_bytes=G)
        assert rep["mode"] == "replicated"
        assert rep["update_bytes"] == 5 * G == 212040
        assert rep["opt_state_resident_bytes"] == G
        assert rep["allreduce_bytes"] == 2 * 3 * G // 4

    def test_replicated_mode_keys_unchanged(self):
        G = 400
        rec = dp_weight_update_bytes(G, dp=4)
        assert rec["allreduce_bytes"] == 2 * 3 * G // 4
        assert rec["update_replicated_bytes"] == 5 * G
        assert rec["update_sharded_bytes"] == 5 * G // 4
        assert rec["sharding_saves_bytes"] == 5 * G - 5 * G // 4


class TestPlanFactor:
    def test_par06_factor_and_tp_heavy_honesty(self):
        """The PAR06 weight_update_sharding factor divides optimizer
        residency by the EXACT effective per-leaf factor — and on a
        tp-heavy mesh (tp > dp) it drops below 1, charging the ZeRO
        1/dp layout's true (larger) residency instead of clamping to
        the cheaper tp placement."""
        from deeplearning4j_tpu.analysis import validate_plan
        from deeplearning4j_tpu.analysis.partitioning import ShardingPlan

        conf = _mlp(nin=256, hidden=512, nout=8)
        dp8 = validate_plan(conf, {"data": 8}, batchSize=64,
                            plan=ShardingPlan(
                                weight_update="sharded",
                                weight_update_min_shard=1024))
        base = validate_plan(conf, {"data": 8}, batchSize=64)
        m_s, m_r = dp8.plan["memory"], base.plan["memory"]
        assert 1 < m_s["weight_update_sharding"] <= 8
        assert m_s["optimizer_state_bytes"] < m_r["optimizer_state_bytes"]

        tp = validate_plan(conf, {"data": 2, "model": 8}, batchSize=64,
                           plan=ShardingPlan(
                               weight_update="sharded",
                               weight_update_min_shard=1024))
        tp_base = validate_plan(conf, {"data": 2, "model": 8},
                                batchSize=64)
        assert tp.plan["memory"]["weight_update_sharding"] < 1
        assert tp.plan["memory"]["optimizer_state_bytes"] > \
            tp_base.plan["memory"]["optimizer_state_bytes"]

    def test_par03_warns_indivisible_only(self):
        """dp-indivisible leaves warn PAR03; below-min-shard leaves
        replicate silently (the intended default for biases)."""
        from deeplearning4j_tpu.analysis import validate_plan
        from deeplearning4j_tpu.analysis.partitioning import ShardingPlan

        conf = _mlp(nin=9, hidden=63)  # W1 = 567 elems: % 8 != 0
        r = validate_plan(conf, {"data": 8}, batchSize=64,
                          plan=ShardingPlan(weight_update="sharded",
                                            weight_update_min_shard=64))
        wu = [d for d in r.diagnostics
              if d.code == "PAR03" and "weight-update" in d.where]
        # W1 = 9x63 = 567 and W2 = 63x4 = 252: both indivisible by 8
        assert len(wu) == 2
        assert any("567" in d.message for d in wu)
        clean = validate_plan(_mlp(), {"data": 8}, batchSize=64,
                              plan=ShardingPlan(
                                  weight_update="sharded",
                                  weight_update_min_shard=256))
        assert not [d for d in clean.diagnostics
                    if d.code == "PAR03" and "weight-update" in d.where]


@pytest.fixture(scope="module")
def sharded_step_subject():
    """One dp8 compile each of the replicated and sharded MLP train
    steps, shared by the measured-bin gates below."""
    from deeplearning4j_tpu.parallel import dp_weight_update_bytes  # noqa

    rng = np.random.RandomState(0)
    B = 64
    x = rng.randn(B, 256).astype("float32")
    y = np.eye(8, dtype="float32")[rng.randint(0, 8, B)]

    def conf():
        return (NeuralNetConfiguration.Builder()
                .seed(42).updater(Adam(1e-2)).activation("relu")
                .list()
                .layer(DenseLayer(nOut=512))
                .layer(DenseLayer(nOut=256))
                .layer(OutputLayer(nOut=8, activation="softmax"))
                .setInputType(InputType.feedForward(256))
                .build())

    out = {}
    for mode in ("replicated", "sharded"):
        net = MultiLayerNetwork(conf()).init()
        pw = ParallelWrapper(net, mesh=_mesh(), weight_update=mode,
                             min_shard_size=1024)
        pw._place_replicated()
        pw._build_jit()
        xs = pw._shard_batch(jnp.asarray(x))
        ys = pw._shard_batch(jnp.asarray(y))
        low = pw._jit.lower(net._params, net._upd_states, net._states,
                            jnp.asarray(0, jnp.int32), xs, ys,
                            jax.random.key(0), None, None)
        out[mode] = (net, pw, low.compile())
    return out


class TestMeasuredWeightUpdateBin:
    """The tier-1 bytes gate for the sharded path: the compiled dp8
    step's measured collective weight_update bin and per-chip
    updater-state bytes must land within 10% of the
    dp_weight_update_bytes(sharded=True) bill."""

    def _collective_weight_update_bytes(self, compiled, net):
        from deeplearning4j_tpu.util.hbm_ledger import attribute_ledger

        rec = attribute_ledger(compiled, net=net, x_shape=(64, 256),
                               optimizer_slots=2, top=50)
        rows = [t for t in rec["bin_top"]["collective"]
                if "[weight_update]" in t["name"]]
        return sum(t["bytes"] for t in rows), rec

    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_sharded_bin_within_10pct_of_bill(self, sharded_step_subject):
        net, pw, compiled = sharded_step_subject["sharded"]
        measured, _ = self._collective_weight_update_bytes(compiled, net)
        z = pw._zero
        elig = rep = 0
        for p in net._params:
            for leaf in jtu.tree_leaves(p):
                n = int(np.prod(leaf.shape)) * 4
                if z.eligible(leaf):
                    elig += n
                else:
                    rep += n
        bill = dp_weight_update_bytes(elig, dp=DP, opt_state_bytes=2 * elig,
                                      sharded=True)
        # XLA:CPU lowering: all-reduce + local slice + param all-gather
        # over the eligible bytes; replicate-fallback leaves keep the
        # plain 2G all-reduce. Gated through the reusable COL05 check
        # (analysis.collectives.check_bill, ISSUE 14).
        from deeplearning4j_tpu.analysis.collectives import check_bill

        model = bill["hlo_collective_bytes"]["all_reduce_gather"] \
            + 2 * rep
        rep_bill = check_bill(measured, model, rel=0.10,
                              where="zero sharded weight_update bin")
        assert rep_bill.ok, (
            f"{rep_bill.format()} — the ZeRO update's collective "
            "traffic regressed")

    def test_per_chip_state_within_10pct_of_bill(self,
                                                 sharded_step_subject):
        net, pw, _ = sharded_step_subject["sharded"]
        z = pw._zero
        measured = z.per_chip_state_bytes(net._upd_states)
        elig = rep = 0
        for p in net._params:
            for leaf in jtu.tree_leaves(p):
                n = int(np.prod(leaf.shape)) * 4
                if z.eligible(leaf):
                    elig += n
                else:
                    rep += n
        bill = dp_weight_update_bytes(elig, dp=DP, opt_state_bytes=2 * elig,
                                      sharded=True)
        model = bill["opt_state_resident_bytes"] + 2 * rep
        assert measured == pytest.approx(model, rel=0.10)

    def test_sharded_program_carries_the_gather(self,
                                                sharded_step_subject):
        """Program-structure proof: the sharded step all-gathers the
        fresh params; the replicated step has no param-scale
        all-gather at all."""
        _, _, comp_s = sharded_step_subject["sharded"]
        _, _, comp_r = sharded_step_subject["replicated"]
        assert " all-gather(" in comp_s.as_text()
        assert " all-gather(" not in comp_r.as_text()

    def test_sharded_total_not_worse_than_replicated(
            self, sharded_step_subject):
        """The whole point: per-replica HBM traffic of the sharded step
        must undercut the replicated step (the update touches 1/dp of
        the master/opt bytes; the extra all-gather costs less than the
        saved full-width update on this subject)."""
        from deeplearning4j_tpu.util.hbm_ledger import ledger_for_compiled

        _, _, comp_s = sharded_step_subject["sharded"]
        _, _, comp_r = sharded_step_subject["replicated"]
        ts = ledger_for_compiled(comp_s)["total_bytes"]
        tr = ledger_for_compiled(comp_r)["total_bytes"]
        assert ts < tr, (ts, tr)


# ----------------------------------------------------------------------
# resilience: sharded updater state through preempt/resume
# ----------------------------------------------------------------------
class TestResilientShardedResume:
    def _wrap(self, seed=42):
        net = MultiLayerNetwork(_mlp(seed)).init()
        return net, ParallelWrapper(net, mesh=_mesh(),
                                    weight_update="sharded",
                                    min_shard_size=256)

    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_mid_epoch_resume_bitwise(self, tmp_path):
        from deeplearning4j_tpu.runtime.resilience import (
            FaultInjector, Preemption, ResilientFit)

        X, Y = _data(8 * 16)

        def it():
            return DataSetIterator(X, Y, 16)

        n1, w1 = self._wrap()
        ResilientFit(w1).fit(it(), epochs=2)

        d = str(tmp_path / "ck")
        n2, w2 = self._wrap()
        inj = FaultInjector().killAfterStep(11)
        with pytest.raises(Preemption):
            ResilientFit(w2, d, saveEveryNIterations=3,
                         injector=inj).fit(it(), epochs=2)
        n3, w3 = self._wrap()
        ResilientFit(w3, d, saveEveryNIterations=3).fit(it(), epochs=2)
        _assert_tree_equal(n1._params, n3._params)
        # updater state bitwise too, compared in the canonical layout
        _assert_tree_equal(w1._unview_upd_states(n1._upd_states),
                           w3._unview_upd_states(n3._upd_states))

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_guarded_k_loop_matches_k1(self):
        """ResilientFit(stepsPerSync=2): the non-finite-guarded staged
        k-loop carries the SHARDED updater state and bitwise-matches the
        per-batch guarded path."""
        from deeplearning4j_tpu.runtime.resilience import ResilientFit

        X, Y = _data(8 * 16)
        n1, w1 = self._wrap()
        ResilientFit(w1).fit(DataSetIterator(X, Y, 16), epochs=1)
        n2, w2 = self._wrap()
        ResilientFit(w2).fit(DataSetIterator(X, Y, 16), epochs=1,
                             stepsPerSync=2)
        _assert_tree_equal(n1._params, n2._params)

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_plain_serializer_saves_canonical_layout(self, tmp_path):
        """net.save() (the npz ModelSerializer) applies the same
        canonical unview as the Orbax path."""
        x, y = _data()
        net, pw = self._wrap()
        pw.fit(x, y)
        p = str(tmp_path / "m.npz")
        net.save(p)
        restored = MultiLayerNetwork.load(p)
        _assert_tree_equal(pw._unview_upd_states(net._upd_states),
                           restored._upd_states)

    def test_checkpoint_holds_canonical_layout(self, tmp_path):
        from deeplearning4j_tpu.util.sharded_checkpoint import \
            ShardedModelSerializer

        x, y = _data()
        net, pw = self._wrap()
        pw.fit(x, y)
        p = str(tmp_path / "m")
        ShardedModelSerializer.writeModel(net, p)
        restored = ShardedModelSerializer.restore(p)
        # full param-shaped leaves, not flat shards: restores into any
        # mode, and re-sharding on resume is a lossless reshape
        for s, ref in zip(restored._upd_states, net._params):
            shapes = {tuple(l.shape) for l in jtu.tree_leaves(s)}
            assert all(len(sh) <= 2 for sh in shapes)
        _assert_tree_equal(restored._upd_states,
                           pw._unview_upd_states(net._upd_states))
