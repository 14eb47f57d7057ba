"""Hierarchical 2-hop sparse gradient exchange (ROADMAP item 4, the
dp128-wall tentpole): dense/block_int8 psum_scatter inside each node
group, fixed-capacity Strom threshold exchange between group leaders,
all-gather fan-back — wire bytes scale with capacity x groups instead of
capacity x dp.

Proof layers on the virtual 8-device CPU mesh:

- mesh factorization: hierarchical_mesh splits the 1-D data mesh into
  (group, intra) with intra innermost (contiguous devices), and rejects
  indivisible / degenerate group sizes naming the constraint;
- subject parity: gradient_compression="hierarchical" trains to 25%
  loss parity with the dense psum at dp8 with ONE compile
  (RetraceSentinel), for both hop-1 encodings and both group sizes;
- semantics: each node group acts as ONE virtual Strom replica (hop 1
  computes the group MEAN), so the transmitted +-tau has the same
  effective magnitude as the flat threshold mode's;
- resilience: ResilientFit mid-epoch preempt+resume matches the
  uninterrupted run bitwise — the per-shard error-feedback residual +
  live tau ride the checkpoint exactly as the flat carry does;
- the bytes bill: measured collective bytes of the compiled dp8 step
  land within 10% of compressed_hlo_collective_bytes(group_size=...),
  and the analytic wire bill shows the crossover moved past dp128;
- loud rejections: unknown/indivisible group sizes, cross-mode
  compressionGroupSize, sharded-update composition and cross-mode
  carry restores all raise naming the constraint.
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
from deeplearning4j_tpu.data import DataSetIterator
from deeplearning4j_tpu.parallel import (
    GROUP_AXIS, INTRA_AXIS, ParallelWrapper, SharedTrainingMaster,
    SharedTrainingMasterBuilder, compressed_hlo_collective_bytes,
    compressed_wire_bytes, data_parallel_mesh, default_compression_group,
    hierarchical_mesh, hierarchical_shard_elems,
)

# this module compiles several dp8 step variants; drop jax's global
# caches at teardown so they don't starve the zoo fits that run last
from conftest import drop_jax_caches_fixture

_drop_jax_caches_after_module = drop_jax_caches_fixture()

DP = 8


def _mesh():
    return data_parallel_mesh()


def _mlp(seed=42, nin=256, h1=512, h2=256, nout=8, updater=None,
         lr=1e-2, act="relu"):
    return (NeuralNetConfiguration.Builder()
            .seed(seed).updater(updater or Adam(lr)).activation(act)
            .list()
            .layer(DenseLayer(nOut=h1))
            .layer(DenseLayer(nOut=h2))
            .layer(OutputLayer(nOut=nout, activation="softmax"))
            .setInputType(InputType.feedForward(nin))
            .build())


def _data(n=64, nin=256, nout=8, seed=0):
    rng = np.random.RandomState(seed)
    yi = rng.randint(0, nout, n)
    x = (np.eye(nout)[yi] @ rng.randn(nout, nin)
         + 0.1 * rng.randn(n, nin)).astype("float32")
    return x, np.eye(nout, dtype="float32")[yi]


def _assert_tree_equal(a, b):
    for la, lb in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ----------------------------------------------------------------------
# the (group, intra) mesh factorization
# ----------------------------------------------------------------------
class TestHierarchicalMesh:
    def test_factorization_shape_and_device_order(self):
        m = _mesh()
        h = hierarchical_mesh(m, 4)
        assert h.axis_names == (GROUP_AXIS, INTRA_AXIS)
        assert dict(h.shape) == {GROUP_AXIS: 2, INTRA_AXIS: 4}
        # intra innermost: one group's chips are CONTIGUOUS in the
        # original data-mesh order (the fastest-ICI domain on hardware)
        flat = np.asarray(m.devices).reshape(-1)
        fact = np.asarray(h.devices)
        for gi in range(2):
            assert list(fact[gi]) == list(flat[gi * 4:(gi + 1) * 4])

    def test_group_size_must_divide(self):
        with pytest.raises(ValueError, match="divisor"):
            hierarchical_mesh(_mesh(), 3)

    def test_one_chip_group_points_at_flat_threshold(self):
        with pytest.raises(ValueError,
                           match="gradient_compression='threshold'"):
            hierarchical_mesh(_mesh(), 1)

    def test_needs_pure_data_mesh(self):
        from deeplearning4j_tpu.parallel import build_mesh

        m2 = build_mesh({"data": 4, "model": 2})
        with pytest.raises(ValueError, match="1-D pure data-parallel"):
            hierarchical_mesh(m2, 2)

    def test_default_group_prefers_two_plus_groups(self):
        assert default_compression_group(8) == 4
        assert default_compression_group(128) == 8
        assert default_compression_group(32) == 8
        assert default_compression_group(4) == 2
        # dp=2 and prime dp admit no (>=2 chips) x (>=2 groups)
        # factorization — loud rejection naming the flat fallback,
        # not a silent single-group degeneration
        for dp in (2, 7):
            with pytest.raises(ValueError,
                               match="no hierarchical factorization"):
                default_compression_group(dp)

    def test_single_group_rejected(self):
        with pytest.raises(ValueError, match="single node group"):
            hierarchical_mesh(_mesh(), DP)
        with pytest.raises(ValueError, match="2 <= group_size <= dp/2"):
            compressed_wire_bytes(4000, DP, "hierarchical", group_size=DP)

    def test_shard_elems_pads_to_group_multiple(self):
        assert hierarchical_shard_elems(1000, 4) == 250
        assert hierarchical_shard_elems(1001, 4) == 251
        assert hierarchical_shard_elems(3, 4) == 1


# ----------------------------------------------------------------------
# subject parity: dp8 training vs the dense psum, one compile
# ----------------------------------------------------------------------
@pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
@pytest.mark.parametrize("intra_mode,group", [("block_int8", 4),
                                              (None, 4),
                                              ("block_int8", 2)])
def test_hierarchical_trains_to_loss_parity(intra_mode, group):
    """The acceptance gate at dp8: the 2-hop exchange tracks the dense
    run within the documented 25% tolerance (docs/PARALLEL.md), for
    both hop-1 encodings and both swept group sizes, with ONE compile
    per config (RetraceSentinel)."""
    from deeplearning4j_tpu.analysis.retrace import RetraceSentinel

    x, y = _data(DP * 2, nin=32)
    losses = {}
    for mode in (None, "hierarchical"):
        net = MultiLayerNetwork(
            _mlp(seed=3, nin=32, h1=64, h2=32, updater=Sgd(0.1),
                 act="tanh")).init()
        kw = {} if mode is None else {
            "threshold": 1e-1, "encodingCapacity": 1.0,
            "compressionGroupSize": group,
            "intraGroupCompression": intra_mode}
        pw = ParallelWrapper(net, mesh=_mesh(),
                             gradient_compression=mode, **kw)
        sentinel = RetraceSentinel(max_compiles=1)
        pw._place_replicated()
        pw._jit = jax.jit(sentinel.wrap(pw.trainStep(), name="step"),
                          donate_argnums=(0, 1, 2))
        traj = []
        for _ in range(10):
            pw.fit(x, y)
            traj.append(net.score())
        losses[mode] = traj
        assert np.isfinite(traj[-1]), (mode, traj)
        assert sentinel.compiles("step") == 1
    dense, hier = losses[None], losses["hierarchical"]
    assert all(b < a for a, b in zip(hier, hier[1:])), hier
    assert abs(hier[-1] - dense[-1]) <= 0.25 * max(dense[-1], 0.5), (
        f"hierarchical({intra_mode}, g{group}) loss {hier[-1]} vs dense "
        f"{dense[-1]} — outside the documented 25% parity tolerance")


def test_group_is_one_virtual_replica():
    """Hop 1 computes the group MEAN, so with every chip fed the SAME
    batch the hierarchical step at (dense intra, capacity 1, huge tau
    ... tiny tau) reduces to the flat threshold step's math: the two
    modes' parameters match to f32 roundoff after a step."""
    x, y = _data(DP * 2, nin=32)
    # identical per-replica batches: tile one shard to all chips
    xs = np.tile(x[:2], (DP, 1))
    ys = np.tile(y[:2], (DP, 1))
    params = {}
    for mode, kw in (
            ("threshold", {}),
            ("hierarchical", {"compressionGroupSize": 4,
                              "intraGroupCompression": None})):
        net = MultiLayerNetwork(
            _mlp(seed=3, nin=32, h1=64, h2=32, updater=Sgd(0.1))).init()
        pw = ParallelWrapper(net, mesh=_mesh(), gradient_compression=mode,
                             threshold=5e-2, encodingCapacity=1.0, **kw)
        pw.fit(xs, ys)
        params[mode] = net._params
    for lt, lh in zip(jtu.tree_leaves(params["threshold"]),
                      jtu.tree_leaves(params["hierarchical"])):
        np.testing.assert_allclose(np.asarray(lt), np.asarray(lh),
                                   rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# resilience: bitwise preempt/resume with the per-shard residual
# ----------------------------------------------------------------------
class TestResilientHierarchical:
    def _wrap(self, seed=42):
        net = MultiLayerNetwork(
            _mlp(seed, nin=32, h1=64, h2=32, nout=4,
                 updater=Sgd(0.25))).init()
        return net, ParallelWrapper(net, mesh=_mesh(),
                                    gradient_compression="hierarchical",
                                    threshold=1e-2,
                                    compressionGroupSize=4)

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_mid_epoch_resume_bitwise_with_residuals(self, tmp_path):
        from deeplearning4j_tpu.runtime.resilience import (
            FaultInjector, Preemption, ResilientFit)

        X, Y = _data(DP * 12, nin=32, nout=4)

        def it():
            return DataSetIterator(X, Y, DP * 2)

        n1, w1 = self._wrap()
        ResilientFit(w1).fit(it(), epochs=2)

        d = str(tmp_path / "ck")
        n2, w2 = self._wrap()
        inj = FaultInjector().killAfterStep(7)
        with pytest.raises(Preemption):
            ResilientFit(w2, d, saveEveryNIterations=3,
                         injector=inj).fit(it(), epochs=2)
        n3, w3 = self._wrap()
        ResilientFit(w3, d, saveEveryNIterations=3).fit(it(), epochs=2)
        _assert_tree_equal(n1._params, n3._params)
        # the [groups, group, shard] residual and live tau came back —
        # without them the resumed trajectory could not be bitwise
        _assert_tree_equal(w1._residual[0], w3._residual[0])
        _assert_tree_equal(w1._residual[1], w3._residual[1])

    def test_residual_layout_is_per_chip_shard(self):
        X, Y = _data(DP * 2, nin=32, nout=4)
        net, pw = self._wrap()
        pw.fit(X, Y)
        ef, tau = pw._residual
        for p, r in zip(jtu.tree_leaves(net._params),
                        jtu.tree_leaves(ef)):
            m = hierarchical_shard_elems(int(np.prod(p.shape)), 4)
            assert r.shape == (2, 4, m)
        assert float(tau) == pytest.approx(1e-2)

    def test_cross_mode_carry_restore_raises(self):
        """A flat-threshold carry re-placed by a hierarchical wrapper
        (or vice versa) is refused naming the layout — silently
        device_putting the wrong residual shape would corrupt the
        step."""
        X, Y = _data(DP * 2, nin=32, nout=4)
        net, pw = self._wrap()
        pw.fit(X, Y)
        flat = ParallelWrapper(net, mesh=_mesh(),
                               gradient_compression="threshold",
                               threshold=1e-2)
        with pytest.raises(ValueError, match="incompatible"):
            flat._place_replicated()


# ----------------------------------------------------------------------
# the k-loop carry: fitDataSet(stepsPerSync=k)
# ----------------------------------------------------------------------
def test_fit_dataset_k_loop_carries_residual():
    X, Y = _data(DP * 8, nin=32)
    net = MultiLayerNetwork(
        _mlp(seed=3, nin=32, h1=64, h2=32, updater=Sgd(0.1))).init()
    pw = ParallelWrapper(net, mesh=_mesh(),
                         gradient_compression="hierarchical",
                         threshold=5e-2, encodingCapacity=1.0,
                         compressionGroupSize=4)
    pw.fitDataSet(DataSetIterator(X, Y, DP * 2), stepsPerSync=2,
                  epochs=2)
    assert np.isfinite(net.score())
    assert pw._fit_dataset_syncs == 4
    ef, _ = pw._residual
    # the residual actually accumulated through the staged k-loop
    assert any(np.abs(np.asarray(l)).max() > 0
               for l in jtu.tree_leaves(ef))


# ----------------------------------------------------------------------
# loud rejections + the STM / builder mapping (satellite)
# ----------------------------------------------------------------------
class TestValidationAndMapping:
    def _net(self):
        return MultiLayerNetwork(_mlp(nin=32, h1=64, h2=32)).init()

    def test_indivisible_group_raises(self):
        with pytest.raises(ValueError, match="divisor"):
            ParallelWrapper(self._net(), mesh=_mesh(),
                            gradient_compression="hierarchical",
                            compressionGroupSize=3)

    def test_group_size_with_other_mode_raises(self):
        with pytest.raises(ValueError, match="node-group size"):
            ParallelWrapper(self._net(), mesh=_mesh(),
                            gradient_compression="threshold",
                            compressionGroupSize=4)

    def test_sharded_update_rejected(self):
        with pytest.raises(ValueError, match="reduce-scatter form"):
            ParallelWrapper(self._net(), mesh=_mesh(),
                            gradient_compression="hierarchical",
                            compressionGroupSize=4,
                            weight_update="sharded")

    def test_unknown_intra_mode_raises(self):
        with pytest.raises(ValueError, match="intraGroupCompression"):
            ParallelWrapper(self._net(), mesh=_mesh(),
                            gradient_compression="hierarchical",
                            compressionGroupSize=4,
                            intraGroupCompression="int8")

    def test_nonpositive_tau_rejected(self):
        with pytest.raises(ValueError, match="tau"):
            ParallelWrapper(self._net(), mesh=_mesh(),
                            gradient_compression="hierarchical",
                            compressionGroupSize=4, threshold=0.0)

    def test_stm_maps_group_size(self):
        m = SharedTrainingMaster(self._net(), mesh=_mesh(),
                                 compressionGroupSize=4,
                                 thresholdAlgorithm=5e-2)
        assert m.gradient_compression == "hierarchical"
        assert m.compression_group == 4
        assert m.threshold == 5e-2

    def test_stm_group_size_with_other_mode_raises(self):
        with pytest.raises(ValueError, match="node-group size"):
            SharedTrainingMaster(self._net(), mesh=_mesh(),
                                 compressionGroupSize=4,
                                 gradient_compression="int8")

    def test_stm_default_group_from_dp(self):
        m = SharedTrainingMaster(self._net(), mesh=_mesh(),
                                 gradient_compression="hierarchical")
        assert m.compression_group == default_compression_group(DP) == 4
        assert m._n_groups == 2

    def test_builder_maps_group_size(self):
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer

        master = (SharedTrainingMasterBuilder()
                  .compressionGroupSize(4)
                  .thresholdAlgorithm(5e-2)
                  .intraGroupCompression(None)
                  .build())
        s = SparkDl4jMultiLayer(_mesh(), _mlp(nin=32, h1=64, h2=32),
                                master)
        m = s.getTrainingMaster()
        assert m.gradient_compression == "hierarchical"
        assert m.compression_group == 4
        assert m.intra_compression is None

    def test_builder_indivisible_group_raises_at_bind(self):
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer

        master = (SharedTrainingMasterBuilder()
                  .compressionGroupSize(5).build())
        with pytest.raises(ValueError, match="divisor"):
            SparkDl4jMultiLayer(_mesh(), _mlp(nin=32, h1=64, h2=32),
                                master)

    def test_sharding_plan_group_knob(self):
        from deeplearning4j_tpu.analysis.partitioning import ShardingPlan

        p = ShardingPlan(gradient_compression="hierarchical",
                         compression_group=4)
        assert p.compression_group == 4
        with pytest.raises(ValueError, match="node-group size"):
            ShardingPlan(gradient_compression="block_int8",
                         compression_group=4)
        with pytest.raises(ValueError, match="sharded"):
            ShardingPlan(gradient_compression="hierarchical",
                         weight_update="sharded")

    def test_par06_bills_both_hops(self):
        from deeplearning4j_tpu.analysis import validate_plan
        from deeplearning4j_tpu.analysis.partitioning import ShardingPlan

        r = validate_plan(_mlp(), {"data": 8}, batchSize=64,
                          plan=ShardingPlan(
                              gradient_compression="hierarchical",
                              compression_group=4))
        gc = r.plan["memory"]["grad_collective"]
        assert gc["mode"] == "hierarchical"
        assert gc["group_size"] == 4 and gc["groups"] == 2
        # the two-term bill: intra-group + leader-ring, separately
        assert gc["wire_bytes"] == \
            gc["intra_wire_bytes"] + gc["leader_wire_bytes"]
        assert 0 < gc["leader_wire_bytes"] < gc["intra_wire_bytes"]


# ----------------------------------------------------------------------
# the measured bytes gate (per-hop analytic bill vs the dp8 compile)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def compiled_hier_steps():
    """One dp8 compile per hop-1 encoding, shared by the bytes gates."""
    x, y = _data()
    out = {}
    for name, imode in (("block_int8", "block_int8"), ("dense", None)):
        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net, mesh=_mesh(),
                             gradient_compression="hierarchical",
                             threshold=1e-3, compressionGroupSize=4,
                             intraGroupCompression=imode)
        pw._place_replicated()
        pw._build_jit()
        xs = pw._shard_batch(jnp.asarray(x))
        ys = pw._shard_batch(jnp.asarray(y))
        low = pw._jit.lower(net._params, net._upd_states, net._states,
                            jnp.asarray(0, jnp.int32), xs, ys,
                            jax.random.key(0), None, None)
        out[name] = (net, pw, low.compile())
    return out


class TestMeasuredHierBytes:
    """The acceptance gate: per-hop analytic bill within 10% of the
    measured collective bytes on a dp8 compile — a lowering regression
    (hop 1 silently widening to f32, a hop dropping out) fails
    statically, not on a TPU window."""

    def _measured(self, compiled, net):
        from deeplearning4j_tpu.util.hbm_ledger import attribute_ledger

        rec = attribute_ledger(compiled, net=net, x_shape=(64, 256),
                               optimizer_slots=2, top=80)
        return sum(t["bytes"] for t in rec["bin_top"]["collective"])

    def _leaf_elems(self, net):
        return [int(np.prod(l.shape))
                for p in net._params for l in jtu.tree_leaves(p)]

    @pytest.mark.slow  # tier-1 budget (PR 21): 10 s on 8 CPU cores
    @pytest.mark.parametrize("name,imode", [("block_int8", "block_int8"),
                                            ("dense", None)])
    def test_within_10pct(self, name, imode, compiled_hier_steps):
        from deeplearning4j_tpu.analysis.collectives import check_bill

        net, pw, compiled = compiled_hier_steps[name]
        measured = self._measured(compiled, net)
        model = compressed_hlo_collective_bytes(
            self._leaf_elems(net), DP, "hierarchical",
            capacity=pw.encoding_capacity, group_size=4,
            intra_mode=imode)
        rep = check_bill(measured, model, rel=0.10,
                         where=f"hierarchical/{name}")
        assert rep.ok, rep.format()
