"""Per-op HBM ledger + roofline floor (util/hbm_ledger.py).

The ledger is validated against XLA's own cost model: on this backend
the ENTRY-walk total must reproduce compiled.cost_analysis()["bytes
accessed"] (observed exact on XLA:CPU — both charge each instruction
its operands + results). The floor is validated arithmetically and as
a genuine lower bound on the compiled step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.util.hbm_ledger import (boundary_activation_elems,
                                                ledger, ledger_for_compiled,
                                                train_step_floor)


def _cost_bytes(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float((ca or {}).get("bytes accessed", 0.0))


class TestLedger:
    def test_single_matmul_accounting(self):
        f = jax.jit(lambda x, w: x @ w)
        one = jnp.ones((1024, 1024), jnp.float32)  # conftest enables x64
        c = f.lower(one, one).compile()
        led = ledger(c.as_text())
        # 3 x 4 MiB buffers (x, w, out) — exact up to tiny epilogue ops
        assert led["total_bytes"] == pytest.approx(3 * 1024 * 1024 * 4,
                                                   rel=0.05)
        assert "dot" in led["by_opcode"]

    def test_extended_dtypes_and_unknown_dtype_raises(self):
        # TPU modules carry dtypes CPU ones never show (u16 rng state,
        # f8 buffers): they must be priced, and anything NOT in the
        # table must raise rather than silently rank as free
        led = ledger("ENTRY e {\n"
                     "  %a = u16[1024]{0} iota(), iota_dimension=0\n"
                     "  %b = f8e4m3fn[64,64]{1,0} convert(%a)\n"
                     "}")
        by = led["by_opcode"]
        assert by["iota"] == 2048
        assert by["convert"] == 64 * 64 + 2048
        with pytest.raises(ValueError, match="unknown HLO dtype"):
            ledger("ENTRY e {\n  %a = q77[8]{0} iota()\n}")

    def test_subbyte_dtypes_priced_packed(self):
        # s4 packs two per byte (ShapeUtil::ByteSizeOf): 1001 elems ->
        # ceil(1001/2) = 501 bytes, not 1001
        led = ledger("ENTRY e {\n  %a = s4[1001]{0} iota()\n}")
        assert led["by_opcode"]["iota"] == 501

    def test_lenet_step_matches_xla_cost_analysis(self):
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.zoo import LeNet

        net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                    dataType=DataType.BFLOAT16).init()
        B = 64
        x = jnp.ones((B, 1, 28, 28), jnp.bfloat16)
        y = jnp.asarray(np.eye(10, dtype="float32")[np.zeros(B, int)])
        comp = jax.jit(net._train_step).lower(
            net._params, net._upd_states, net._states,
            jnp.asarray(0, jnp.int32), x, y, jax.random.key(0),
            None, None).compile()
        led = ledger_for_compiled(comp, top=5)
        assert led["total_bytes"] == pytest.approx(_cost_bytes(comp),
                                                   rel=0.01)
        # ranked descending, fusions dominate a fused conv net
        tops = [r["bytes"] for r in led["top"]]
        assert tops == sorted(tops, reverse=True)
        assert max(led["by_opcode"], key=led["by_opcode"].get) == "fusion"
        # every row decomposes: bytes = out + in
        for r in led["top"]:
            assert r["bytes"] == r["out_bytes"] + r["in_bytes"]


class TestFloor:
    def _lenet(self):
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.zoo import LeNet

        return LeNet(numClasses=10, inputShape=(1, 28, 28),
                     dataType=DataType.BFLOAT16).init()

    def test_terms_arithmetic_and_param_count(self):
        net = self._lenet()
        fl = train_step_floor(net, (64, 1, 28, 28), optimizer_slots=1)
        assert fl["floor_bytes"] == sum(fl["terms"].values())
        assert fl["param_count"] == net.numParams()
        P, cb, pb = fl["param_count"], 2, 4
        assert fl["terms"]["params_master_rw"] == 2 * P * pb
        assert fl["terms"]["params_compute_copy"] == 3 * P * cb
        assert fl["terms"]["grads_wr"] == 2 * P * pb
        assert fl["terms"]["input_read"] == 64 * 28 * 28 * cb
        assert fl["terms"]["activations_4touch"] == \
            4 * fl["boundary_activation_elems"] * cb

    def test_fp32_net_has_no_phantom_cast_copy(self):
        """compute dtype == param dtype: no separate cast copy exists,
        so the floor must charge direct master reads instead (else the
        'floor' can exceed real fp32 programs)."""
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.zoo import LeNet

        net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                    dataType=DataType.FLOAT).init()
        fl = train_step_floor(net, (64, 1, 28, 28), optimizer_slots=1)
        P = fl["param_count"]
        assert fl["terms"]["params_compute_copy"] == 2 * P * 4

    def test_floor_is_a_lower_bound_on_compiled_step(self):
        net = self._lenet()
        B = 64
        x = jnp.ones((B, 1, 28, 28), jnp.bfloat16)
        y = jnp.asarray(np.eye(10, dtype="float32")[np.zeros(B, int)])
        comp = jax.jit(net._train_step).lower(
            net._params, net._upd_states, net._states,
            jnp.asarray(0, jnp.int32), x, y, jax.random.key(0),
            None, None).compile()
        fl = train_step_floor(net, (B, 1, 28, 28), optimizer_slots=1)
        assert fl["floor_bytes"] < _cost_bytes(comp)

    def test_boundaries_on_computation_graph(self):
        """The spy-based shape recording must work on ComputationGraph
        (the flagship ResNet-50 is one) and restore layer.forward."""
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.nn import Nesterovs
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(numClasses=10, inputShape=(3, 32, 32),
                       updater=Nesterovs(0.1, 0.9),
                       dataType=DataType.BFLOAT16,
                       dataFormat="NHWC").init()
        acts = boundary_activation_elems(net, (2, 32, 32, 3))
        # ResNet-50: 53 convs + stem pool
        assert len(acts) == 54
        assert all(a > 0 for a in acts)
        # spies removed: class methods are back in charge
        assert all("forward" not in l.__dict__
                   for n in net.conf.nodes.values()
                   if (l := getattr(n, "payload", None)) is not None)

    def test_resnet50_b128_headline_floor(self):
        """Pin the headline floor the bench reports: ResNet-50 b128
        NHWC bf16 + Nesterovs. Recomputed here from the model so the
        analytic floor (11.85 GB/step) is reproducible by CI, not
        copied."""
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.nn import Nesterovs
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(numClasses=1000, inputShape=(3, 224, 224),
                       updater=Nesterovs(0.1, 0.9),
                       dataType=DataType.BFLOAT16,
                       dataFormat="NHWC").init()
        fl = train_step_floor(net, (128, 224, 224, 3), optimizer_slots=1)
        assert fl["param_count"] == 25_557_032
        assert fl["floor_bytes"] == pytest.approx(11.85e9, rel=0.01)
        assert 46.8e9 / fl["floor_bytes"] == pytest.approx(3.95, abs=0.1)
