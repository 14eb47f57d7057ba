"""SPMD collective-safety verifier tests (analysis/collectives.py,
pass 7 — ISSUE 14).

Matrix: every COL01-COL06 code triggered by a deliberately broken
input (the PR 2/3 pattern), the safe twins of each hazard proven
unflagged (the CG while_loop, symmetric cond branches, well-formed
rings), the declarative CollectiveContract covering ALL FOUR
gradient_compression modes + the ZeRO-composed path + the canonical
linalg routines, and the back-compat proof that
linalg.collective_counts (now a re-export of the hoisted walker)
reports the identical counts.

Cost discipline: every check here is ONE jax.make_jaxpr trace — zero
XLA compiles. The trainer-step subjects are traced once per module
(module-scoped fixture) and the zero-compile claim is proven live with
CompileWatch over the session AOT cache.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.analysis import collectives as colan
from deeplearning4j_tpu.analysis.diagnostics import ALL_CODES
from jax import shard_map
from deeplearning4j_tpu.parallel.mesh import build_mesh, DATA_AXIS

DP = 8


@pytest.fixture(scope="module")
def dmesh():
    return build_mesh({DATA_AXIS: DP}, jax.devices())


def _smap(body, mesh, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _codes(report):
    return set(report.codes())


# ======================================================================
# signature extraction + collective_counts back-compat
# ======================================================================

class TestSignature:
    def test_ordered_sites_with_context_and_bytes(self, dmesh):
        def body(x):
            g = lax.all_gather(x, DATA_AXIS, tiled=True)

            def step(i, c):
                return c + lax.ppermute(
                    c, DATA_AXIS, [(j, (j + 1) % DP) for j in range(DP)])

            l = lax.fori_loop(0, 4, step, x)
            return lax.psum(g.sum() + l.sum(), DATA_AXIS)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        sig = colan.collective_signature(
            f, jnp.ones((DP, 4), jnp.float32))
        prims = [s.prim for s in sig]
        assert prims == ["all_gather", "ppermute", "psum"]
        # the ppermute site sits inside the fori_loop's scan, inside
        # the shard_map
        pp = sig.sites[1]
        assert "shard_map" in pp.context and "scan" in pp.context
        assert pp.perm is not None and len(pp.perm) == DP
        # per-chip bytes: the all_gather output is [DP, 4] f32
        assert sig.sites[0].out_bytes == DP * 4 * 4
        assert sig.axes() == {DATA_AXIS}

    def test_collective_counts_reexport_unchanged(self, dmesh):
        """linalg.collective_counts is the hoisted walker — identical
        counts, sites-not-dispatches semantics preserved."""
        from deeplearning4j_tpu import linalg

        def body(x):
            def step(i, c):
                return c + lax.ppermute(
                    c, DATA_AXIS, [(j, (j + 1) % DP) for j in range(DP)])

            return lax.psum(lax.fori_loop(0, 3, step, x), DATA_AXIS)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(None, None))
        x = jnp.ones((DP, 4))
        counts = linalg.collective_counts(f, x)
        # the in-loop ppermute is ONE site even over 3 iterations
        assert counts == {"ppermute": 1, "psum": 1}
        assert counts == colan.collective_signature(f, x).counts()


# ======================================================================
# COL01 — collectives under data-dependent control flow
# ======================================================================

class TestCol01ControlFlow:
    def test_divergent_cond_predicate_flags(self, dmesh):
        def body(x):
            # predicate from the SHARDED block: replicas disagree
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v, DATA_AXIS),
                            lambda v: v, x)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert "COL01" in _codes(rep), rep.format()

    def test_uniform_pred_asymmetric_branches_flag(self, dmesh):
        def body(x):
            s = lax.psum(x, DATA_AXIS)
            return lax.cond(s.sum() > 0,
                            lambda v: lax.pmax(v, DATA_AXIS),
                            lambda v: v, x)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert "COL01" in _codes(rep), rep.format()

    def test_uniform_pred_symmetric_branches_clean(self, dmesh):
        def body(x):
            s = lax.psum(x, DATA_AXIS)
            return lax.cond(s.sum() > 0,
                            lambda v: lax.pmax(v, DATA_AXIS),
                            lambda v: lax.pmax(-v, DATA_AXIS), x)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert rep.ok, rep.format()

    def test_divergent_while_predicate_flags(self, dmesh):
        def body(x):
            def cond(c):
                return c[0] < 10.0  # local partial sum: diverges

            def step(c):
                return (c[0] + c[1].sum()
                        + lax.psum(c[1], DATA_AXIS).sum() * 0.0, c[1])

            out, _ = lax.while_loop(cond, step, (x.sum(), x))
            return out

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert "COL01" in _codes(rep), rep.format()

    def test_reduced_while_predicate_clean(self, dmesh):
        """The CG shape: every term reaching the predicate passed
        through a psum — replica-uniform, no flag."""
        def body(x):
            def cond(c):
                return (c[0] < 10.0) & (c[2] < 5)

            def step(c):
                acc = c[0] + lax.psum(c[1], DATA_AXIS).sum()
                return (acc.astype(c[0].dtype), c[1], c[2] + 1)

            out, _, _ = lax.while_loop(
                cond, step, (jnp.zeros((), x.dtype), x, jnp.int32(0)))
            return out

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert rep.ok, rep.format()

    def test_real_cg_lstsq_clean(self, dmesh):
        """The REAL distributed CG (linalg/solvers._build_lstsq): psum
        inside a convergence-predicated while_loop, proven safe — and
        matching its declared contract."""
        from deeplearning4j_tpu.linalg.solvers import _build_lstsq

        f = _build_lstsq(dmesh, DATA_AXIS, None, 0.0, 1e-6, 16)
        rep = colan.verify_program(
            f, jnp.ones((4 * DP, 4)), jnp.ones((4 * DP, 1)),
            mesh=dmesh, contract=colan.linalg_contract("lstsq"))
        assert rep.ok, rep.format()
        assert rep.signature.counts() == {"psum": 3}

    def test_divergent_trip_count_poisons_downstream(self, dmesh):
        """A collective-FREE while whose trip count diverges (bounded
        by axis_index) must poison its outputs: a second loop bounded
        by the first one's result deadlocks mid-psum, and COL01 must
        see through the laundering (code-review regression)."""
        def body(x):
            i0 = lax.axis_index(DATA_AXIS)
            trips = lax.while_loop(lambda i: i < i0,
                                   lambda i: i + 1, jnp.int32(0))

            def cond(c):
                return c[1] < trips

            def step(c):
                return (c[0] + lax.psum(x, DATA_AXIS).sum(), c[1] + 1)

            out, _ = lax.while_loop(
                cond, step, (jnp.zeros((), x.dtype), jnp.int32(0)))
            return out

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert "COL01" in _codes(rep), rep.format()

    def test_hazard_inside_scan_reported_once(self, dmesh):
        """One hazard inside a scan body yields ONE diagnostic, not
        one per fixpoint iteration (code-review regression — the
        bench/CI gates count errors)."""
        def body(x):
            def step(c, _):
                out = lax.cond(x.sum() > 0,
                               lambda v: lax.psum(v, DATA_AXIS),
                               lambda v: v, x)
                return c + out.sum(), None

            acc, _ = lax.scan(step, jnp.zeros((), x.dtype),
                              jnp.arange(3))
            return acc

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        col01 = [d for d in rep.errors if d.code == "COL01"]
        assert len(col01) == 1, rep.format()

    def test_static_fori_loop_clean(self, dmesh):
        """A static-trip fori_loop (lowers to scan) communicates
        safely — the SUMMA ring shape."""
        def body(x):
            def step(i, c):
                return c + lax.ppermute(
                    c, DATA_AXIS, [(j, (j + 1) % DP) for j in range(DP)])

            return lax.fori_loop(0, DP, step, x)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)))
        assert rep.ok, rep.format()


# ======================================================================
# COL02 / COL06 — axis sanity and ring shape
# ======================================================================

class TestCol02Axes:
    def test_axis_absent_from_requested_mesh(self, dmesh):
        def body(x):
            return lax.psum(x, DATA_AXIS)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(None, None))
        # the program reduces over "data"; validate against a mesh
        # that names its axes differently (the drifted-deploy shape)
        rep = colan.verify_program(f, jnp.ones((DP, 4)),
                                   mesh={"rows": DP})
        assert "COL02" in _codes(rep), rep.format()

    def test_axes_present_clean(self, dmesh):
        def body(x):
            return lax.psum(x, DATA_AXIS)

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(None, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)), mesh=dmesh)
        assert rep.ok, rep.format()

    def test_signature_only_path(self):
        sig = colan.CollectiveSignature([colan.CollectiveSite(
            "psum", ("nodes",), "float32", 64, ("shard_map",))])
        rep = colan.check_signature(sig, mesh_axes={"data", "model"})
        assert _codes(rep) == {"COL02"}


class TestCol06Rings:
    def _ring_site(self, perm):
        return colan.CollectiveSignature([colan.CollectiveSite(
            "ppermute", (DATA_AXIS,), "float32", 64, (), perm=perm)])

    def test_duplicate_destination_flags(self):
        rep = colan.check_signature(
            self._ring_site(((0, 1), (1, 1), (2, 3))),
            mesh_axes={DATA_AXIS})
        assert "COL06" in _codes(rep)

    def test_duplicate_source_flags(self):
        rep = colan.check_signature(
            self._ring_site(((0, 1), (0, 2))), mesh_axes={DATA_AXIS})
        assert "COL06" in _codes(rep)

    def test_self_cycle_flags(self):
        rep = colan.check_signature(
            self._ring_site(((0, 0), (1, 2), (2, 1))),
            mesh_axes={DATA_AXIS})
        assert any(d.code == "COL06" and "self-cycle" in d.message
                   for d in rep.errors), rep.format()

    def test_proper_ring_clean_from_real_trace(self, dmesh):
        def body(x):
            return lax.ppermute(
                x, DATA_AXIS, [(j, (j + 1) % DP) for j in range(DP)])

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)), mesh=dmesh)
        assert rep.ok, rep.format()

    def test_broken_ring_flagged_from_real_trace(self, dmesh):
        # (j, j) instead of (j, j+1): the classic ring-arithmetic slip
        def body(x):
            return lax.ppermute(
                x, DATA_AXIS, [(j, j) for j in range(DP)])

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
        rep = colan.verify_program(f, jnp.ones((DP, 4)), mesh=dmesh)
        assert "COL06" in _codes(rep), rep.format()


# ======================================================================
# COL03 — quantized-accumulator agreement
# ======================================================================

class TestCol03AccDtype:
    def _sig(self, dtype):
        return colan.CollectiveSignature([colan.CollectiveSite(
            "psum", (DATA_AXIS,), dtype, 64, ())])

    def test_int16_correct_through_dp256(self):
        assert colan.check_acc_dtype(self._sig("int16"), 8).ok
        assert colan.check_acc_dtype(self._sig("int16"), 256).ok

    def test_int16_overflows_past_dp256(self):
        rep = colan.check_acc_dtype(self._sig("int16"), 512)
        assert "COL03" in _codes(rep), rep.format()

    def test_int32_required_and_accepted_past_dp256(self):
        assert colan.check_acc_dtype(self._sig("int32"), 512).ok
        # int32 at dp=8 is over-wide vs the shared definition: drift
        rep = colan.check_acc_dtype(self._sig("int32"), 8)
        assert "COL03" in _codes(rep)

    def test_bill_disagreement_flags(self):
        rep = colan.check_acc_dtype(self._sig("int16"), 8,
                                    billed_acc_bytes=4)
        assert any(d.code == "COL03" and "bill" in d.where
                   for d in rep.errors), rep.format()

    def test_bill_shares_the_runtime_definition(self):
        """compressed_hlo_collective_bytes derives its accumulator
        width from _acc_dtype — the three-party agreement by
        construction (one 100-elem int8 leaf: 8 B scale pmax + 2n acc
        psum at the dp-correct width)."""
        from deeplearning4j_tpu.parallel.sharding import (
            compressed_hlo_collective_bytes,
        )

        assert compressed_hlo_collective_bytes([100], 8, "int8") \
            == 8 + 2 * 100 * 2
        assert compressed_hlo_collective_bytes([100], 512, "int8") \
            == 8 + 2 * 100 * 4

    def test_quantized_contract_demands_integer_reduce(self, dmesh):
        """A program whose COUNTS satisfy the int8 contract but whose
        reductions all run in float (the silent-widening regression)
        fails COL03 — the count alone must not green-light it
        (code-review regression)."""
        def body(x):
            s = lax.pmax(x, DATA_AXIS)                    # "scale"
            a = lax.psum(x, DATA_AXIS)                    # float, not int!
            loss = lax.psum(x.sum(), DATA_AXIS)
            return s.sum() + a.sum() + loss

        f = _smap(body, dmesh, (P(DATA_AXIS, None),), P())
        rep = colan.verify_program(
            f, jnp.ones((DP, 4), jnp.float32), mesh=dmesh, dp=DP,
            contract=colan.compression_contract("int8", 1))
        assert "COL03" in _codes(rep), rep.format()

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_lowered_step_acc_dtype_verified(self, compressed_subjects):
        """The REAL int8 step's integer psum dtype agrees with
        expected_acc_dtype(dp) — checked by verify_program's COL03 leg
        (dp=8: int16)."""
        sig = compressed_subjects["int8"]["signature"]
        int_psums = [s for s in sig if s.prim == "psum"
                     and s.dtype.startswith("int")]
        assert int_psums, "int8 step lost its integer psum"
        assert all(s.dtype == "int16" for s in int_psums)
        assert colan.check_acc_dtype(sig, DP).ok


# ======================================================================
# COL04 — CollectiveContract drift
# ======================================================================

class TestCol04Contracts:
    def test_count_drift_flags(self):
        c = colan.compression_contract("int8", 4)
        got = {"pmax": 4, "psum": 3}   # lost the loss pmean + one leaf
        rep = c.check(got)
        assert "COL04" in _codes(rep), rep.format()

    def test_undeclared_collective_flags(self):
        c = colan.compression_contract("threshold", 2)
        got = {"all_gather": 4, "psum": 1, "ppermute": 1}
        rep = c.check(got)
        assert any("undeclared" in d.message for d in rep.errors), \
            rep.format()

    def test_dense_contract_rejects_explicit_collectives(self):
        c = colan.compression_contract(None, 4)
        assert not c.check({"psum": 1}).ok
        assert c.check({}).ok

    def test_range_bounds(self):
        c = colan.CollectiveContract("r", {"psum": (2, None)})
        assert c.check({"psum": 5}).ok
        assert not c.check({"psum": 1}).ok

    def test_axis_restriction(self):
        c = colan.CollectiveContract("a", {"psum": 1},
                                     axes=(DATA_AXIS,))
        sig = colan.CollectiveSignature([colan.CollectiveSite(
            "psum", ("model",), "float32", 4, ())])
        assert not c.check(sig).ok

    def test_unknown_mode_and_routine_raise(self):
        with pytest.raises(ValueError, match="gradient_compression"):
            colan.compression_contract("sparse", 4)
        with pytest.raises(ValueError, match="linalg routine"):
            colan.linalg_contract("qr")


# ======================================================================
# COL05 — bill-vs-measured divergence
# ======================================================================

class TestCol05Bill:
    def test_within_tolerance_clean(self):
        assert colan.check_bill(105, 100, rel=0.10).ok
        assert colan.check_bill(100, 100).ok

    def test_divergence_flags_both_directions(self):
        assert "COL05" in _codes(colan.check_bill(115, 100, rel=0.10))
        assert "COL05" in _codes(colan.check_bill(85, 100, rel=0.10))

    def test_zero_bill_with_traffic_flags(self):
        rep = colan.check_bill(512, 0)
        assert "COL05" in _codes(rep)
        assert colan.check_bill(0, 0).ok


# ======================================================================
# declared contracts over the REAL trainer + linalg programs
# (one trace per subject, zero compiles — CompileWatch-proven)
# ======================================================================

def _tiny_mlp():
    from deeplearning4j_tpu.nn import (
        DenseLayer, InputType, NeuralNetConfiguration, OutputLayer, Sgd,
    )

    return (NeuralNetConfiguration.Builder()
            .seed(7).updater(Sgd(0.05)).activation("tanh").list()
            .layer(DenseLayer(nOut=16))
            .layer(DenseLayer(nOut=16))
            .layer(OutputLayer(nOut=4, activation="softmax"))
            .setInputType(InputType.feedForward(8)).build())


@pytest.fixture(scope="module")
def compressed_subjects(dmesh):
    """One TRACE per gradient_compression mode (+ the ZeRO-composed
    form): the signature subjects every contract test shares. Proven
    compile-free against the session AOT cache."""
    from deeplearning4j_tpu.nn import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.runtime.aot import CompileWatch

    rng = np.random.RandomState(0)
    x = rng.randn(2 * DP, 8).astype("float32")
    y = np.eye(4, dtype="float32")[rng.randint(0, 4, 2 * DP)]
    specs = (
        (None, None, {}),
        ("int8", "int8", {}),
        ("block_int8", "block_int8", {}),
        ("threshold", "threshold", {"threshold": 1e-2}),
        ("block_int8+zero", "block_int8",
         {"weight_update": "sharded", "min_shard_size": 64}),
        ("hierarchical", "hierarchical",
         {"threshold": 1e-2, "compressionGroupSize": 4}),
    )
    out = {}
    with CompileWatch() as watch:
        for name, mode, kw in specs:
            net = MultiLayerNetwork(_tiny_mlp()).init()
            pw = ParallelWrapper(net, mesh=dmesh,
                                 gradient_compression=mode, **kw)
            pw._place_replicated()
            leaves = jtu.tree_leaves(net._params)
            args = (net._params, net._upd_states, net._states,
                    jnp.asarray(0, jnp.int32),
                    pw._shard_batch(jnp.asarray(x)),
                    pw._shard_batch(jnp.asarray(y)),
                    jax.random.key(0), None, None)
            out[name] = {
                "net": net, "pw": pw, "n_leaves": len(leaves),
                "n_eligible": sum(1 for l in leaves
                                  if pw._zero is not None
                                  and pw._zero.eligible(l)),
                "signature": colan.collective_signature(
                    pw.trainStep(), *args),
                "args": args,
            }
    # make_jaxpr is trace-only: the whole subject build must pay ZERO
    # XLA compiles (the session-cache budget obligation in ISSUE 14)
    watch.assert_no_compiles("collective-signature subject build")
    return out


class TestTrainerContracts:
    """COL04 over all four gradient_compression modes + the composed
    ZeRO path — the scattered hand asserts now live HERE, as declared
    contracts (the dryrun checks the same declarations)."""

    @pytest.mark.parametrize("mode", [None, "int8", "block_int8",
                                      "threshold"])
    def test_mode_matches_declared_contract(self, mode,
                                            compressed_subjects):
        sub = compressed_subjects[mode]
        c = colan.compression_contract(mode, sub["n_leaves"])
        rep = c.check(sub["signature"])
        assert rep.ok, rep.format()

    def test_composed_zero_contract(self, compressed_subjects):
        sub = compressed_subjects["block_int8+zero"]
        assert sub["n_eligible"] > 0
        c = colan.compression_contract("block_int8", sub["n_leaves"],
                                       n_eligible=sub["n_eligible"])
        rep = c.check(sub["signature"])
        assert rep.ok, rep.format()

    def test_hierarchical_matches_declared_contract(
            self, compressed_subjects):
        """COL04 over the 2-hop hierarchical step (the tier-1 gate the
        tentpole adds): the declared two-hop signature — per leaf one
        hop-1 reduce_scatter, three all_gathers (hop-2 idx + value,
        hop-3 fan-back), one scale pmax, plus the single loss pmean —
        must match the traced step EXACTLY, per-hop counts and axes."""
        sub = compressed_subjects["hierarchical"]
        L = sub["n_leaves"]
        c = colan.compression_contract("hierarchical", L)
        rep = c.check(sub["signature"])
        assert rep.ok, rep.format()
        # exact per-hop counts, asserted directly so a miscounted
        # contract cannot mask a miscounted program
        counts = sub["signature"].counts()
        assert counts["reduce_scatter"] == L          # hop 1 per leaf
        assert counts["all_gather"] == 3 * L          # hop 2 (x2) + hop 3
        assert counts["pmax"] == L                    # hop-1 scale sync
        assert counts["psum"] == 1                    # the loss pmean
        # the two hops ride DIFFERENT axes of the 2-D mesh
        hop1_axes = {ax for s in sub["signature"]
                     if s.prim in ("reduce_scatter", "psum_scatter")
                     for ax in s.axes}
        gather_axes = {ax for s in sub["signature"]
                       if s.prim == "all_gather" for ax in s.axes}
        assert hop1_axes == {"intra"}
        assert gather_axes == {"group", "intra"}

    def test_hierarchical_full_verify_clean(self, compressed_subjects):
        """One-stop COL01/02/03/06 + contract over the hierarchical
        step. dp is the GROUP size: the hop-1 integer sum spans only the
        group's lanes, so the COL03 accumulator-dtype rule keys off
        group_size, not the full data-parallel degree."""
        sub = compressed_subjects["hierarchical"]
        pw = sub["pw"]
        rep = colan.verify_program(
            pw.trainStep(), *sub["args"], mesh=pw._hmesh,
            dp=pw.compression_group,
            contract=colan.compression_contract(
                "hierarchical", sub["n_leaves"]))
        assert rep.ok, rep.format()

    def test_full_verify_clean_per_mode(self, compressed_subjects,
                                        dmesh):
        """The one-stop pass (COL01/02/03/06 + contract) over the int8
        and threshold steps: the package's own trainers must be
        hazard-free."""
        for mode in ("int8", "threshold"):
            sub = compressed_subjects[mode]
            rep = colan.verify_program(
                sub["pw"].trainStep(), *sub["args"], mesh=dmesh, dp=DP,
                contract=colan.compression_contract(
                    mode, sub["n_leaves"]))
            assert rep.ok, (mode, rep.format())

    def test_drifted_program_fails_contract(self, compressed_subjects,
                                            dmesh):
        """A wrapped step that sneaks ONE extra collective in is
        caught — the silent-communication-change regression the
        contracts exist for."""
        sub = compressed_subjects["int8"]

        def drifted(*args):
            out = sub["pw"].trainStep()(*args)
            extra = _smap(lambda v: lax.pmax(v, DATA_AXIS), dmesh,
                          (P(),), P())(jnp.zeros(()))
            return (*out[:-1], out[-1] + extra)

        c = colan.compression_contract("int8", sub["n_leaves"])
        rep = c.check(colan.collective_signature(drifted, *sub["args"]))
        assert "COL04" in _codes(rep), rep.format()


class TestLinalgContracts:
    """COL04 over the canonical distributed-linalg routines (>= 3 —
    acceptance): SUMMA 2-D GEMM, Gram, covariance, transpose-B matmul
    and the CG lstsq (the latter in TestCol01ControlFlow)."""

    @pytest.fixture(scope="class")
    def mesh2(self):
        return build_mesh({"data": 4, "model": 2}, jax.devices())

    def test_matmul2d(self, mesh2):
        from deeplearning4j_tpu.linalg.distributed import _summa_2d_body

        f = _smap(functools.partial(_summa_2d_body, row_axis="data",
                                    col_axis="model", n_cols=2),
                  mesh2, (P("data", "model"),) * 2, P("data", "model"))
        rep = colan.verify_program(
            f, jnp.ones((8, 8)), jnp.ones((8, 4)), mesh=mesh2,
            contract=colan.linalg_contract("matmul2d"))
        assert rep.ok, rep.format()

    def test_matmul1d(self, dmesh):
        from deeplearning4j_tpu.linalg.distributed import _summa_1d_body

        f = _smap(functools.partial(_summa_1d_body, row_axis=DATA_AXIS,
                                    n_rows=DP),
                  dmesh, (P(DATA_AXIS, None),) * 2, P(DATA_AXIS, None))
        rep = colan.verify_program(
            f, jnp.ones((DP * 2, DP * 2)), jnp.ones((DP * 2, 4)),
            mesh=dmesh, contract=colan.linalg_contract("matmul1d"))
        assert rep.ok, rep.format()

    def test_gram_and_covariance(self, dmesh):
        from deeplearning4j_tpu.linalg.distributed import _build_gram

        rep = colan.verify_program(
            _build_gram(dmesh, DATA_AXIS, None), jnp.ones((DP * 2, 4)),
            mesh=dmesh, contract=colan.linalg_contract("gram"))
        assert rep.ok, rep.format()

    def test_routine_drift_is_caught(self, dmesh):
        """gram checked against the WRONG declaration (matmul2d's)
        fails — contracts discriminate between routines."""
        from deeplearning4j_tpu.linalg.distributed import _build_gram

        rep = colan.verify_program(
            _build_gram(dmesh, DATA_AXIS, None), jnp.ones((DP * 2, 4)),
            mesh=dmesh, contract=colan.linalg_contract("matmul2d"))
        assert "COL04" in _codes(rep), rep.format()


# ======================================================================
# acceptance: every COL code fires on broken input, clean corpus passes
# ======================================================================

@pytest.mark.lint
def test_acceptance_all_col_codes_covered(dmesh):
    triggered = set()

    def bad_cond(x):
        return lax.cond(x.sum() > 0,
                        lambda v: lax.psum(v, DATA_AXIS),
                        lambda v: v, x)

    f = _smap(bad_cond, dmesh, (P(DATA_AXIS, None),), P(DATA_AXIS, None))
    triggered |= _codes(colan.verify_program(f, jnp.ones((DP, 4))))

    def psum_only(x):
        return lax.psum(x, DATA_AXIS)

    f2 = _smap(psum_only, dmesh, (P(DATA_AXIS, None),), P(None, None))
    triggered |= _codes(colan.verify_program(f2, jnp.ones((DP, 4)),
                                             mesh={"rows": DP}))

    sig16 = colan.CollectiveSignature([colan.CollectiveSite(
        "psum", (DATA_AXIS,), "int16", 64, ())])
    triggered |= _codes(colan.check_acc_dtype(sig16, 512))
    triggered |= _codes(colan.compression_contract("int8", 4)
                        .check({"pmax": 4, "psum": 3}))
    triggered |= _codes(colan.check_bill(150, 100))
    triggered |= _codes(colan.check_signature(
        colan.CollectiveSignature([colan.CollectiveSite(
            "ppermute", (DATA_AXIS,), "float32", 8, (),
            perm=((0, 0),))]), mesh_axes={DATA_AXIS}))

    assert {"COL01", "COL02", "COL03", "COL04", "COL05",
            "COL06"} <= triggered, triggered
    assert triggered <= set(ALL_CODES)
