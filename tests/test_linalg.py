"""Distributed linear algebra (deeplearning4j_tpu/linalg, docs/LINALG.md):
mesh-sharded SUMMA GEMM / Gram / randomized SVD / CG least-squares on the
virtual 8-device CPU mesh — allclose parity vs single-device numpy, the
never-pad divisibility contract, the RetraceSentinel one-compile-per-shape
proof, the PAR04/PAR06 clean-plan gate, and the consumers (kmeans, LSH,
deepwalk, nn CONJUGATE_GRADIENT) routed through the new tier."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import linalg
from deeplearning4j_tpu.parallel import DATA_AXIS, MODEL_AXIS, build_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device CPU mesh")


@pytest.fixture(scope="module")
def mesh2():
    return build_mesh({DATA_AXIS: 4, MODEL_AXIS: 2})


@pytest.fixture(scope="module")
def mesh1():
    return build_mesh({DATA_AXIS: 8})


def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


class TestDistributedMatrix:
    def test_block_placement_and_metadata(self, mesh2):
        A = _rand((16, 24))
        dA = linalg.DistributedMatrix(A, mesh2, row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        assert dA.shape == (16, 24)
        assert dA.block_shape() == (4, 12)
        assert dA.per_chip_bytes() == 4 * 12 * 4
        np.testing.assert_array_equal(dA.toNumpy(), A)
        # the placed buffer really is distributed: device 0 holds a block
        shard0 = dA.jax().addressable_shards[0]
        assert shard0.data.shape == (4, 12)

    def test_never_pad_divisibility_contract(self, mesh1, mesh2):
        # the same PAR03 wording as parallel.sharding.shard_batch: an
        # uneven tiling must refuse, never silently pad
        with pytest.raises(ValueError, match="refusing to silently pad"):
            linalg.DistributedMatrix(_rand((10, 4)), mesh1,
                                     row_axis=DATA_AXIS)
        with pytest.raises(ValueError, match="PAR03"):
            linalg.DistributedMatrix(_rand((16, 3)), mesh2,
                                     row_axis=DATA_AXIS,
                                     col_axis=MODEL_AXIS)
        with pytest.raises(ValueError, match="PAR01"):
            linalg.DistributedMatrix(_rand((16, 4)), mesh1,
                                     row_axis="nope")
        # shape mismatches fail at dispatch with the shapes named, not
        # inside XLA lowering
        dA = linalg.DistributedMatrix(_rand((16, 8)), mesh1,
                                      row_axis=DATA_AXIS)
        dB = linalg.DistributedMatrix(_rand((16, 4)), mesh1,
                                      row_axis=DATA_AXIS)
        with pytest.raises(ValueError, match="shape mismatch"):
            linalg.matmul(dA, dB)

    def test_indarray_distribute_entry_point(self, mesh1):
        from deeplearning4j_tpu.ndarray import Nd4j

        A = _rand((16, 8))
        arr = Nd4j.create(A)
        dA = arr.distribute(mesh1)
        assert isinstance(dA, linalg.DistributedMatrix)
        assert dA.row_axis == DATA_AXIS
        G = linalg.gram(dA)
        np.testing.assert_allclose(G.toNumpy(), A.T @ A, rtol=2e-5,
                                   atol=2e-4)
        out = G.toINDArray()
        assert out.shape() == (8, 8)

    def test_replicate_roundtrip(self, mesh1):
        A = _rand((16, 4))
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        r = dA.replicate()
        assert r.is_replicated()
        np.testing.assert_array_equal(r.toNumpy(), A)


class TestMatmulParity:
    def test_summa_2d(self, mesh2):
        A, B = _rand((16, 24), 1), _rand((24, 8), 2)
        dA = linalg.DistributedMatrix(A, mesh2, row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        dB = linalg.DistributedMatrix(B, mesh2, row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        C = linalg.matmul(dA, dB)
        assert (C.row_axis, C.col_axis) == (DATA_AXIS, MODEL_AXIS)
        np.testing.assert_allclose(C.toNumpy(), A @ B, rtol=2e-5,
                                   atol=1e-4)

    def test_summa_1d_ring(self, mesh1):
        A, B = _rand((16, 24), 3), _rand((24, 8), 4)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        dB = linalg.DistributedMatrix(B, mesh1, row_axis=DATA_AXIS)
        np.testing.assert_allclose(linalg.matmul(dA, dB).toNumpy(),
                                   A @ B, rtol=2e-5, atol=1e-4)

    def test_replicated_rhs(self, mesh1, mesh2):
        A, B = _rand((16, 24), 5), _rand((24, 8), 6)
        dA1 = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        np.testing.assert_allclose(linalg.matmul(dA1, B).toNumpy(),
                                   A @ B, rtol=2e-5, atol=1e-4)
        # col-sharded A vs replicated B: k-panel partials psum over tp
        dA2 = linalg.DistributedMatrix(A, mesh2, row_axis=DATA_AXIS,
                                       col_axis=MODEL_AXIS)
        np.testing.assert_allclose(linalg.matmul(dA2, B).toNumpy(),
                                   A @ B, rtol=2e-5, atol=1e-4)

    def test_transpose_fused_variants(self, mesh1):
        A, B = _rand((16, 6), 7), _rand((16, 4), 8)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        dB = linalg.DistributedMatrix(B, mesh1, row_axis=DATA_AXIS)
        ta = linalg.matmul(dA, dB, transpose_a=True)
        assert ta.is_replicated()
        np.testing.assert_allclose(ta.toNumpy(), A.T @ B, rtol=2e-5,
                                   atol=1e-4)
        tb = linalg.matmul(dA, dA, transpose_b=True)
        assert tb.row_axis == DATA_AXIS
        np.testing.assert_allclose(tb.toNumpy(), A @ A.T, rtol=2e-5,
                                   atol=1e-4)
        with pytest.raises(ValueError, match="transpose_a and "
                                             "transpose_b"):
            linalg.matmul(dA, dB, transpose_a=True, transpose_b=True)

    def test_replicated_distributedmatrix_rhs(self, mesh1):
        # regression: a replicated DistributedMatrix rhs used to hit
        # the layout-mismatch error whose own hint (replicate()) led
        # straight back to the same error
        A, B = _rand((16, 8), 19), _rand((8, 4), 20)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        dB = linalg.DistributedMatrix(B, mesh1, row_axis=None)
        C = linalg.matmul(dA, dB)
        assert C.row_axis == DATA_AXIS
        np.testing.assert_allclose(C.toNumpy(), A @ B, rtol=2e-5,
                                   atol=1e-4)

    def test_mismatched_layouts_refused(self, mesh1, mesh2):
        dA = linalg.DistributedMatrix(_rand((16, 8)), mesh2,
                                      row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        dB = linalg.DistributedMatrix(_rand((8, 4)), mesh2,
                                      row_axis=DATA_AXIS)
        with pytest.raises(ValueError, match="same layout"):
            linalg.matmul(dA, dB)


class TestGramCovariancePairwise:
    def test_gram(self, mesh1, mesh2):
        A = _rand((16, 6), 9)
        for m, kw in ((mesh1, {}), (mesh2, {"col_axis": MODEL_AXIS})):
            dA = linalg.DistributedMatrix(A, m, row_axis=DATA_AXIS, **kw)
            G = linalg.gram(dA)
            assert G.is_replicated()
            np.testing.assert_allclose(G.toNumpy(), A.T @ A, rtol=2e-5,
                                       atol=2e-4)

    def test_covariance(self, mesh1):
        A = _rand((32, 5), 10) + 7.0  # offset: centering must matter
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        np.testing.assert_allclose(linalg.covariance(dA).toNumpy(),
                                   np.cov(A, rowvar=False), rtol=1e-4,
                                   atol=1e-5)

    def test_pairwise_sq_dists(self, mesh1):
        A, B = _rand((16, 4), 11), _rand((5, 4), 12)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        D = linalg.pairwise_sq_dists(dA, B)
        ref = ((A[:, None, :] - B[None, :, :]) ** 2).sum(-1)
        np.testing.assert_allclose(D.toNumpy(), ref, rtol=1e-4,
                                   atol=1e-4)


class TestSolvers:
    def test_cg_plain_spd(self):
        rng = np.random.RandomState(0)
        M = rng.randn(12, 12).astype(np.float32)
        M = M @ M.T + 0.5 * np.eye(12, dtype=np.float32)
        b = rng.randn(12).astype(np.float32)
        res = linalg.cg(lambda x: M @ x, b, tol=1e-6, maxiter=200)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.x),
                                   np.linalg.solve(M, b), rtol=1e-3,
                                   atol=1e-3)
        assert int(res.iterations) <= 200
        assert float(res.residual_norm) < 1e-4

    def test_cg_pytree_and_diagnostics(self):
        # block-diagonal SPD operator over a pytree; non-convergence at
        # a tiny maxiter must be REPORTED, not silently returned
        b = {"w": jnp.asarray(_rand((6,), 1)),
             "v": jnp.asarray(_rand((3,), 2))}

        def matvec(x):
            return {"w": 3.0 * x["w"], "v": 0.5 * x["v"]}

        res = linalg.cg(matvec, b, tol=1e-6, maxiter=50)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.x["w"]),
                                   np.asarray(b["w"]) / 3.0, rtol=1e-5)
        bad = linalg.cg(matvec, b, tol=1e-12, maxiter=1)
        assert not bool(bad.converged)

    def test_lstsq_parity_and_ridge(self, mesh1):
        A, b = _rand((64, 6), 13), _rand((64,), 14)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        res = linalg.lstsq(dA, b, tol=1e-7)
        assert bool(res.converged)
        np.testing.assert_allclose(
            np.asarray(res.x), np.linalg.lstsq(A, b, rcond=None)[0],
            rtol=1e-3, atol=1e-3)
        lam = 0.5
        ridge = linalg.lstsq(dA, b, l2=lam, tol=1e-7)
        ref = np.linalg.solve(A.T @ A + lam * np.eye(6), A.T @ b)
        np.testing.assert_allclose(np.asarray(ridge.x), ref, rtol=1e-3,
                                   atol=1e-3)

    def test_lstsq_multi_rhs_and_col_sharded(self, mesh2):
        A, B = _rand((16, 4), 15), _rand((16, 3), 16)
        dA = linalg.DistributedMatrix(A, mesh2, row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        res = linalg.lstsq(dA, B, tol=1e-7)
        np.testing.assert_allclose(
            np.asarray(res.x), np.linalg.lstsq(A, B, rcond=None)[0],
            rtol=1e-3, atol=1e-3)


class TestRandomized:
    def test_rsvd_parity(self, mesh1):
        rng = np.random.RandomState(3)
        A = (rng.randn(64, 5) @ rng.randn(5, 16)
             + 1e-3 * rng.randn(64, 16)).astype(np.float32)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        U, s, Vt = linalg.rsvd(dA, 5, n_iter=4)
        np.testing.assert_allclose(
            np.asarray(s), np.linalg.svd(A, compute_uv=False)[:5],
            rtol=1e-3)
        rec = U.toNumpy() @ np.diag(np.asarray(s)) @ np.asarray(Vt)
        np.testing.assert_allclose(rec, A, atol=0.05)
        # U really is an orthonormal row-sharded basis
        np.testing.assert_allclose(U.toNumpy().T @ U.toNumpy(),
                                   np.eye(5), atol=1e-3)

    def test_pca_parity(self, mesh1):
        rng = np.random.RandomState(4)
        A = (rng.randn(64, 4) @ rng.randn(4, 12) + 5.0
             + 1e-3 * rng.randn(64, 12)).astype(np.float32)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        comps, ev, mu = linalg.pca(dA, 3)
        centered = A - A.mean(0)
        s_ref = np.linalg.svd(centered, compute_uv=False)[:3]
        np.testing.assert_allclose(np.asarray(ev), s_ref ** 2 / 63,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(mu), A.mean(0), atol=1e-4)
        # components span the top principal subspace: projecting the
        # centered data through them preserves the top singular mass
        proj = centered @ np.asarray(comps).T
        np.testing.assert_allclose(
            np.linalg.norm(proj), np.linalg.norm(s_ref), rtol=1e-3)


class TestRetraceContract:
    def test_one_compile_per_shape(self, mesh1):
        from deeplearning4j_tpu.analysis import RetraceSentinel

        sentinel = RetraceSentinel(max_compiles=2)
        linalg.install_retrace_sentinel(sentinel)
        try:
            A, B = _rand((16, 8)), _rand((8, 4))
            dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
            dB = linalg.DistributedMatrix(B, mesh1, row_axis=DATA_AXIS)
            for _ in range(3):  # same shape: ONE compile
                linalg.matmul(dA, dB)
            assert sentinel.compiles("matmul1d") == 1
            # a second shape costs exactly one more
            dA2 = linalg.DistributedMatrix(_rand((32, 8)), mesh1,
                                           row_axis=DATA_AXIS)
            linalg.matmul(dA2, dB)
            linalg.matmul(dA2, dB)
            assert sentinel.compiles("matmul1d") == 2
            for _ in range(2):
                linalg.gram(dA)
            assert sentinel.compiles("gram") == 1
        finally:
            linalg.install_retrace_sentinel(None)

    def test_precompile_shares_the_dispatch_body(self, mesh1):
        # regression: precompile once registered a Gram-shaped body
        # (second operand ignored) under the matmul_ta entry key — a
        # transpose_a matmul after precompile silently returned A^T A
        linalg.precompile(mesh1, 16, 8, 8)
        A, B = _rand((16, 8), 21), _rand((16, 4), 22)
        dA = linalg.DistributedMatrix(A, mesh1, row_axis=DATA_AXIS)
        dB = linalg.DistributedMatrix(B, mesh1, row_axis=DATA_AXIS)
        out = linalg.matmul(dA, dB, transpose_a=True)
        assert out.shape == (8, 4)
        np.testing.assert_allclose(out.toNumpy(), A.T @ B, rtol=2e-5,
                                   atol=1e-4)

    def test_pca_entry_keys_on_row_count(self, mesh1):
        # regression: the entry key once omitted n (the centering
        # divisor the body closes over) — a second pca at a different
        # row count reused the first call's divisor and mis-centered
        X1 = _rand((32, 8), 23) + 3.0
        X2 = _rand((64, 8), 24) + 3.0
        _, _, mu1 = linalg.pca(
            linalg.DistributedMatrix(X1, mesh1, row_axis=DATA_AXIS), 2)
        _, _, mu2 = linalg.pca(
            linalg.DistributedMatrix(X2, mesh1, row_axis=DATA_AXIS), 2)
        np.testing.assert_allclose(np.asarray(mu1), X1.mean(0),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(mu2), X2.mean(0),
                                   atol=1e-4)

    def test_precompile_enforces_never_pad_contract(self, mesh1):
        # regression: an indivisible warm size used to die with a
        # cryptic shard_map lowering error instead of the PAR03 error
        with pytest.raises(ValueError, match="refusing to silently pad"):
            linalg.precompile(mesh1, 64, 6, 4)

    def test_aot_cached_entries_by_default(self, mesh1):
        # without a sentinel the entries are CachedJit (PR 7 warm start)
        from deeplearning4j_tpu.runtime.aot import CachedJit

        out = linalg.precompile(mesh1, 16, 8, 4)
        assert set(out) == {"matmul1d", "matmul_ta", "gram", "lstsq"}
        for status, _ in out.values():
            assert status in ("cold", "warm")
        from deeplearning4j_tpu.linalg.distributed import _JIT_CACHE

        assert any(isinstance(f, CachedJit) for f in _JIT_CACHE.values())


class TestPlanGate:
    def test_canonical_plans_clean_on_dp4xtp2(self):
        # PAR04/PAR06 clean-plan gate: zero violations on the canonical
        # mesh with the 16 GB budget — including the tall subjects whose
        # GLOBAL operand (34.4 GB) does NOT fit one chip
        rep = linalg.validate_linalg_plan({"data": 4, "model": 2},
                                          hbm_gb=16)
        assert rep.ok, [d.format() for d in rep.errors]
        assert "PAR04" not in rep.codes()
        bills = rep.plan["bills"]
        assert set(bills) == {"gemm_32k", "gram_tall", "rsvd_tall",
                              "lstsq_tall"}
        tall = bills["gram_tall"]
        assert tall["global_bytes"] > 16e9          # > one chip
        assert tall["per_chip_bytes"] < 16e9        # but the plan fits

    def test_per_chip_bytes_match_runtime_placement(self, mesh2):
        # the analyzer's contract: the static a-block bill equals the
        # bytes the placed DistributedMatrix actually holds per chip
        from deeplearning4j_tpu.linalg.plan import per_chip_parity

        dA = linalg.DistributedMatrix(_rand((16, 24)), mesh2,
                                      row_axis=DATA_AXIS,
                                      col_axis=MODEL_AXIS)
        bill = linalg.matmul_plan(16, 24, 8, {"data": 4, "model": 2})
        assert bill["a_block_bytes"] == dA.per_chip_bytes()
        assert per_chip_parity(dA) == dA.per_chip_bytes()

    def test_plan_violations_reported(self):
        # PAR01: unknown axis; PAR03: indivisible dim; PAR06: over budget
        rep = linalg.validate_linalg_plan(
            {"data": 4}, plans=({"name": "bad_axis", "op": "gram",
                                 "n": 64, "d": 8, "col_axis": "model"},),
            check_sources=False)
        assert not rep.ok and "PAR01" in rep.codes()
        rep = linalg.validate_linalg_plan(
            {"data": 4}, plans=({"name": "ragged", "op": "gram",
                                 "n": 63, "d": 8},), check_sources=False)
        assert not rep.ok and "PAR03" in rep.codes()
        rep = linalg.validate_linalg_plan(
            {"data": 4}, plans=({"name": "huge", "op": "gram",
                                 "n": 2 ** 26, "d": 1024},),
            hbm_gb=16, check_sources=False)
        assert not rep.ok and "PAR06" in rep.codes()

    def test_plan_rejects_axis_reuse(self):
        # regression: a row_axis == col_axis plan passed the gate clean
        # while _axes_sizes double-counted the axis (r*c), under-billing
        # per_chip_bytes by that factor — runtime placement refuses it
        rep = linalg.validate_linalg_plan(
            {"data": 4}, plans=({"op": "gram", "n": 64, "d": 8,
                                 "row_axis": "data",
                                 "col_axis": "data"},),
            check_sources=False)
        assert not rep.ok and "PAR01" in rep.codes()
        assert rep.plan["bills"] == {}

    def test_matmul_rejects_column_only_sharding(self, mesh2):
        # regression: P(None, model) operands fell through to the
        # "both replicated" local-product branch, mislabelling a
        # sharded result as replicated (wrong block_shape/PAR06 bill)
        dA = linalg.DistributedMatrix(_rand((8, 8), 25), mesh2,
                                      row_axis=None,
                                      col_axis=MODEL_AXIS)
        with pytest.raises(ValueError, match="column-only"):
            linalg.matmul(dA, dA)

    def test_cli_linalg_exit_contract(self):
        from deeplearning4j_tpu.analysis.cli import main

        assert main(["--linalg", "--hbm-gb", "16"]) == 0
        # dp3 mesh: the canonical plans' rows don't divide -> PAR03 -> 1
        assert main(["--linalg", "--mesh", "data=3"]) == 1
        assert main(["--linalg", "--mesh", "data==bad"]) == 2
        # combining with another subject must refuse loudly, not let
        # whichever block runs first swallow the other's exit status
        assert main(["--linalg", "--parallel"]) == 2
        assert main(["--linalg", "--precompile", "lenet"]) == 2

    def test_collective_counts_contract(self, mesh2):
        import functools

        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.linalg.distributed import _summa_2d_body
        from jax import shard_map

        A = jnp.asarray(_rand((16, 8)))
        B = jnp.asarray(_rand((8, 4)))
        counts = linalg.collective_counts(
            shard_map(functools.partial(_summa_2d_body,
                                        row_axis=DATA_AXIS,
                                        col_axis=MODEL_AXIS, n_cols=2),
                      mesh=mesh2,
                      in_specs=(P(DATA_AXIS, MODEL_AXIS),) * 2,
                      out_specs=P(DATA_AXIS, MODEL_AXIS),
                      check_vma=False), A, B)
        assert counts == {"all_gather": 1, "ppermute": 1}
        # gram's single-input body gathers the column shards ONCE —
        # the shape gram_plan bills (one panel + one psum)
        from deeplearning4j_tpu.linalg.distributed import _build_gram

        g2 = linalg.collective_counts(
            _build_gram(mesh2, DATA_AXIS, MODEL_AXIS), A)
        assert g2 == {"all_gather": 1, "psum": 1}, g2
        g1 = linalg.collective_counts(
            _build_gram(mesh2, DATA_AXIS, None), A)
        assert g1 == {"psum": 1}, g1


class TestConsumers:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_kmeans_sharded_parity(self, mesh1):
        from deeplearning4j_tpu.clustering import KMeansClustering

        rng = np.random.RandomState(0)
        X = np.concatenate([rng.randn(32, 4) + c
                            for c in (0, 10, 20)]).astype(np.float32)
        X = X[rng.permutation(96)]
        local = KMeansClustering.setup(3, seed=1).applyTo(X)
        shard = KMeansClustering.setup(3, seed=1, mesh=mesh1).applyTo(X)
        # same partition up to label permutation + same inertia
        a, b = local.getAssignments(), shard.getAssignments()
        assert ((a[:, None] == a[None, :])
                == (b[:, None] == b[None, :])).all()
        np.testing.assert_allclose(shard.inertia, local.inertia,
                                   rtol=1e-4)
        with pytest.raises(ValueError, match="refusing to silently pad"):
            KMeansClustering.setup(3, seed=1, mesh=mesh1).applyTo(X[:90])

    def test_lsh_distributed_projection_parity(self, mesh1):
        from deeplearning4j_tpu.clustering.lsh import RandomProjectionLSH

        X = _rand((64, 6), 17)
        a = RandomProjectionLSH(10, 3, 6, seed=2).index(X)
        b = RandomProjectionLSH(10, 3, 6, seed=2, mesh=mesh1).index(X)
        i1, d1 = a.search(X[7], 5)
        i2, d2 = b.search(X[7], 5)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(d1, d2, rtol=1e-5)

    def test_deepwalk_gram_products(self, mesh1):
        from deeplearning4j_tpu.graph.deepwalk import DeepWalk, Graph

        g = Graph(8)
        for a in range(8):
            g.addEdge(a, (a + 1) % 8)
        dw = (DeepWalk.Builder().vectorSize(8).windowSize(2).seed(1)
              .build())
        dw.fit(g, walkLength=6, walksPerVertex=2, iterations=1)
        E = dw.embeddings()
        assert E.shape == (8, 8)
        np.testing.assert_allclose(dw.embeddingGram(mesh=mesh1),
                                   E.T @ E, rtol=1e-4, atol=1e-4)
        sim = dw.similarityMatrix(mesh=mesh1)
        np.testing.assert_allclose(sim, dw.similarityMatrix(),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.diag(sim), np.ones(8), atol=1e-4)

    def test_nn_conjugate_gradient_is_native_and_converges(self):
        # the seed-old optax-CG failure, replaced: CONJUGATE_GRADIENT
        # builds the optax-free Newton-CG routed through linalg.cg and
        # crushes a convex quadratic to the noise floor
        from deeplearning4j_tpu.nn.solvers import (_NewtonCG,
                                                   build_solver,
                                                   solver_update)

        solver = build_solver("CONJUGATE_GRADIENT", maxIterations=20)
        assert isinstance(solver, _NewtonCG)

        rng = np.random.RandomState(5)
        A = rng.randn(32, 6).astype(np.float32)
        b = rng.randn(32).astype(np.float32)
        params = {"x": jnp.zeros((6,), jnp.float32)}

        def value_fn(p):
            r = A @ p["x"] - b
            return 0.5 * jnp.vdot(r, r)

        state = solver.init(params)
        for _ in range(3):
            loss, grads = jax.value_and_grad(value_fn)(params)
            params, state = solver_update(solver, grads, state, params,
                                          loss, value_fn)
        ref = np.linalg.lstsq(A, b, rcond=None)[0]
        np.testing.assert_allclose(np.asarray(params["x"]), ref,
                                   rtol=1e-3, atol=1e-3)
