"""SameDiff graph tests: build, whole-graph compile, autodiff parity vs a
jax.grad oracle, training convergence, serialization round-trip.

Mirrors reference tests in nd4j-autodiff samediff test suites
(SameDiffTests: basic ops, gradients, training)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig, VariableType
from deeplearning4j_tpu.nn.updaters import Sgd, Adam


def test_basic_arithmetic_eval():
    sd = SameDiff.create()
    a = sd.constant(np.array([1.0, 2.0, 3.0]), name="a")
    b = sd.constant(np.array([10.0, 20.0, 30.0]), name="b")
    c = (a + b) * 2.0 - 3.0
    got = c.eval().toNumpy()
    np.testing.assert_allclose(got, np.array([19.0, 41.0, 63.0]))


def test_placeholder_exec_and_jit_cache():
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 2, 3)
    w = sd.var("w", np.ones((3, 4)))
    y = sd.nn.linear(x, w, name="y")
    xv = np.arange(6.0).reshape(2, 3)
    out = sd.output({"x": xv}, ["y"])["y"].toNumpy()
    np.testing.assert_allclose(out, xv @ np.ones((3, 4)))
    # second call hits the jit cache (no retrace needed for same shape)
    out2 = sd.output({"x": xv + 1}, ["y"])["y"].toNumpy()
    np.testing.assert_allclose(out2, (xv + 1) @ np.ones((3, 4)))


def test_namespaces_cover_op_families():
    sd = SameDiff.create()
    x = sd.constant(np.linspace(-1, 1, 12).reshape(3, 4))
    assert sd.math.exp(x).eval().shape() == (3, 4)
    assert sd.nn.softmax(x).eval().shape() == (3, 4)
    assert sd.math.sum(x, 1).eval().shape() == (3,)
    s = sd.math.reshape(x, (4, 3))
    assert s.eval().shape() == (4, 3)
    q, r = sd.linalg.qr(sd.constant(np.random.rand(4, 4)))
    np.testing.assert_allclose((q.mmul(r)).eval().toNumpy(),
                               q.eval().toNumpy() @ r.eval().toNumpy())


def test_reduction_and_argmax():
    sd = SameDiff.create()
    x = sd.constant(np.array([[1.0, 5.0, 2.0], [7.0, 0.0, 3.0]]))
    assert float(sd.math.max(x).eval().toNumpy()) == 7.0
    np.testing.assert_array_equal(
        sd.math.argmax(x, 1).eval().toNumpy(), np.array([1, 0]))


def test_gradients_match_jax_oracle():
    """calculateGradients == jax.grad on the equivalent pure function."""
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 4, 3)
    w = sd.var("w", np.random.RandomState(0).rand(3, 2))
    b = sd.var("b", np.zeros(2))
    out = sd.math.tanh(sd.nn.linear(x, w, b))
    loss = sd.math.sum(sd.math.square(out), name="loss")
    sd.setLossVariables("loss")

    xv = np.random.RandomState(1).rand(4, 3)
    grads = sd.calculateGradients({"x": xv}, "w", "b")

    wv = np.random.RandomState(0).rand(3, 2)

    def oracle(w_, b_):
        return jnp.sum(jnp.square(jnp.tanh(xv @ w_ + b_)))

    gw, gb = jax.grad(oracle, argnums=(0, 1))(wv, np.zeros(2))
    np.testing.assert_allclose(grads["w"].toNumpy(), gw, rtol=1e-6)
    np.testing.assert_allclose(grads["b"].toNumpy(), gb, rtol=1e-6)


def test_loss_ops_marked_and_graph_slice():
    sd = SameDiff.create()
    labels = sd.placeHolder("labels", jnp.float64, 8, 3)
    logits = sd.placeHolder("logits", jnp.float64, 8, 3)
    sd.loss.softmaxCrossEntropy(labels, logits, name="sce")
    assert "sce" in sd._loss_names()


def test_training_linear_regression_converges():
    """fit() drives loss down on y = Xw* synthetic data (reference:
    SameDiffTrainingTest)."""
    rs = np.random.RandomState(42)
    X = rs.rand(64, 5)
    true_w = np.array([[1.0], [-2.0], [3.0], [0.5], [-1.5]])
    Y = X @ true_w

    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 64, 5)
    y = sd.placeHolder("y", jnp.float64, 64, 1)
    w = sd.var("w", np.zeros((5, 1)))
    pred = sd.nn.linear(x, w, name="pred")
    sd.loss.meanSquaredError(y, pred, name="mse")

    sd.setTrainingConfig(TrainingConfig.Builder()
                         .updater(Adam(learningRate=0.1))
                         .dataSetFeatureMapping("x")
                         .dataSetLabelMapping("y")
                         .build())
    hist = sd.fit(features=X, labels=Y, epochs=200)
    assert hist[-1] < 0.01 * hist[0]
    np.testing.assert_allclose(
        sd.getVariable("w").getArr().toNumpy(), true_w, atol=0.15)


def test_training_l2_regularization_shrinks_weights():
    X = np.random.RandomState(0).rand(32, 4)
    Y = np.zeros((32, 1))

    def run(l2):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float64, 32, 4)
        y = sd.placeHolder("y", jnp.float64, 32, 1)
        w = sd.var("w", np.full((4, 1), 5.0))
        sd.loss.meanSquaredError(y, sd.nn.linear(x, w, name="p"), name="l")
        sd.setTrainingConfig(TrainingConfig.Builder()
                             .updater(Sgd(learningRate=0.05))
                             .dataSetFeatureMapping("x")
                             .dataSetLabelMapping("y")
                             .l2(l2).build())
        sd.fit(features=X, labels=Y, epochs=50)
        return float(np.abs(sd.getVariable("w").getArr().toNumpy()).sum())

    assert run(0.1) < run(0.0) + 1e-9


def test_serialization_roundtrip(tmp_path):
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 2, 3)
    w = sd.var("w", np.random.RandomState(3).rand(3, 4))
    sd.nn.gelu(sd.nn.linear(x, w), name="out")

    xv = np.random.RandomState(4).rand(2, 3)
    before = sd.output({"x": xv}, ["out"])["out"].toNumpy()

    p = str(tmp_path / "model.sdz")
    sd.save(p)
    sd2 = SameDiff.load(p)
    after = sd2.output({"x": xv}, ["out"])["out"].toNumpy()
    np.testing.assert_allclose(before, after, rtol=1e-7)
    assert sd2.getVariable("w").variableType == VariableType.VARIABLE


def test_variable_rename_and_summary():
    sd = SameDiff.create()
    a = sd.constant(np.ones(3), name="a")
    b = sd.math.exp(a, name="e")
    b.rename("expA")
    assert "expA" in sd.summary()
    np.testing.assert_allclose(sd.getVariable("expA").eval().toNumpy(),
                               np.e * np.ones(3), rtol=1e-7)


def test_multi_output_unstack():
    sd = SameDiff.create()
    x = sd.constant(np.arange(6.0).reshape(3, 2))
    rows = sd.math.unstack(x, 0, 3)
    assert len(rows) == 3
    np.testing.assert_allclose(rows[1].eval().toNumpy(), np.array([2.0, 3.0]))


def test_gradient_accessor():
    sd = SameDiff.create()
    w = sd.var("w", np.array([2.0]))
    loss = sd.math.sum(sd.math.square(w), name="loss")
    sd.setLossVariables("loss")
    g = sd.grad("w").eval()
    np.testing.assert_allclose(g.toNumpy(), np.array([4.0]))


def test_cnn_namespace_conv_and_pool():
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 1, 8, 8, 2)  # NHWC
    w = sd.var("w", np.random.RandomState(0).rand(3, 3, 2, 4) * 0.1)  # HWIO
    c = sd.cnn.conv2d(x, w, padding=((1, 1), (1, 1)), name="c")
    p = sd.cnn.maxPooling2d(c, (2, 2), name="p")
    out = sd.output({"x": np.random.RandomState(1).rand(1, 8, 8, 2)}, ["p"])
    assert out["p"].shape() == (1, 4, 4, 4)


def test_rnn_namespace_lstm():
    sd = SameDiff.create()
    T, B, F, H = 5, 2, 3, 4
    rs = np.random.RandomState(0)
    x = sd.placeHolder("x", jnp.float64, T, B, F)
    w = sd.var("w", rs.rand(F, 4 * H) * 0.1)
    u = sd.var("u", rs.rand(H, 4 * H) * 0.1)
    b = sd.var("b", np.zeros(4 * H))
    h_seq, h_last, c_last = sd.rnn.lstmLayer(x, w, u, b)
    out = sd.output({"x": rs.rand(T, B, F)}, [h_seq])
    assert out[h_seq.name].shape() == (T, B, H)


def test_dropout_active_in_fit_identity_in_inference():
    """Dropout must perturb the forward during fit() (train mode + rng
    threaded by _run_graph) but be identity under output()."""
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 16, 8)
    w = sd.var("w", np.ones((8, 1)))
    d = sd.nn.dropout(sd.nn.linear(x, w), 0.5, name="d")
    sd.loss.meanSquaredError(sd.constant(np.zeros((16, 1))), d, name="l")

    xv = np.ones((16, 8))
    # inference: identity
    np.testing.assert_allclose(sd.output({"x": xv}, ["d"])["d"].toNumpy(),
                               xv @ np.ones((8, 1)))
    # training: two iterations with different rng keys give different losses
    # than the dropout-free analytic loss of 64.0
    sd.setTrainingConfig(TrainingConfig.Builder()
                         .updater(Sgd(learningRate=0.0))
                         .dataSetFeatureMapping("x")
                         .dataSetLabelMapping("__unused__")
                         .build())
    hist = sd.fit(features=xv, labels=np.zeros((16, 1)), epochs=3)
    assert any(abs(h - 64.0) > 1e-6 for h in hist), \
        "dropout was a no-op during training"


class TestControlFlow:
    """sd.ifCond / sd.whileLoop (reference: nd4j-autodiff If/While ops),
    lowered to lax.cond / lax.while_loop / differentiable masked scan."""

    def test_if_cond_both_branches(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 3)
        p = sd.placeHolder("p", jnp.float32)
        out = sd.ifCond(p, lambda s, a: a * 2.0, lambda s, a: a - 1.0,
                        inputs=[x], name="branch")
        xv = np.array([1.0, 2.0, 3.0], "float32")
        hi = sd.output({"x": xv, "p": np.float32(1.0)}, [out])["branch"]
        lo = sd.output({"x": xv, "p": np.float32(0.0)}, [out])["branch"]
        np.testing.assert_allclose(hi.toNumpy(), xv * 2)
        np.testing.assert_allclose(lo.toNumpy(), xv - 1)

    def test_if_cond_subgraph_ops(self):
        """Branch bodies may use full SameDiff namespaces."""
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 2, 2)
        p = sd.placeHolder("p", jnp.float32)
        out = sd.ifCond(
            p,
            lambda s, a: s.math.exp(a),
            lambda s, a: s.nn.relu(a),
            inputs=[x], name="cf")
        xv = np.array([[-1.0, 2.0], [0.5, -3.0]], "float32")
        hi = sd.output({"x": xv, "p": np.float32(5.0)}, [out])["cf"]
        lo = sd.output({"x": xv, "p": np.float32(0.0)}, [out])["cf"]
        np.testing.assert_allclose(hi.toNumpy(), np.exp(xv), rtol=1e-6)
        np.testing.assert_allclose(lo.toNumpy(), np.maximum(xv, 0))

    def test_while_loop_dynamic_count(self):
        """True lax.while_loop: iteration count depends on runtime data."""
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32)
        limit = sd.placeHolder("limit", jnp.float32)
        cnt0 = sd.constant(0.0, name="cnt0")
        acc, cnt, _ = sd.whileLoop(
            lambda s, a, c, lim: s.math.lt(c, lim),
            lambda s, a, c, lim: (a * 2.0, c + 1.0, lim),
            loopVars=[x, cnt0, limit], name="wl")
        for n_iter in (3, 7):
            r = sd.output({"x": np.float32(1.5), "limit": np.float32(n_iter)},
                          [acc, cnt])
            np.testing.assert_allclose(r[acc.name].toNumpy(),
                                       1.5 * 2 ** n_iter)
            np.testing.assert_allclose(r[cnt.name].toNumpy(), n_iter)

    def test_bounded_while_matches_unbounded(self):
        """maxIterations (masked scan) computes the same values as the
        dynamic while when the bound is large enough."""
        def build(max_it):
            sd = SameDiff.create()
            x = sd.placeHolder("x", jnp.float32)
            limit = sd.placeHolder("limit", jnp.float32)
            cnt0 = sd.constant(0.0)
            acc, cnt, _ = sd.whileLoop(
                lambda s, a, c, lim: s.math.lt(c, lim),
                lambda s, a, c, lim: (a + 3.0, c + 1.0, lim),
                loopVars=[x, cnt0, limit], maxIterations=max_it, name="wl")
            return sd, acc
        sd_b, acc_b = build(8)
        r = sd_b.output({"x": np.float32(1.0), "limit": np.float32(5)}, [acc_b])
        np.testing.assert_allclose(r[acc_b.name].toNumpy(), 16.0)

    def test_bounded_while_trains_under_jit(self):
        """A dynamic-iteration-count graph trains under jit.
        The applied step count comes from a runtime placeholder (differs
        per batch); w trains through the masked-scan while loop."""
        rs = np.random.RandomState(0)
        w_true = 0.8
        x0 = rs.randn(32, 4).astype("float32")
        batches = []
        for k in (2.0, 4.0):
            batches.append((
                [x0, np.float32(k)], [x0 * (w_true ** k)]))

        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 32, 4)
        klim = sd.placeHolder("k", jnp.float32)
        y = sd.placeHolder("y", jnp.float32, 32, 4)
        w = sd.var("w", np.array(0.3, "float32"))
        cnt0 = sd.constant(np.float32(0.0))
        h, _, _, _ = sd.whileLoop(
            lambda s, a, c, lim, ww: s.math.lt(c, lim),
            lambda s, a, c, lim, ww: (a * ww, c + 1.0, lim, ww),
            loopVars=[x, cnt0, klim, w], maxIterations=6, name="wl")
        sd.loss.meanSquaredError(y, h, name="mse")
        sd.setTrainingConfig(TrainingConfig.Builder()
                             .updater(Adam(learningRate=0.05))
                             .dataSetFeatureMapping("x", "k")
                             .dataSetLabelMapping("y").build())
        hist = sd.fit(data=batches, epochs=100)
        assert hist[-1] < 0.05 * hist[0], f"loss {hist[0]} -> {hist[-1]}"
        w_fit = float(sd.getVariable("w").getArr().toNumpy())
        assert abs(w_fit - w_true) < 0.1, f"w learned {w_fit} vs {w_true}"

    def test_dropout_inside_cond_respects_train_mode(self):
        """Stochastic ops inside control-flow bodies must see the outer
        train/rng: dropout in a branch is active during training and
        identity at inference."""
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 1000)
        p = sd.placeHolder("p", jnp.float32)
        out = sd.ifCond(p, lambda s, a: s.nn.dropout(a, 0.5),
                        lambda s, a: a, inputs=[x], name="cf")
        xv = np.ones(1000, "float32")
        env = dict(sd._base_env()); env.update({"x": xv, "p": np.float32(1)})
        train_out = np.asarray(sd._run_graph(
            env, ["cf"], train=True, rng=jax.random.key(7))["cf"])
        env = dict(sd._base_env()); env.update({"x": xv, "p": np.float32(1)})
        infer_out = np.asarray(sd._run_graph(env, ["cf"])["cf"])
        assert (train_out == 0).mean() > 0.3, "dropout inactive in training"
        np.testing.assert_allclose(infer_out, xv)

    def test_if_cond_output_count_validated(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 2)
        p = sd.placeHolder("p", jnp.float32)
        out = sd.ifCond(p, lambda s, a: (a, a * 2.0), lambda s, a: (a, a),
                        inputs=[x], name="bad")  # nOut defaults to 1
        with pytest.raises(ValueError, match="declared"):
            sd.output({"x": np.ones(2, "float32"), "p": np.float32(1)}, [out])


class TestExtraMathOps:
    def test_clip_sort_topk_split(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 2, 6)
        c = sd.math.clipByValue(x, -1.0, 1.0, name="clip")
        s = sd.math.sort(x, descending=True, name="srt")
        tv, ti = sd.math.topK(x, 2, name="tk")
        a, b, cc = sd.math.split(x, 3, axis=1, name="sp")
        xv = np.array([[3., -5., 1., 0.5, 2., -2.],
                       [0., 1., -1., 4., -4., 2.]], "float32")
        r = sd.output({"x": xv}, [c, s, tv, ti, a])
        np.testing.assert_allclose(r["clip"].toNumpy(), np.clip(xv, -1, 1))
        np.testing.assert_allclose(r["srt"].toNumpy(), -np.sort(-xv, -1))
        np.testing.assert_allclose(r[tv.name].toNumpy(),
                                   -np.sort(-xv, -1)[:, :2])
        np.testing.assert_allclose(r[ti.name].toNumpy(),
                                   np.argsort(-xv, -1)[:, :2])
        np.testing.assert_allclose(r[a.name].toNumpy(), xv[:, :2])

    def test_clip_by_norm(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 4)
        y = sd.math.clipByNorm(x, 2.0, name="cn")
        xv = np.array([3.0, 4.0, 0.0, 0.0], "float32")  # norm 5
        r = sd.output({"x": xv}, [y])["cn"].toNumpy()
        np.testing.assert_allclose(np.linalg.norm(r), 2.0, rtol=1e-5)
        np.testing.assert_allclose(r, xv * 0.4, rtol=1e-4)

    def test_clip_preserves_integer_dtype(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.int32, 5)
        y = sd.math.clipByValue(x, 0, 3, name="ci")
        r = sd.output({"x": np.array([-2, 1, 9, 3, 0], "int32")}, [y])["ci"]
        assert r.toNumpy().dtype == np.int32
        np.testing.assert_array_equal(r.toNumpy(), [0, 1, 3, 3, 0])


class TestRandomOps:
    """sd.random namespace (reference: ops.SDRandom)."""

    def test_normal_stats_and_determinism(self):
        sd = SameDiff.create()
        n = sd.random.normal(2.0, 3.0, 4000, name="n")
        a = sd.output({}, ["n"])["n"].toNumpy()
        b = sd.output({}, ["n"])["n"].toNumpy()
        np.testing.assert_array_equal(a, b)  # seeded inference
        assert abs(a.mean() - 2.0) < 0.2 and abs(a.std() - 3.0) < 0.2

    def test_uniform_bounds_and_bernoulli_rate(self):
        sd = SameDiff.create()
        sd.random.uniform(-1.0, 1.0, 1000, name="u")
        sd.random.bernoulli(0.3, 5000, name="b")
        out = sd.output({}, ["u", "b"])
        u, b = out["u"].toNumpy(), out["b"].toNumpy()
        assert u.min() >= -1.0 and u.max() < 1.0
        assert set(np.unique(b)) <= {0.0, 1.0}
        assert abs(b.mean() - 0.3) < 0.05

    def test_exponential_mean(self):
        sd = SameDiff.create()
        sd.random.exponential(4.0, 8000, name="e")
        e = sd.output({}, ["e"])["e"].toNumpy()
        assert e.min() >= 0.0 and abs(e.mean() - 0.25) < 0.05

    def test_distinct_ops_draw_independently(self):
        sd = SameDiff.create()
        sd.random.normal(0.0, 1.0, 100, name="n1")
        sd.random.normal(0.0, 1.0, 100, name="n2")
        out = sd.output({}, ["n1", "n2"])
        assert not np.allclose(out["n1"].toNumpy(), out["n2"].toNumpy())

    def test_noise_in_expression_trains(self):
        # denoising-style objective: w is pulled toward the data mean
        # despite per-step bernoulli corruption of the input
        rs = np.random.RandomState(0)
        X = (3.0 + 0.1 * rs.randn(64, 8)).astype("float32")
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 64, 8)
        w = sd.var("w", np.zeros((8,), dtype="float32"))
        mask = sd.random.bernoulli(0.5, 64, 8, name="mask")
        corrupted = sd.math.mul(x, mask)
        delta = sd.math.sub(corrupted, w)
        loss = sd.math.mean(sd.math.square(delta), name="loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig.Builder()
                             .updater(Adam(learningRate=0.05))
                             .dataSetFeatureMapping("x").build())
        hist = sd.fit(features=X, labels=None, epochs=60)
        assert np.isfinite(hist[-1])
        # E[x*mask] = 1.5: w should land near it, proving noise refreshes
        # and gradients flow around the non-differentiable draw
        wv = sd.getVariable("w").eval().toNumpy()
        assert abs(wv.mean() - 1.5) < 0.25, wv.mean()


class TestControlFlowSerialization:
    """ifCond/whileLoop graphs round-trip through save/load: bodies are
    recorded as subgraph specs at definition (reference: SameDiff
    FlatBuffers stores If/While subgraphs) and replayed on load."""

    def test_ifcond_roundtrip(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 4)
        pred = sd.math.gt(sd.math.sum(x), sd.constant(np.float32(0.0)))
        sd.ifCond(pred,
                  lambda s, a: s.math.mul(a, s.constant(np.float32(2.0))),
                  lambda s, a: s.math.neg(a),
                  inputs=[x], name="branch")
        for sign in (1.0, -1.0):
            xv = (sign * np.arange(1, 5)).astype("float32")
            before = sd.output({"x": xv}, ["branch"])["branch"].toNumpy()
            p = str(tmp_path / f"cf{sign}.sdz")
            sd.save(p)
            after = SameDiff.load(p).output({"x": xv},
                                            ["branch"])["branch"].toNumpy()
            np.testing.assert_allclose(before, after, rtol=1e-6)

    def test_while_roundtrip_dynamic_trip_count(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32)
        sd.whileLoop(lambda s, v: s.math.lt(v, s.constant(np.float32(100.0))),
                     lambda s, v: s.math.mul(v, s.constant(np.float32(3.0))),
                     loopVars=[x], name="tripled")
        p = str(tmp_path / "while.sdz")
        sd.save(p)
        sd2 = SameDiff.load(p)
        for v0 in (2.0, 50.0, 200.0):
            a = sd.output({"x": np.float32(v0)}, ["tripled"])["tripled"]
            b = sd2.output({"x": np.float32(v0)}, ["tripled"])["tripled"]
            np.testing.assert_allclose(a.toNumpy(), b.toNumpy())

    def test_random_op_roundtrip(self, tmp_path):
        sd = SameDiff.create()
        sd.random.normal(0.0, 1.0, 32, name="n")
        p = str(tmp_path / "rng.sdz")
        sd.save(p)
        a = sd.output({}, ["n"])["n"].toNumpy()
        b = SameDiff.load(p).output({}, ["n"])["n"].toNumpy()
        np.testing.assert_array_equal(a, b)  # same seeded draw

    def test_unrecordable_body_fails_at_save_not_define(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 2)
        captured = {}

        def bad_body(s, a):
            # touches concrete shape state recording cannot provide
            raise RuntimeError("I inspect runtime values")

        # definition succeeds (execution of this op would also fail, but
        # that is the body author's bug, not serialization's)
        sd.ifCond(sd.math.gt(sd.math.sum(x), sd.constant(np.float32(0.0))),
                  bad_body, lambda s, a: a, inputs=[x], name="b")
        with pytest.raises(NotImplementedError, match="could not be recorded"):
            sd.save(str(tmp_path / "bad.sdz"))


class TestControlFlowSerializationHardening:
    def test_while_body_random_redraws_each_iteration(self):
        """A stochastic op inside a whileLoop body must draw fresh values
        per iteration (key rides in the loop carry), not replay one
        sample N times."""
        def run(n_iters):
            sd = SameDiff.create()
            v = sd.placeHolder("v", jnp.float32)
            i = sd.placeHolder("i", jnp.float32)
            out = sd.whileLoop(
                lambda s, vv, ii: s.math.lt(ii, s.constant(
                    np.float32(n_iters))),
                lambda s, vv, ii: (s.math.add(vv, s.random.normal(0.0, 1.0)),
                                   s.math.add(ii, s.constant(np.float32(1)))),
                loopVars=[v, i], name="acc")
            res = sd.output({"v": np.float32(0), "i": np.float32(0)},
                            [out[0].name])
            return float(res[out[0].name].toNumpy())

        v1, v2 = run(1), run(2)
        eps1, eps2 = v1, v2 - v1
        assert abs(eps2 - eps1) > 1e-6, "second draw replayed the first"

    def test_nested_unrecordable_fails_at_save(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 2)

        def bad(s, a):
            raise RuntimeError("inspects runtime values")

        def outer(s, a):
            return s.ifCond(
                s.math.gt(s.math.sum(a), s.constant(np.float32(0.0))),
                bad, lambda s2, b: b, inputs=[a])

        sd.ifCond(sd.math.gt(sd.math.sum(x), sd.constant(np.float32(0.0))),
                  outer, lambda s, a: a, inputs=[x], name="o")
        with pytest.raises(NotImplementedError, match="could not be recorded"):
            sd.save(str(tmp_path / "nested.sdz"))

    def test_body_constants_stored_in_npz_not_json(self, tmp_path):
        import json as _json
        import zipfile as _zf

        big = np.random.RandomState(0).rand(64, 64).astype("float32")
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 64)
        pred = sd.math.gt(sd.math.sum(x), sd.constant(np.float32(0.0)))
        sd.ifCond(pred,
                  lambda s, a: s.math.sum(s.math.mul(
                      s.constant(big), a), 1),
                  lambda s, a: a, inputs=[x], name="proj")
        p = str(tmp_path / "bigbody.sdz")
        sd.save(p)
        with _zf.ZipFile(p) as z:
            gj = z.read("graph.json").decode()
            assert len(gj) < 20_000, "body constant leaked into graph.json"
            names = np.load(io_bytes(z.read("arrays.npz"))).files
            assert any(n.startswith("__body__/") for n in names)
        xv = np.random.RandomState(1).rand(64).astype("float32")
        a = sd.output({"x": xv}, ["proj"])["proj"].toNumpy()
        b = SameDiff.load(p).output({"x": xv}, ["proj"])["proj"].toNumpy()
        np.testing.assert_allclose(a, b, rtol=1e-6)


def io_bytes(b):
    import io
    return io.BytesIO(b)


class TestNonMaxSuppression:
    """sd.image.nonMaxSuppression (reference: SDImage / libnd4j
    non_max_suppression) — fixed-size jittable greedy NMS."""

    def _boxes(self):
        boxes = np.array([[0, 0, 1, 1],        # top score
                          [0, 0, 1.05, 1.05],  # IoU ~0.9 with 0: suppressed
                          [2, 2, 3, 3],        # disjoint: kept
                          [0, 0, 0.4, 0.4]],   # inside 0, IoU 0.16: kept
                         "float32")
        scores = np.array([0.9, 0.8, 0.7, 0.6], "float32")
        return boxes, scores

    def test_greedy_selection_and_padding(self):
        sd = SameDiff.create()
        boxes, scores = self._boxes()
        out = sd.image.nonMaxSuppression(sd.constant(boxes),
                                         sd.constant(scores),
                                         maxOutputSize=4, iouThreshold=0.5,
                                         name="nms")
        np.testing.assert_array_equal(out.eval().toNumpy(), [0, 2, 3, -1])

    def test_score_threshold_filters(self):
        sd = SameDiff.create()
        boxes, scores = self._boxes()
        out = sd.image.nonMaxSuppression(sd.constant(boxes),
                                         sd.constant(scores),
                                         maxOutputSize=4, iouThreshold=0.5,
                                         scoreThreshold=0.65, name="nms")
        np.testing.assert_array_equal(out.eval().toNumpy(), [0, 2, -1, -1])

    def test_max_output_truncates(self):
        sd = SameDiff.create()
        boxes, scores = self._boxes()
        out = sd.image.nonMaxSuppression(sd.constant(boxes),
                                         sd.constant(scores),
                                         maxOutputSize=1, name="nms")
        np.testing.assert_array_equal(out.eval().toNumpy(), [0])


def test_cnn_namespace_conv3d():
    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float64, 1, 5, 6, 7, 2)  # NDHWC
    rs = np.random.RandomState(0)
    w = sd.var("w", rs.rand(3, 3, 3, 2, 4) * 0.1)  # DHWIO
    c = sd.cnn.conv3d(x, w, padding=((1, 1), (1, 1), (1, 1)), name="c")
    xv = rs.rand(1, 5, 6, 7, 2)
    out = sd.output({"x": xv}, ["c"])
    assert out["c"].shape() == (1, 5, 6, 7, 4)
    # numeric oracle at one output position: pure correlation sum
    import jax.numpy as _jnp
    from jax import lax as _lax
    ref = _lax.conv_general_dilated(
        _jnp.asarray(xv), _jnp.asarray(sd.getVariable("w").getArr().toNumpy()),
        (1, 1, 1), ((1, 1),) * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    np.testing.assert_allclose(out["c"].toNumpy(), np.asarray(ref), rtol=1e-6)


def test_nms_nan_scores_and_empty_input():
    # a NaN score (diverged head) must not poison selection
    sd = SameDiff.create()
    boxes = np.array([[0, 0, 1, 1], [5, 5, 6, 6], [2, 2, 3, 3]], "float32")
    scores = np.array([0.9, np.nan, 0.7], "float32")
    out = sd.image.nonMaxSuppression(sd.constant(boxes), sd.constant(scores),
                                     maxOutputSize=3, name="nms")
    np.testing.assert_array_equal(out.eval().toNumpy(), [0, 2, -1])
    # zero candidates is a normal outcome, not a crash
    sd2 = SameDiff.create()
    out2 = sd2.image.nonMaxSuppression(
        sd2.constant(np.zeros((0, 4), "float32")),
        sd2.constant(np.zeros((0,), "float32")), maxOutputSize=2, name="nms")
    np.testing.assert_array_equal(out2.eval().toNumpy(), [-1, -1])


class TestMathLongTail:
    """SDMath distance/segment/counting/entropy families (reference:
    libnd4j reduce3 + segment kernels), each vs a numpy oracle."""

    def test_distances(self):
        rs = np.random.RandomState(0)
        a, b = rs.rand(4, 6), rs.rand(4, 6)
        sd = SameDiff.create()
        x, y = sd.constant(a), sd.constant(b)
        np.testing.assert_allclose(
            sd.math.cosineSimilarity(x, y, 1).eval().toNumpy(),
            np.sum(a * b, 1) / (np.linalg.norm(a, axis=1)
                                * np.linalg.norm(b, axis=1)), rtol=1e-6)
        np.testing.assert_allclose(
            sd.math.euclideanDistance(x, y, 1).eval().toNumpy(),
            np.linalg.norm(a - b, axis=1), rtol=1e-6)
        np.testing.assert_allclose(
            sd.math.manhattanDistance(x, y, 1).eval().toNumpy(),
            np.abs(a - b).sum(1), rtol=1e-6)
        np.testing.assert_allclose(
            sd.math.cosineDistance(x, y, 1).eval().toNumpy(),
            1 - sd.math.cosineSimilarity(x, y, 1).eval().toNumpy(), rtol=1e-6)
        np.testing.assert_allclose(
            sd.math.jaccardDistance(x, y, 1).eval().toNumpy(),
            1 - np.minimum(a, b).sum(1) / np.maximum(a, b).sum(1), rtol=1e-6)
        ai = (a > 0.5).astype(float)
        bi = (b > 0.5).astype(float)
        np.testing.assert_allclose(
            sd.math.hammingDistance(sd.constant(ai), sd.constant(bi),
                                    1).eval().toNumpy(),
            (ai != bi).sum(1))

    def test_special_functions_vs_scipy(self):
        # reference: nd4j Lgamma/Digamma/Igamma/Igammac/BetaInc/
        # Polygamma/Zeta custom ops — scipy is the oracle
        import scipy.special as sp

        rs = np.random.RandomState(1)
        a = rs.uniform(0.5, 5.0, (3, 4))
        b = rs.uniform(0.5, 5.0, (3, 4))
        x01 = rs.uniform(0.05, 0.95, (3, 4))
        sd = SameDiff.create()
        av, bv, xv = sd.constant(a), sd.constant(b), sd.constant(x01)
        np.testing.assert_allclose(sd.math.lgamma(av).eval().toNumpy(),
                                   sp.gammaln(a), rtol=1e-5)
        np.testing.assert_allclose(sd.math.digamma(av).eval().toNumpy(),
                                   sp.digamma(a), rtol=1e-5)
        np.testing.assert_allclose(sd.math.igamma(av, bv).eval().toNumpy(),
                                   sp.gammainc(a, b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(sd.math.igammac(av, bv).eval().toNumpy(),
                                   sp.gammaincc(a, b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            sd.math.betainc(av, bv, xv).eval().toNumpy(),
            sp.betainc(a, b, x01), rtol=1e-5, atol=1e-6)
        n = np.full((2, 3), 2.0)
        xz = rs.uniform(1.5, 4.0, (2, 3))
        np.testing.assert_allclose(
            sd.math.polygamma(sd.constant(n), sd.constant(xz))
            .eval().toNumpy(), sp.polygamma(2, xz), rtol=1e-4, atol=1e-6)
        q = rs.uniform(1.0, 3.0, (2, 3))
        s = rs.uniform(2.0, 5.0, (2, 3))
        np.testing.assert_allclose(
            sd.math.zeta(sd.constant(s), sd.constant(q)).eval().toNumpy(),
            sp.zeta(s, q), rtol=1e-4, atol=1e-6)

    def test_segment_reductions(self):
        data = np.array([3.0, 1.0, 4.0, 1.0, 5.0, 9.0])
        ids = np.array([0, 0, 1, 1, 1, 2])
        sd = SameDiff.create()
        d, i = sd.constant(data), sd.constant(ids)
        np.testing.assert_allclose(
            sd.math.segmentSum(d, i, numSegments=3).eval().toNumpy(),
            [4.0, 10.0, 9.0])
        np.testing.assert_allclose(
            sd.math.segmentMax(d, i, numSegments=3).eval().toNumpy(),
            [3.0, 5.0, 9.0])
        np.testing.assert_allclose(
            sd.math.segmentMean(d, i, numSegments=3).eval().toNumpy(),
            [2.0, 10.0 / 3, 9.0])
        # unsorted alias accepts permuted ids
        np.testing.assert_allclose(
            sd.math.unsortedSegmentSum(
                sd.constant(data), sd.constant(np.array([2, 0, 1, 0, 1, 2])),
                numSegments=3).eval().toNumpy(),
            [2.0, 9.0, 12.0])

    def test_confusion_and_counts(self):
        sd = SameDiff.create()
        lab = sd.constant(np.array([0, 1, 1, 2]))
        prd = sd.constant(np.array([0, 1, 0, 2]))
        cm = sd.math.confusionMatrix(lab, prd, numClasses=3).eval().toNumpy()
        np.testing.assert_array_equal(cm, [[1, 0, 0], [1, 1, 0], [0, 0, 1]])
        x = sd.constant(np.array([[0.0, 1.0, 0.0], [2.0, 0.0, 3.0]]))
        assert float(sd.math.zeroFraction(x).eval().toNumpy()) == 0.5
        np.testing.assert_array_equal(
            sd.math.countNonZero(x, 1).eval().toNumpy(), [1, 2])
        np.testing.assert_array_equal(
            sd.math.countZero(x, 1).eval().toNumpy(), [2, 1])
        assert float(sd.math.matchConditionCount(
            x, "gt", 0.5).eval().toNumpy()) == 3

    def test_entropy_iamax_creation(self):
        p = np.array([0.5, 0.25, 0.25, 0.0])
        sd = SameDiff.create()
        x = sd.constant(p)
        np.testing.assert_allclose(
            sd.math.shannonEntropy(x).eval().toNumpy(), 1.5, rtol=1e-6)
        np.testing.assert_allclose(
            sd.math.entropy(x).eval().toNumpy(),
            -(p[p > 0] * np.log(p[p > 0])).sum(), rtol=1e-6)
        assert int(sd.math.iamax(sd.constant(
            np.array([1.0, -7.0, 3.0]))).eval().toNumpy()) == 1
        np.testing.assert_allclose(
            sd.math.linspace(0, 1, 5).eval().toNumpy(), np.linspace(0, 1, 5))
        np.testing.assert_array_equal(
            sd.math.range(2, 10, 3, dtype="int32").eval().toNumpy(),
            [2, 5, 8])
        gx, gy = sd.math.meshgrid(sd.constant(np.arange(2.0)),
                                  sd.constant(np.arange(3.0)))
        assert gx.eval().shape() == (3, 2) and gy.eval().shape() == (3, 2)


class TestLossLongTail:
    """SDLoss additions vs independent oracles (torch for the CE family,
    brute force for pairwise)."""

    def test_sigmoid_ce_matches_torch(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        rs = np.random.RandomState(0)
        lab = (rs.rand(4, 5) > 0.5).astype("float32")
        log = rs.randn(4, 5).astype("float32")
        sd = SameDiff.create()
        v = sd.loss.sigmoidCrossEntropy(sd.constant(lab), sd.constant(log),
                                        name="l")
        ref = float(F.binary_cross_entropy_with_logits(
            torch.tensor(log), torch.tensor(lab)))
        np.testing.assert_allclose(float(v.eval().toNumpy()), ref, rtol=1e-5)

    def test_weighted_ce_matches_torch_pos_weight(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        rs = np.random.RandomState(1)
        lab = (rs.rand(6, 3) > 0.5).astype("float32")
        log = rs.randn(6, 3).astype("float32")
        w = np.array([0.5, 2.0, 3.0], "float32")
        sd = SameDiff.create()
        v = sd.loss.weightedCrossEntropyWithLogits(
            sd.constant(lab), sd.constant(log), sd.constant(w), name="l")
        ref = float(F.binary_cross_entropy_with_logits(
            torch.tensor(log), torch.tensor(lab),
            pos_weight=torch.tensor(w)))
        np.testing.assert_allclose(float(v.eval().toNumpy()), ref, rtol=1e-5)

    def test_l2_and_pairwise(self):
        rs = np.random.RandomState(2)
        x = rs.randn(3, 4)
        sd = SameDiff.create()
        np.testing.assert_allclose(
            float(sd.loss.l2Loss(sd.constant(x), name="a").eval().toNumpy()),
            np.sum(x ** 2) / 2, rtol=1e-6)
        lab, pred = rs.randn(3, 4), rs.randn(3, 4)
        v = sd.loss.meanPairwiseSquaredError(
            sd.constant(lab), sd.constant(pred), name="b")
        d = pred - lab
        per = []
        for k in range(3):
            s = 0.0
            for i in range(4):
                for j in range(4):
                    s += (d[k, i] - d[k, j]) ** 2
            per.append(s / (4 * 3))
        np.testing.assert_allclose(float(v.eval().toNumpy()),
                                   np.mean(per), rtol=1e-6)
        # uniform-offset case: the centered form is EXACTLY zero where the
        # naive n*sum(d^2)-(sum d)^2 form cancels catastrophically
        v0 = sd.loss.meanPairwiseSquaredError(
            sd.constant(np.zeros((2, 4), "float32")),
            sd.constant(np.full((2, 4), 1e3, "float32")), name="c")
        assert float(v0.eval().toNumpy()) == 0.0


class TestAdamW:
    def test_decoupled_decay_equals_adam_plus_wd(self):
        from deeplearning4j_tpu.nn.updaters import Adam, AdamW

        rs = np.random.RandomState(0)
        p = {"W": jnp.asarray(rs.randn(4, 3), jnp.float32)}
        g = {"W": jnp.asarray(rs.randn(4, 3), jnp.float32)}
        a, w = Adam(1e-2), AdamW(1e-2, weightDecay=0.1)
        ua, _ = a.apply(g, a.init(p), 0, params=p)
        uw, _ = w.apply(g, w.init(p), 0, params=p)
        np.testing.assert_allclose(
            np.asarray(uw["W"]),
            np.asarray(ua["W"]) + 1e-2 * 0.1 * np.asarray(p["W"]),
            rtol=1e-6)

    def test_adamw_trains_and_shrinks_unused_weights(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, AdamW)

        conf = (NeuralNetConfiguration.Builder().seed(1)
                .updater(AdamW(1e-2, weightDecay=0.2)).list()
                .layer(DenseLayer(nOut=8, activation="tanh"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        rs = np.random.RandomState(0)
        x = rs.randn(32, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        def total_norm(n):
            return float(sum(np.linalg.norm(np.asarray(l)) for l in
                             jax.tree_util.tree_leaves(n._params)))

        base_conf = (NeuralNetConfiguration.Builder().seed(1)
                     .updater(AdamW(1e-2, weightDecay=0.0)).list()
                     .layer(DenseLayer(nOut=8, activation="tanh"))
                     .layer(OutputLayer(nOut=2, activation="softmax"))
                     .setInputType(InputType.feedForward(4)).build())
        base = MultiLayerNetwork(base_conf).init()
        for _ in range(20):
            net.fit(x, y)
            base.fit(x, y)
        assert np.isfinite(net.score())
        # the decay must actually bite: wd=0.2 weights end smaller than
        # the wd=0 twin (catches params= being dropped at a call site)
        assert total_norm(net) < 0.97 * total_norm(base), \
            (total_norm(net), total_norm(base))


def test_distance_ops_finite_gradients_at_degenerate_points():
    """d/dx sqrt(0) is inf under autodiff; the distance ops must take the
    zero subgradient at converged/zero inputs instead of emitting NaN."""
    from deeplearning4j_tpu.autodiff.ops_impl import OPS

    g1 = jax.grad(lambda x: jnp.sum(
        OPS["euclideanDistance"](x, jnp.zeros(3), dimensions=None)))(
            jnp.zeros(3))
    g2 = jax.grad(lambda x: jnp.sum(
        OPS["cosineSimilarity"](x, jnp.ones(3), dimensions=None)))(
            jnp.zeros(3))
    assert bool(jnp.all(jnp.isfinite(g1)))
    assert bool(jnp.all(jnp.isfinite(g2)))


class TestBlockOpsAndLinalgTail:
    """spaceToDepth/depthToSpace/spaceToBatch/batchToSpace (block
    rearrangement, NHWC) and linalg lu/eigh — inverse/reconstruction
    round trips as the oracle."""

    def test_space_depth_batch_roundtrips(self):
        rs = np.random.RandomState(0)
        sd = SameDiff.create()
        x = sd.constant(rs.rand(2, 4, 4, 3))
        rt = sd.image.depthToSpace(sd.image.spaceToDepth(x, 2), 2, name="a")
        np.testing.assert_allclose(rt.eval().toNumpy(), x.eval().toNumpy())
        bt = sd.image.batchToSpace(sd.image.spaceToBatch(x, 2), 2, name="b")
        np.testing.assert_allclose(bt.eval().toNumpy(), x.eval().toNumpy())
        # shape semantics
        s2d = sd.image.spaceToDepth(x, 2, name="c")
        assert s2d.eval().shape() == (2, 2, 2, 12)
        s2b = sd.image.spaceToBatch(x, 2, name="d")
        assert s2b.eval().shape() == (8, 2, 2, 3)

    def test_space_to_batch_padding_and_crops(self):
        rs = np.random.RandomState(1)
        sd = SameDiff.create()
        x = sd.constant(rs.rand(1, 2, 2, 1))
        padded = sd.image.spaceToBatch(x, 2, padding=((1, 1), (1, 1)),
                                       name="p")
        assert padded.eval().shape() == (4, 2, 2, 1)
        back = sd.image.batchToSpace(padded, 2, crops=((1, 1), (1, 1)),
                                     name="q")
        np.testing.assert_allclose(back.eval().toNumpy(),
                                   x.eval().toNumpy())

    def test_lu_and_eigh_reconstruct(self):
        rs = np.random.RandomState(2)
        A = rs.rand(4, 4)
        sd = SameDiff.create()
        p, l, u = sd.linalg.lu(sd.constant(A))
        plu = (p.eval().toNumpy() @ l.eval().toNumpy()
               @ u.eval().toNumpy())
        np.testing.assert_allclose(plu, A, atol=1e-6)
        S = A + A.T
        w, v = sd.linalg.eigh(sd.constant(S))
        V = v.eval().toNumpy()
        np.testing.assert_allclose(V @ np.diag(w.eval().toNumpy()) @ V.T,
                                   S, atol=1e-5)


class TestFFTOps:
    """sd.fft namespace (reference: the Nd4j.fft spectral family) —
    numpy.fft oracles, gradient flow, serialization."""

    def test_fft_ifft_roundtrip_oracle(self):
        rng = np.random.RandomState(0)
        xv = rng.randn(4, 16)
        sd = SameDiff.create()
        x = sd.constant(xv, name="x")
        spec = sd.fft.fft(x, name="spec")
        back = sd.fft.real(sd.fft.ifft(spec), name="back")
        got = spec.eval().toNumpy()
        np.testing.assert_allclose(got, np.fft.fft(xv, axis=-1),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(back.eval().toNumpy(), xv,
                                   rtol=1e-4, atol=1e-4)

    def test_rfft_irfft_numpoints_dimension(self):
        rng = np.random.RandomState(1)
        xv = rng.randn(8, 10)
        sd = SameDiff.create()
        x = sd.constant(xv)
        r = sd.fft.rfft(x, numPoints=16, dimension=0)
        np.testing.assert_allclose(r.eval().toNumpy(),
                                   np.fft.rfft(xv, n=16, axis=0),
                                   rtol=1e-4, atol=1e-4)
        back = sd.fft.irfft(sd.fft.rfft(x), dimension=-1)
        np.testing.assert_allclose(back.eval().toNumpy(), xv,
                                   rtol=1e-4, atol=1e-4)

    def test_fft2_and_complex_parts(self):
        rng = np.random.RandomState(2)
        xv = rng.randn(6, 8)
        sd = SameDiff.create()
        x = sd.constant(xv)
        s = sd.fft.fft2(x)
        oracle = np.fft.fft2(xv)
        np.testing.assert_allclose(sd.fft.real(s).eval().toNumpy(),
                                   oracle.real, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(sd.fft.imag(s).eval().toNumpy(),
                                   oracle.imag, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(sd.fft.angle(s).eval().toNumpy(),
                                   np.angle(oracle), rtol=1e-4, atol=1e-4)
        rt = sd.fft.real(sd.fft.ifft2(s))
        np.testing.assert_allclose(rt.eval().toNumpy(), xv,
                                   rtol=1e-4, atol=1e-4)

    def test_toComplex_conj(self):
        sd = SameDiff.create()
        re = sd.constant(np.array([1.0, 2.0]))
        im = sd.constant(np.array([3.0, -4.0]))
        z = sd.fft.toComplex(re, im)
        zc = sd.fft.conj(z)
        np.testing.assert_allclose(sd.fft.imag(zc).eval().toNumpy(),
                                   np.array([-3.0, 4.0]))

    def test_gradient_through_power_spectrum(self):
        # d/dx sum(|rfft(x)|^2) has a clean oracle via jax.grad on the
        # same jnp program
        rng = np.random.RandomState(3)
        xv = rng.randn(12)
        sd = SameDiff.create()
        x = sd.var("x", xv)
        spec = sd.fft.rfft(x)
        power = sd.math.sum(sd.math.square(sd.fft.real(spec))
                            + sd.math.square(sd.fft.imag(spec)),
                            name="power")
        sd.setLossVariables("power")
        grads = sd.calculateGradients(None, "x")

        def f(v):
            s = jnp.fft.rfft(v)
            return jnp.sum(jnp.real(s) ** 2 + jnp.imag(s) ** 2)
        oracle = jax.grad(f)(jnp.asarray(xv))
        np.testing.assert_allclose(grads["x"].toNumpy(), oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_fft_graph_serializes(self, tmp_path):
        rng = np.random.RandomState(4)
        xv = rng.randn(4, 8)
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, 4, 8)
        mag = sd.math.sum(sd.math.square(sd.fft.real(sd.fft.rfft(x))),
                          name="mag")
        before = sd.output({"x": xv}, ["mag"])["mag"].toNumpy()
        p = str(tmp_path / "fftgraph.sdz")
        sd.save(p)
        sd2 = SameDiff.load(p)
        after = sd2.output({"x": xv}, ["mag"])["mag"].toNumpy()
        np.testing.assert_allclose(after, before, rtol=1e-5)


class TestEvaluateAndScopedSerde:
    """sd.evaluate(iterator, output, IEvaluation...) (reference:
    SameDiff.evaluate) and scoped-name serialization."""

    def test_evaluate_iterator(self):
        from deeplearning4j_tpu.autodiff import TrainingConfig
        from deeplearning4j_tpu.data import DataSetIterator
        from deeplearning4j_tpu.evaluation import Evaluation
        from deeplearning4j_tpu.nn import Adam

        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype("float32")
        w_true = rng.randn(4, 3)
        yidx = np.argmax(x @ w_true, 1)
        y = np.eye(3, dtype="float32")[yidx]

        sd = SameDiff.create()
        xin = sd.placeHolder("x", np.float32, 64, 4)
        yin = sd.placeHolder("y", np.float32, 64, 3)
        w = sd.var("w", 4, 3)
        b = sd.var("b", np.zeros(3, np.float32))
        logits = sd.nn.linear(xin, w, b, name="logits")
        loss = sd.loss.softmaxCrossEntropy(yin, logits)
        loss.markAsLoss()
        sd.setTrainingConfig(
            TrainingConfig.Builder().updater(Adam(0.05))
            .dataSetFeatureMapping("x").dataSetLabelMapping("y").build())
        it = DataSetIterator(x, y, 64)
        for _ in range(60):
            it.reset()
            sd.fit(list(it))
        e = sd.evaluate(it, "logits", Evaluation(3))
        assert e.accuracy() > 0.9, e.accuracy()
        with pytest.raises(ValueError, match="TrainingConfig"):
            SameDiff.create().evaluate(it, "z")
        # multi-input mapping with a single-feature iterator is LOUD,
        # not silently bound to every placeholder
        sd.setTrainingConfig(
            TrainingConfig.Builder().dataSetFeatureMapping("x", "x2")
            .dataSetLabelMapping("y").build())
        with pytest.raises(ValueError, match="feature array"):
            sd.evaluate(it, "logits")

    def test_scoped_names_survive_serde(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", np.float32, 2, 3)
        with sd.withNameScope("enc"):
            w = sd.var("w", 3, 4)
            out = sd.nn.relu(sd.nn.linear(x, w), name="out")
        p = str(tmp_path / "scoped.sdz")
        sd.save(p)
        sd2 = SameDiff.load(p)
        xv = np.random.RandomState(1).randn(2, 3).astype("float32")
        np.testing.assert_array_equal(
            np.asarray(sd.getVariable("enc/out").eval({"x": xv}).jax()),
            np.asarray(sd2.getVariable("enc/out").eval({"x": xv}).jax()))


class TestFitSteps:
    """SameDiff.fitSteps — the on-device k-step loop — must follow the
    same trajectory as k fit() calls on the same batch (shared raw step,
    same RNG/iteration streams)."""

    def _linreg(self):
        rs = np.random.RandomState(0)
        X = rs.rand(32, 5)
        Y = X @ np.array([[1.0], [-2.0], [3.0], [0.5], [-1.5]])
        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float64, 32, 5)
        y = sd.placeHolder("y", jnp.float64, 32, 1)
        w = sd.var("w", np.zeros((5, 1)))
        sd.loss.meanSquaredError(y, sd.nn.linear(x, w, name="p"), name="l")
        sd.setTrainingConfig(TrainingConfig.Builder()
                             .updater(Adam(learningRate=0.05))
                             .dataSetFeatureMapping("x")
                             .dataSetLabelMapping("y").build())
        return sd, X, Y

    def test_matches_k_fit_calls(self):
        a, X, Y = self._linreg()
        b, _, _ = self._linreg()
        hist = a.fit(features=X, labels=Y, epochs=6)
        loss = b.fitSteps(features=X, labels=Y, numSteps=6)
        np.testing.assert_allclose(
            a.getVariable("w").getArr().toNumpy(),
            b.getVariable("w").getArr().toNumpy(), rtol=1e-6, atol=1e-8)
        # fitSteps returns the LAST step's loss (fp32 carry)
        np.testing.assert_allclose(loss, hist[-1], rtol=1e-5)
        assert a._iteration == b._iteration == 6

    def test_interleaves_with_fit(self):
        """fit() after fitSteps() continues the same updater state and
        iteration counter (no hidden reset)."""
        a, X, Y = self._linreg()
        b, _, _ = self._linreg()
        a.fit(features=X, labels=Y, epochs=4)
        b.fitSteps(features=X, labels=Y, numSteps=2)
        b.fit(features=X, labels=Y, epochs=2)
        np.testing.assert_allclose(
            a.getVariable("w").getArr().toNumpy(),
            b.getVariable("w").getArr().toNumpy(), rtol=1e-6, atol=1e-8)
