"""Data pipeline tests: normalizers, built-in iterators, record readers,
transform pipelines.

Mirrors the reference's nd4j-dataset / datavec tests
(NormalizerStandardizeTest, CSVRecordReaderTest, TransformProcessTest...).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.data import (
    DataSet, DataSetIterator, NormalizerStandardize, NormalizerMinMaxScaler,
    ImagePreProcessingScaler, VGG16ImagePreProcessor, IrisDataSetIterator,
    MnistDataSetIterator, Cifar10DataSetIterator, CSVRecordReader,
    CollectionRecordReader, Schema, TransformProcess,
    RecordReaderDataSetIterator,
)


# ------------------------------------------------------------- normalizers
class TestNormalizerStandardize:
    def test_zero_mean_unit_var(self):
        rng = np.random.RandomState(0)
        f = rng.randn(200, 5) * np.array([1, 2, 3, 4, 5.0]) + np.arange(5)
        ds = DataSet(f.astype(np.float32), np.zeros((200, 2), np.float32))
        n = NormalizerStandardize().fit(ds)
        n.preProcess(ds)
        out = ds.getFeatures().toNumpy()
        np.testing.assert_allclose(out.mean(0), 0, atol=1e-4)
        np.testing.assert_allclose(out.std(0), 1, atol=1e-3)

    def test_streaming_fit_equals_full_fit(self):
        rng = np.random.RandomState(1)
        f = rng.randn(120, 3).astype(np.float32) * 4 + 7
        l = np.zeros((120, 2), np.float32)
        full = NormalizerStandardize().fit(DataSet(f, l))
        it = DataSetIterator(f, l, 32, pad_final=False)
        stream = NormalizerStandardize().fit(it)
        np.testing.assert_allclose(stream._mean, full._mean, rtol=1e-6)
        np.testing.assert_allclose(stream._std, full._std, rtol=1e-5)

    def test_revert_round_trip(self):
        rng = np.random.RandomState(2)
        f = (rng.randn(50, 4) * 3 + 1).astype(np.float32)
        ds = DataSet(f.copy(), np.zeros((50, 2), np.float32))
        n = NormalizerStandardize().fit(ds)
        n.preProcess(ds)
        back = n.revertFeatures(ds.getFeatures()).toNumpy()
        np.testing.assert_allclose(back, f, atol=1e-4)

    def test_cnn_4d_per_channel(self):
        rng = np.random.RandomState(3)
        f = rng.rand(20, 3, 8, 8).astype(np.float32) * np.array([1, 10, 100]).reshape(1, 3, 1, 1)
        ds = DataSet(f, np.zeros((20, 2), np.float32))
        n = NormalizerStandardize().fit(ds)
        n.preProcess(ds)
        out = ds.getFeatures().toNumpy()
        np.testing.assert_allclose(out.mean((0, 2, 3)), 0, atol=1e-4)
        np.testing.assert_allclose(out.std((0, 2, 3)), 1, atol=1e-3)

    def test_fit_label(self):
        rng = np.random.RandomState(4)
        f = rng.randn(60, 2).astype(np.float32)
        l = (rng.randn(60, 1) * 9 + 5).astype(np.float32)
        ds = DataSet(f, l)
        n = NormalizerStandardize().fitLabel(True).fit(ds)
        n.preProcess(ds)
        np.testing.assert_allclose(ds.getLabels().toNumpy().mean(), 0, atol=1e-4)

    def test_save_load(self, tmp_path):
        rng = np.random.RandomState(5)
        ds = DataSet(rng.randn(30, 3).astype(np.float32), np.zeros((30, 1), np.float32))
        n = NormalizerStandardize().fit(ds)
        p = str(tmp_path / "norm.npz")
        n.save(p)
        n2 = NormalizerStandardize.load(p)
        np.testing.assert_allclose(n2._mean, n._mean)
        np.testing.assert_allclose(n2._std, n._std)


class TestMinMaxAndImageScalers:
    def test_minmax_range(self):
        rng = np.random.RandomState(6)
        f = (rng.randn(100, 4) * 5).astype(np.float32)
        ds = DataSet(f, np.zeros((100, 1), np.float32))
        n = NormalizerMinMaxScaler(-1.0, 1.0).fit(ds)
        n.preProcess(ds)
        out = ds.getFeatures().toNumpy()
        np.testing.assert_allclose(out.min(0), -1, atol=1e-5)
        np.testing.assert_allclose(out.max(0), 1, atol=1e-5)
        back = n.revertFeatures(ds.getFeatures()).toNumpy()
        np.testing.assert_allclose(back, f, atol=1e-3)

    def test_image_scaler(self):
        f = np.array([[0.0, 127.5, 255.0]], np.float32)
        ds = DataSet(f, None)
        ImagePreProcessingScaler().fit(ds).preProcess(ds)
        np.testing.assert_allclose(ds.getFeatures().toNumpy(), [[0, 0.5, 1.0]], atol=1e-5)

    def test_vgg_preprocessor(self):
        f = np.zeros((2, 3, 4, 4), np.float32)
        ds = DataSet(f, None)
        VGG16ImagePreProcessor().preProcess(ds)
        out = ds.getFeatures().toNumpy()
        np.testing.assert_allclose(out[0, :, 0, 0], -VGG16ImagePreProcessor.MEANS)


# --------------------------------------------------------------- iterators
class TestBuiltinIterators:
    def test_iris(self):
        it = IrisDataSetIterator(batchSize=50)
        ds = it.next()
        assert ds.getFeatures().shape() == (50, 4)
        assert ds.getLabels().shape() == (50, 3)
        assert it.totalExamples() == 150

    def test_mnist_shapes(self):
        it = MnistDataSetIterator(batchSize=32, train=True, numExamples=200)
        ds = it.next()
        assert ds.getFeatures().shape() == (32, 784)
        assert ds.getLabels().shape() == (32, 10)
        f = ds.getFeatures().toNumpy()
        assert 0.0 <= f.min() and f.max() <= 1.0

    def test_mnist_cnn_shape(self):
        it = MnistDataSetIterator(batchSize=16, numExamples=64, reshapeToCnn=True)
        assert it.next().getFeatures().shape() == (16, 1, 28, 28)

    def test_cifar_shapes(self):
        it = Cifar10DataSetIterator(batchSize=8, numExamples=64)
        ds = it.next()
        assert ds.getFeatures().shape() == (8, 3, 32, 32)
        assert ds.getLabels().shape() == (8, 10)

    def test_mnist_deterministic(self):
        a = MnistDataSetIterator(batchSize=16, numExamples=32, shuffle=False, seed=7)
        b = MnistDataSetIterator(batchSize=16, numExamples=32, shuffle=False, seed=7)
        np.testing.assert_array_equal(a.next().getFeatures().toNumpy(),
                                      b.next().getFeatures().toNumpy())

    def test_mnist_is_learnable(self):
        """Synthetic-or-real, a linear probe must beat chance easily —
        guards the synthetic generator's class-conditional structure."""
        it = MnistDataSetIterator(batchSize=512, numExamples=512, shuffle=False)
        ds = it.next()
        f = ds.getFeatures().toNumpy()
        y = ds.getLabels().toNumpy().argmax(-1)
        w = np.linalg.lstsq(np.c_[f, np.ones(len(f))],
                            np.eye(10)[y], rcond=None)[0]
        acc = (np.c_[f, np.ones(len(f))].dot(w).argmax(-1) == y).mean()
        assert acc > 0.5, f"linear probe acc {acc} barely above chance"


# ----------------------------------------------------------------- records
class TestRecordReaders:
    def test_csv_reader(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("# header\n1.5,2,hello\n3.5,4,world\n")
        rr = CSVRecordReader(skipNumLines=1).initialize(p)
        assert rr.next() == [1.5, 2, "hello"]
        assert rr.next() == [3.5, 4, "world"]
        assert not rr.hasNext()
        rr.reset()
        assert rr.hasNext()

    def test_reader_to_dataset_iterator_classification(self, tmp_path):
        p = tmp_path / "d.csv"
        rows = ["%f,%f,%d" % (i * 0.1, i * 0.2, i % 3) for i in range(30)]
        p.write_text("\n".join(rows))
        rr = CSVRecordReader().initialize(p)
        it = RecordReaderDataSetIterator(rr, batchSize=10, labelIndex=2,
                                         numPossibleLabels=3)
        ds = it.next()
        assert ds.getFeatures().shape() == (10, 2)
        assert ds.getLabels().shape() == (10, 3)
        np.testing.assert_allclose(ds.getLabels().toNumpy().sum(-1), 1.0)

    def test_reader_regression(self):
        rr = CollectionRecordReader([[1.0, 2.0, 10.0], [3.0, 4.0, 20.0]])
        it = RecordReaderDataSetIterator(rr, batchSize=2, labelIndex=2,
                                         regression=True)
        ds = it.next()
        np.testing.assert_allclose(ds.getLabels().toNumpy(), [[10.0], [20.0]])

    def test_image_record_reader(self, tmp_path):
        from PIL import Image
        from deeplearning4j_tpu.data import ImageRecordReader

        for cls, color in [("cats", (255, 0, 0)), ("dogs", (0, 0, 255))]:
            d = tmp_path / cls
            d.mkdir()
            for i in range(3):
                Image.new("RGB", (10, 12), color).save(d / f"{i}.png")
        rr = ImageRecordReader(height=8, width=8, channels=3).initialize(tmp_path)
        assert rr.getLabels() == ["cats", "dogs"]
        rec = rr.next()
        assert rec[0].shape == (3, 8, 8) and rec[1] == 0
        it = RecordReaderDataSetIterator(rr, batchSize=6)
        ds = it.next()
        assert ds.getFeatures().shape() == (6, 3, 8, 8)
        assert ds.getLabels().shape() == (6, 2)


class TestTransformProcess:
    def _schema(self):
        return (Schema.Builder()
                .addColumnsDouble("a", "b")
                .addColumnCategorical("cat", "x", "y", "z")
                .addColumnString("junk")
                .build())

    def test_remove_and_math(self):
        tp = (TransformProcess.Builder(self._schema())
              .removeColumns("junk")
              .doubleMathOp("a", "Multiply", 2.0)
              .categoricalToInteger("cat")
              .build())
        out = tp.execute([[1.0, 2.0, "y", "drop"], [3.0, 4.0, "z", "drop"]])
        assert out == [[2.0, 2.0, 1], [6.0, 4.0, 2]]
        assert tp.getFinalSchema().getColumnNames() == ["a", "b", "cat"]

    def test_one_hot(self):
        tp = (TransformProcess.Builder(self._schema())
              .removeColumns("junk")
              .categoricalToOneHot("cat")
              .build())
        out = tp.execute([[1.0, 2.0, "y"]])
        assert out == [[1.0, 2.0, 0, 1, 0]]
        assert tp.getFinalSchema().numColumns() == 5

    def test_filter(self):
        tp = (TransformProcess.Builder(self._schema())
              .filter(lambda r: r["a"] > 2.0)
              .build())
        out = tp.execute([[1.0, 0.0, "x", ""], [5.0, 0.0, "x", ""]])
        assert len(out) == 1 and out[0][0] == 1.0


# -------------------------------------------- iterator + normalizer wiring
class TestIteratorPreprocessorWiring:
    def test_normalizer_as_preprocessor(self):
        rng = np.random.RandomState(9)
        f = (rng.randn(64, 3) * 10 + 4).astype(np.float32)
        l = np.zeros((64, 2), np.float32)
        it = DataSetIterator(f, l, 16)
        n = NormalizerStandardize().fit(it)
        it.setPreProcessor(n)
        batch = it.next().getFeatures().toNumpy()
        assert abs(batch.mean()) < 1.0  # roughly centered after transform


class TestReviewRegressions:
    def test_fit_ignores_padding_and_preprocessor(self):
        rng = np.random.RandomState(10)
        f = (rng.randn(20, 3) * 5 + 2).astype(np.float32)
        l = np.zeros((20, 1), np.float32)
        # batch 16 pads the final 4-row batch to 16 by repeating the last row
        it = DataSetIterator(f, l, 16)  # pad_final defaults True
        n = NormalizerStandardize().fit(it)
        np.testing.assert_allclose(n._mean, f.mean(0), rtol=1e-5)
        # re-fitting with the preprocessor installed must see RAW data
        it.setPreProcessor(n)
        n2 = NormalizerStandardize().fit(it)
        np.testing.assert_allclose(n2._mean, f.mean(0), rtol=1e-5)

    def test_synthetic_train_test_share_templates(self):
        tr = MnistDataSetIterator(batchSize=256, numExamples=256, train=True,
                                  shuffle=False, seed=3)
        te = MnistDataSetIterator(batchSize=256, numExamples=256, train=False,
                                  shuffle=False, seed=3)
        if not tr.isSynthetic:
            pytest.skip("real MNIST present")
        dtr = tr._f, tr._l
        dte = te._f, te._l
        # linear probe trained on train split must transfer to test split
        Xtr, Ytr = dtr[0].reshape(256, -1), dtr[1].argmax(-1)
        Xte, Yte = dte[0].reshape(256, -1), dte[1].argmax(-1)
        w = np.linalg.lstsq(np.c_[Xtr, np.ones(256)], np.eye(10)[Ytr], rcond=None)[0]
        acc = (np.c_[Xte, np.ones(256)].dot(w).argmax(-1) == Yte).mean()
        assert acc > 0.4, f"train->test transfer {acc}: splits use different templates"

    def test_normalizer_promotes_uint8(self):
        f = np.arange(12, dtype=np.uint8).reshape(4, 3)
        ds = DataSet(f, np.zeros((4, 1), np.float32))
        # DataSet wraps to device array; use raw numpy apply path instead
        n = NormalizerStandardize().fit(DataSet(f.astype(np.float32), np.zeros((4, 1), np.float32)))
        out = n._apply(f, label=False)
        assert np.issubdtype(out.dtype, np.floating)
        assert out.min() < 0  # negatives preserved, not wrapped

    def test_random_iterator_lazy_and_deterministic(self):
        from deeplearning4j_tpu.data import RandomDataSetIterator
        it = RandomDataSetIterator(3, (4, 5), (4, 2), seed=9)
        b1 = [it.next().getFeatures().toNumpy() for _ in range(3)]
        assert not it.hasNext()
        it.reset()
        b2 = [it.next().getFeatures().toNumpy() for _ in range(3)]
        for a, b in zip(b1, b2):
            np.testing.assert_array_equal(a, b)
        assert not np.array_equal(b1[0], b1[1])

    def test_one_hot_unknown_state_raises(self):
        sch = Schema.Builder().addColumnCategorical("c", "x", "y").build()
        tp = TransformProcess.Builder(sch).categoricalToOneHot("c").build()
        with pytest.raises(ValueError, match="not in states"):
            tp.execute([["X"]])


class TestTransformDSL:
    """Joins, reducers, condition filters, DataAnalysis (reference:
    datavec-api transform.join/reduce/condition/analysis) against pandas
    oracles."""

    def _schemas(self):
        from deeplearning4j_tpu.data import Schema

        left = (Schema.Builder().addColumnString("user")
                .addColumnDouble("amount").build())
        right = (Schema.Builder().addColumnString("user")
                 .addColumnCategorical("tier", "gold", "basic").build())
        lrecs = [["ann", 10.0], ["bob", 5.0], ["ann", 2.5], ["eve", 1.0]]
        rrecs = [["ann", "gold"], ["bob", "basic"], ["zoe", "basic"]]
        return left, right, lrecs, rrecs

    def _pd_join(self, lrecs, rrecs, how):
        import pandas as pd

        ld = pd.DataFrame(lrecs, columns=["user", "amount"])
        rd = pd.DataFrame(rrecs, columns=["user", "tier"])
        return ld.merge(rd, on="user", how=how)

    @pytest.mark.parametrize("jtype,how", [("Inner", "inner"),
                                           ("LeftOuter", "left"),
                                           ("RightOuter", "right"),
                                           ("FullOuter", "outer")])
    def test_join_matches_pandas(self, jtype, how):
        from deeplearning4j_tpu.data import Join, executeJoin

        left, right, lrecs, rrecs = self._schemas()
        join = (Join.Builder(jtype).setJoinColumns("user")
                .setSchemas(left, right).build())
        schema, out = executeJoin(join, lrecs, rrecs)
        assert schema.getColumnNames() == ["user", "amount", "tier"]
        oracle = self._pd_join(lrecs, rrecs, how)
        got = sorted((r[0], -1.0 if r[1] is None else r[1], r[2] or "")
                     for r in out)
        want = sorted((u, -1.0 if a != a else a, t if t == t else "")
                      for u, a, t in oracle.itertuples(index=False))
        assert got == want

    def test_join_validates_columns(self):
        from deeplearning4j_tpu.data import Join

        left, right, _, _ = self._schemas()
        with pytest.raises(ValueError, match="missing from right"):
            (Join.Builder("Inner").setJoinColumns("amount")
             .setSchemas(left, right).build())
        with pytest.raises(ValueError, match="unknown join type"):
            Join.Builder("CrossApply")

    def test_reducer_matches_pandas_groupby(self):
        import pandas as pd

        from deeplearning4j_tpu.data import Reducer, ReduceOp, Schema

        schema = (Schema.Builder().addColumnString("k")
                  .addColumnDouble("x").addColumnDouble("y").build())
        rng = np.random.RandomState(0)
        recs = [[rng.choice(["a", "b", "c"]), float(rng.randn()),
                 float(rng.randn())] for _ in range(50)]
        red = (Reducer.Builder(ReduceOp.Mean).keyColumns("k")
               .sumColumns("x").stdevColumns("y").build())
        out_schema, out = red.execute(schema, recs)
        assert out_schema.getColumnNames() == ["k", "sum(x)", "stdev(y)"]
        df = pd.DataFrame(recs, columns=["k", "x", "y"])
        g = df.groupby("k")
        for k, sx, sy in out:
            assert sx == pytest.approx(g["x"].sum()[k])
            assert sy == pytest.approx(g["y"].std()[k])  # pandas = sample

    def test_reducer_count_min_max_first_last(self):
        from deeplearning4j_tpu.data import Reducer, ReduceOp, Schema

        schema = (Schema.Builder().addColumnString("k")
                  .addColumnDouble("v").addColumnString("tag").build())
        recs = [["a", 3.0, "p"], ["a", 1.0, "q"], ["b", 7.0, "r"]]
        red = (Reducer.Builder(ReduceOp.TakeLast).keyColumns("k")
               .countColumns("v").build())
        out_schema, out = red.execute(schema, recs)
        assert out_schema.getColumnNames() == ["k", "count(v)", "tag"]
        assert out == [["a", 2, "q"], ["b", 1, "r"]]
        red2 = (Reducer.Builder(ReduceOp.Min).keyColumns("k")
                .maxColumns("v").takeFirstColumns("tag").build())
        _, out2 = red2.execute(schema, recs)
        assert out2 == [["a", 3.0, "p"], ["b", 7.0, "r"]]
        with pytest.raises(ValueError, match="key column"):
            red.execute((Schema.Builder().addColumnDouble("z").build()),
                        [[1.0]])

    def test_condition_filter_in_transform_process(self):
        from deeplearning4j_tpu.data import (ConditionFilter, ConditionOp,
                                             DoubleColumnCondition,
                                             CategoricalColumnCondition,
                                             Schema, TransformProcess)

        schema = (Schema.Builder().addColumnDouble("amount")
                  .addColumnCategorical("tier", "gold", "basic").build())
        recs = [[10.0, "gold"], [0.5, "basic"], [3.0, "basic"],
                [0.1, "gold"]]
        tp = (TransformProcess.Builder(schema)
              .filter(ConditionFilter(DoubleColumnCondition(
                  "amount", ConditionOp.LessThan, 1.0)))
              .build())
        assert tp.execute(recs) == [[10.0, "gold"], [3.0, "basic"]]
        tp2 = (TransformProcess.Builder(schema)
               .filter(ConditionFilter(CategoricalColumnCondition(
                   "tier", ConditionOp.InSet, {"basic"})))
               .build())
        assert tp2.execute(recs) == [[10.0, "gold"], [0.1, "gold"]]
        with pytest.raises(ValueError, match="ConditionOp"):
            DoubleColumnCondition("amount", "Approximately", 1.0)

    def test_data_analysis_summary(self):
        from deeplearning4j_tpu.data import Schema, analyze

        schema = (Schema.Builder().addColumnDouble("x")
                  .addColumnCategorical("c", "u", "v").build())
        recs = [[1.0, "u"], [-2.0, "v"], [0.0, "u"], [None, None]]
        da = analyze(schema, recs)
        ax = da.getColumnAnalysis("x")
        assert ax.min == -2.0 and ax.max == 1.0
        assert ax.mean == pytest.approx(-1 / 3)
        assert ax.countMissing == 1 and ax.countZero == 1 \
            and ax.countNegative == 1
        ac = da.getColumnAnalysis("c")
        assert ac.mapOfUniqueAndCounts == {"u": 2, "v": 1}
        assert "'x' (double)" in repr(da)
        with pytest.raises(ValueError, match="no analysis"):
            da.getColumnAnalysis("nope")


class TestTransformBreadth:
    """Round-4 column-transform additions (reference: datavec-api
    transform.{string,column,doubletransform} classes)."""

    def _schema(self):
        from deeplearning4j_tpu.data import Schema

        return (Schema.Builder().addColumnString("name")
                .addColumnDouble("a").addColumnDouble("b")
                .addColumnInteger("code").build())

    def _recs(self):
        return [["x", 2.0, 4.0, 0], ["y ", 3.0, 6.0, 1], ["x", 1.0, 0.5, 2]]

    def test_string_and_categorical_retypes(self):
        from deeplearning4j_tpu.data import TransformProcess

        tp = (TransformProcess.Builder(self._schema())
              .stringMapTransform("name", {"y ": "y"})
              .appendStringColumnTransform("name", "_v1")
              .stringToCategorical("name", ["x_v1", "y_v1"])
              .integerToCategorical("code", ["lo", "mid", "hi"])
              .build())
        out = tp.execute(self._recs())
        assert [r[0] for r in out] == ["x_v1", "y_v1", "x_v1"]
        assert [r[3] for r in out] == ["lo", "mid", "hi"]
        fs = tp.getFinalSchema()
        assert fs.getType("name") == "categorical"
        assert fs.getMeta("code") == ["lo", "mid", "hi"]
        tp_bad = (TransformProcess.Builder(self._schema())
                  .stringToCategorical("name", ["x"]).build())
        with pytest.raises(ValueError, match="not in states"):
            tp_bad.execute(self._recs())

    def test_derived_and_structural_columns(self):
        from deeplearning4j_tpu.data import TransformProcess

        tp = (TransformProcess.Builder(self._schema())
              .doubleColumnsMathOp("ratio", "Divide", "a", "b")
              .addConstantColumn("ds", "string", "train")
              .duplicateColumn("a", "a_copy")
              .reorderColumns("ds", "name")
              .build())
        out = tp.execute(self._recs())
        fs = tp.getFinalSchema()
        assert fs.getColumnNames() == ["ds", "name", "a", "b", "code",
                                       "ratio", "a_copy"]
        assert out[0] == ["train", "x", 2.0, 4.0, 0, 0.5, 2.0]
        tp2 = (TransformProcess.Builder(self._schema())
               .removeAllColumnsExceptFor("a", "code").build())
        assert tp2.getFinalSchema().getColumnNames() == ["a", "code"]
        assert tp2.execute(self._recs())[1] == [3.0, 1]
        with pytest.raises(ValueError, match="unknown"):
            (TransformProcess.Builder(self._schema())
             .reorderColumns("nope").build().execute(self._recs()))
        with pytest.raises(ValueError, match="unknown"):
            (TransformProcess.Builder(self._schema())
             .removeAllColumnsExceptFor("labl").build()
             .execute(self._recs()))
        # Divide by zero: Java double semantics, not ZeroDivisionError
        tp3 = (TransformProcess.Builder(self._schema())
               .doubleColumnsMathOp("r", "Divide", "a", "b").build())
        out3 = tp3.execute([["x", 1.0, 0.0, 0], ["y", 0.0, 0.0, 1]])
        assert out3[0][-1] == float("inf")
        assert out3[1][-1] != out3[1][-1]  # NaN

    def test_conditional_replace_and_missing(self):
        from deeplearning4j_tpu.data import (ConditionOp,
                                             DoubleColumnCondition,
                                             TransformProcess)

        recs = [["x", 2.0, float("nan"), 0], ["y", -5.0, 1.0, None],
                ["z", 1.0, "", 2]]
        tp = (TransformProcess.Builder(self._schema())
              .conditionalReplaceValueTransform(
                  "a", 0.0, DoubleColumnCondition(
                      "a", ConditionOp.LessThan, 0.0))
              .replaceMissingWithValue("b", -1.0)
              .replaceMissingWithValue("code", 9)
              .build())
        out = tp.execute(recs)
        assert out[1][1] == 0.0 and out[0][1] == 2.0
        assert out[0][2] == -1.0 and out[1][3] == 9
        assert out[2][2] == -1.0  # "" = CSVRecordReader's missing field


class TestSequenceRecords:
    """CSVSequenceRecordReader + SequenceRecordReaderDataSetIterator
    (reference: datavec sequence readers feeding recurrent nets)."""

    def _write_seqs(self, tmp_path, lengths, nfeat=3):
        fdir = tmp_path / "features"
        ldir = tmp_path / "labels"
        fdir.mkdir()
        ldir.mkdir()
        rng = np.random.RandomState(0)
        for i, T in enumerate(lengths):
            feats = rng.rand(T, nfeat)
            labs = rng.randint(0, 2, (T, 1))
            (fdir / f"seq_{i}.csv").write_text(
                "\n".join(",".join(f"{v:.6f}" for v in row) for row in feats))
            (ldir / f"seq_{i}.csv").write_text(
                "\n".join(str(int(v[0])) for v in labs))
        return str(fdir), str(ldir)

    def test_reader_per_file_sequences(self, tmp_path):
        from deeplearning4j_tpu.data import CSVSequenceRecordReader

        fdir, _ = self._write_seqs(tmp_path, [4, 6])
        rr = CSVSequenceRecordReader().initialize(fdir)
        s0 = rr.next()
        s1 = rr.next()
        assert len(s0) == 4 and len(s1) == 6 and len(s0[0]) == 3
        assert not rr.hasNext()
        rr.reset()
        assert rr.hasNext()

    def test_iterator_pads_and_masks(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)

        fdir, ldir = self._write_seqs(tmp_path, [4, 6, 5])
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=3, numPossibleLabels=2)
        ds = it.next()
        x = ds.getFeatures().toNumpy()
        y = ds.getLabels().toNumpy()
        m = ds.getFeaturesMaskArray().toNumpy()
        assert x.shape == (3, 3, 6) and y.shape == (3, 2, 6)
        np.testing.assert_array_equal(m.sum(1), [4, 6, 5])
        # padding region is zero and one-hot labels sum to 1 on real steps
        assert x[0, :, 4:].sum() == 0
        np.testing.assert_array_equal(y[0, :, :4].sum(0), np.ones(4))
        assert y[0, :, 4:].sum() == 0

    def test_trains_masked_rnn(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, LSTM,
                                           RnnOutputLayer, Adam)

        fdir, ldir = self._write_seqs(tmp_path, [4, 6, 5, 7])
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list().layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(3)).build())
        net = MultiLayerNetwork(conf).init()
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=4, numPossibleLabels=2)
        for _ in range(3):
            net.fit(it)
        assert np.isfinite(net.score())

    def test_misaligned_readers_rejected(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)

        fdir, _ = self._write_seqs(tmp_path, [4, 6])
        (tmp_path / "b").mkdir()
        _, ldir = self._write_seqs(tmp_path / "b", [5, 6])
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=2, numPossibleLabels=2)
        with pytest.raises(ValueError, match="aligned"):
            it.next()

    def test_ragged_regression_label_width_rejected(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)

        fdir, _ = self._write_seqs(tmp_path, [3, 3])
        ldir = tmp_path / "rlabels"
        ldir.mkdir()
        # sequence 0 has 2 label columns, sequence 1 has 3 — must raise
        # the iterator's descriptive error, not a numpy broadcast error
        (ldir / "seq_0.csv").write_text("0.1,0.2\n0.3,0.4\n0.5,0.6")
        (ldir / "seq_1.csv").write_text("0.1,0.2,0.9\n0.3,0.4,0.9\n0.5,0.6,0.9")
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(str(ldir)),
            miniBatchSize=2, regression=True)
        with pytest.raises(ValueError, match="label width"):
            it.next()

    def test_edge_cases_rejected_clearly(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)

        fdir, ldir = self._write_seqs(tmp_path, [3, 3])
        # subdirectory in the source dir is skipped, not opened
        (tmp_path / "features" / "sub").mkdir()
        rr = CSVSequenceRecordReader().initialize(fdir)
        assert len(rr._files) == 2
        # exhausted next() is loud
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=2, numPossibleLabels=2)
        it.next()
        with pytest.raises(ValueError, match="exhausted"):
            it.next()
        # out-of-range label is loud
        (tmp_path / "l2").mkdir()
        for i in range(2):
            (tmp_path / "l2" / f"seq_{i}.csv").write_text("7\n0\n1")
        it2 = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(str(tmp_path / "l2")),
            miniBatchSize=2, numPossibleLabels=2)
        with pytest.raises(ValueError, match="outside"):
            it2.next()
        # mismatched file counts are loud
        (tmp_path / "l3").mkdir()
        (tmp_path / "l3" / "seq_0.csv").write_text("0\n1\n0")
        it3 = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(str(tmp_path / "l3")),
            miniBatchSize=1, numPossibleLabels=2)
        with pytest.raises(ValueError, match="different sequence counts"):
            it3.next()
        # regression + numPossibleLabels=None constructs fine
        SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(fdir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=2, numPossibleLabels=None, regression=True)

    def test_empty_sequence_file_and_zero_batch_rejected(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVSequenceRecordReader,
                                             SequenceRecordReaderDataSetIterator)

        fdir, ldir = self._write_seqs(tmp_path, [3])
        (tmp_path / "features" / "seq_z.csv").write_text("")
        rr = CSVSequenceRecordReader().initialize(fdir)
        rr.next()  # seq_0 fine
        with pytest.raises(ValueError, match="empty sequence file"):
            rr.next()
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader().initialize(ldir),
            CSVSequenceRecordReader().initialize(ldir),
            miniBatchSize=1, numPossibleLabels=2)
        with pytest.raises(ValueError, match="positive"):
            it.next(0)


class TestDatasetIteratorVariants:
    """FashionMnist/Emnist iterators (reference: the corresponding
    deeplearning4j-datasets iterators): idx-or-synthetic loading with
    the right class counts."""

    def test_fashion_mnist_shapes(self):
        from deeplearning4j_tpu.data import FashionMnistDataSetIterator

        it = FashionMnistDataSetIterator(32, train=True, numExamples=96)
        ds = it.next()
        assert ds.getFeatures().shape() == (32, 784)
        assert ds.getLabels().shape() == (32, 10)

    def test_emnist_class_counts_and_validation(self):
        from deeplearning4j_tpu.data import EmnistDataSetIterator

        it = EmnistDataSetIterator("letters", 16, numExamples=64,
                                   reshapeToCnn=True)
        ds = it.next()
        assert ds.getFeatures().shape() == (16, 1, 28, 28)
        assert ds.getLabels().shape() == (16, 26)
        assert EmnistDataSetIterator("balanced", 8, numExamples=16
                                     ).next().getLabels().shape() == (8, 47)
        with pytest.raises(ValueError, match="unknown EMNIST"):
            EmnistDataSetIterator("bogus", 8)


class TestUtilityIterators:
    """KFoldIterator / MultipleEpochsIterator / ViewIterator (reference:
    org.deeplearning4j.datasets.iterator KFoldIterator,
    MultipleEpochsIterator, impl.ViewIterator)."""

    def _ds(self, n=10):
        f = np.arange(n * 3, dtype="float32").reshape(n, 3)
        l = np.eye(2, dtype="float32")[np.arange(n) % 2]
        from deeplearning4j_tpu.data import DataSet
        return DataSet(f, l)

    def test_kfold_partition(self):
        from deeplearning4j_tpu.data import KFoldIterator
        ds = self._ds(10)
        it = KFoldIterator(3, ds)   # fold sizes 4,3,3
        seen_test_rows = []
        folds = 0
        while it.hasNext():
            train = it.next()
            test = it.testFold()
            folds += 1
            assert train.numExamples() + test.numExamples() == 10
            tr = train.getFeatures().toNumpy()[:, 0]
            te = test.getFeatures().toNumpy()[:, 0]
            assert not set(tr) & set(te)  # disjoint
            seen_test_rows.extend(te.tolist())
        assert folds == 3
        # every example held out exactly once across folds
        assert sorted(seen_test_rows) == [float(3 * i) for i in range(10)]

    def test_kfold_sizes_first_folds_larger(self):
        from deeplearning4j_tpu.data import KFoldIterator
        it = KFoldIterator(3, self._ds(10))
        sizes = [it.next().numExamples() for _ in range(3)]
        assert sizes == [6, 7, 7]  # tests are 4,3,3

    def test_kfold_validation(self):
        from deeplearning4j_tpu.data import KFoldIterator
        with pytest.raises(ValueError, match="k must be"):
            KFoldIterator(1, self._ds(10))
        with pytest.raises(ValueError, match="exceeds"):
            KFoldIterator(20, self._ds(10))

    def test_multiple_epochs_replays(self):
        from deeplearning4j_tpu.data import (DataSetIterator,
                                             MultipleEpochsIterator)
        f = np.arange(8, dtype="float32").reshape(4, 2)
        l = np.eye(2, dtype="float32")[[0, 1, 0, 1]]
        it = MultipleEpochsIterator(3, DataSetIterator(f, l, 2))
        batches = [b for b in it]
        assert len(batches) == 6  # 2 batches/epoch x 3 epochs
        assert it.totalExamples() == 12
        # resets cleanly for a second pass
        assert len([b for b in it]) == 6

    def test_multiple_epochs_trains_like_epochs_arg(self):
        from deeplearning4j_tpu.data import (DataSetIterator,
                                             MultipleEpochsIterator)
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration,
                                           DenseLayer, OutputLayer,
                                           MultiLayerNetwork, Adam)
        rng = np.random.RandomState(0)
        X = rng.randn(64, 4).astype("float32")
        Y = np.eye(2, dtype="float32")[(X.sum(1) > 0).astype(int)]

        def build():
            conf = (NeuralNetConfiguration.Builder().seed(3)
                    .updater(Adam(1e-2)).list()
                    .layer(DenseLayer(nIn=4, nOut=8, activation="tanh"))
                    .layer(OutputLayer(nOut=2, activation="softmax"))
                    .build())
            return MultiLayerNetwork(conf).init()

        a = build()
        a.fit(DataSetIterator(X, Y, 32), epochs=4)
        b = build()
        b.fit(MultipleEpochsIterator(4, DataSetIterator(X, Y, 32)))
        assert abs(a.score() - b.score()) < 1e-5

    def test_view_iterator(self):
        from deeplearning4j_tpu.data import ViewIterator
        it = ViewIterator(self._ds(10), 4)
        b1 = it.next()
        assert b1.numExamples() == 4
        np.testing.assert_allclose(
            b1.getFeatures().toNumpy()[:, 0], [0.0, 3.0, 6.0, 9.0])

    def test_kfold_reset_clears_test_fold(self):
        from deeplearning4j_tpu.data import KFoldIterator
        it = KFoldIterator(3, self._ds(9))
        while it.hasNext():
            it.next()
        it.reset()
        with pytest.raises(RuntimeError, match="next"):
            it.testFold()

    def test_multiple_epochs_normalizer_stats_unbiased(self):
        # NormalizerStandardize.fit must see one UNPADDED pass, not
        # numEpochs padded replays
        from deeplearning4j_tpu.data import (DataSetIterator,
                                             MultipleEpochsIterator)
        from deeplearning4j_tpu.data.normalizers import NormalizerStandardize
        f = np.arange(10, dtype="float32").reshape(5, 2)  # odd vs batch 2
        l = np.eye(2, dtype="float32")[[0, 1, 0, 1, 0]]
        n1, n2 = NormalizerStandardize(), NormalizerStandardize()
        n1.fit(DataSetIterator(f, l, 2))
        n2.fit(MultipleEpochsIterator(3, DataSetIterator(f, l, 2)))
        np.testing.assert_allclose(np.asarray(n1._mean), np.asarray(n2._mean))
        np.testing.assert_allclose(np.asarray(n1._std), np.asarray(n2._std))


class TestMiniBatchFileIterator:
    """MiniBatchFileDataSetIterator (reference: org.deeplearning4j
    .datasets.iterator.MiniBatchFileDataSetIterator)."""

    def _ds(self, n=10):
        from deeplearning4j_tpu.data import DataSet
        f = np.arange(n * 2, dtype="float32").reshape(n, 2)
        l = np.eye(2, dtype="float32")[np.arange(n) % 2]
        return DataSet(f, l)

    def test_batches_roundtrip_from_disk(self, tmp_path):
        import os
        from deeplearning4j_tpu.data import MiniBatchFileDataSetIterator
        it = MiniBatchFileDataSetIterator(self._ds(10), 4,
                                          rootDir=tmp_path / "mb")
        assert len(os.listdir(it.rootDir())) == 3  # 4+4+2
        batches = [b for b in it]
        # final batch PADS to the fixed shape with a zero label-mask
        # over the pad rows (module invariant: one XLA executable)
        assert [b.numExamples() for b in batches] == [4, 4, 4]
        lm = batches[-1].getLabelsMaskArray().toNumpy()
        np.testing.assert_allclose(lm, [1, 1, 0, 0])
        all_f = np.concatenate([b.getFeatures().toNumpy()
                                for b in batches[:2]]
                               + [batches[2].getFeatures().toNumpy()[:2]])
        np.testing.assert_allclose(all_f,
                                   self._ds(10).getFeatures().toNumpy())
        assert it.totalExamples() == 10
        assert it.inputColumns() == 2 and it.totalOutcomes() == 2
        # second pass re-reads the same files
        assert len([b for b in it]) == 3

    def test_masks_persist(self, tmp_path):
        from deeplearning4j_tpu.data import (DataSet,
                                             MiniBatchFileDataSetIterator)
        f = np.zeros((5, 2, 3), "float32")
        l = np.zeros((5, 2, 3), "float32")
        fm = np.arange(15, dtype="float32").reshape(5, 3)
        it = MiniBatchFileDataSetIterator(
            DataSet(f, l, featuresMask=fm), 5, rootDir=tmp_path / "mbm")
        b = it.next()
        np.testing.assert_allclose(b.getFeaturesMaskArray().toNumpy(), fm)

    def test_composes_with_normalizer_and_epochs(self, tmp_path):
        from deeplearning4j_tpu.data import (
            DataSetIterator, MiniBatchFileDataSetIterator,
            MultipleEpochsIterator)
        from deeplearning4j_tpu.data.normalizers import NormalizerStandardize
        ds = self._ds(10)
        it = MiniBatchFileDataSetIterator(ds, 4, rootDir=tmp_path / "mbn")
        n1, n2 = NormalizerStandardize(), NormalizerStandardize()
        n1.fit(MultipleEpochsIterator(2, it))
        n2.fit(DataSetIterator(ds.getFeatures().toNumpy(),
                               ds.getLabels().toNumpy(), 4))
        np.testing.assert_allclose(np.asarray(n1._mean),
                                   np.asarray(n2._mean))

    def test_next_num_rejected(self, tmp_path):
        from deeplearning4j_tpu.data import MiniBatchFileDataSetIterator
        it = MiniBatchFileDataSetIterator(self._ds(8), 4,
                                          rootDir=tmp_path / "mbx")
        with pytest.raises(ValueError, match="re-batch"):
            it.next(3)

    def test_delete_on_exhaust(self, tmp_path):
        import os
        from deeplearning4j_tpu.data import MiniBatchFileDataSetIterator
        it = MiniBatchFileDataSetIterator(self._ds(6), 3,
                                          rootDir=tmp_path / "mb2",
                                          delete_on_exhaust=True)
        list(it)
        assert os.listdir(it.rootDir()) == []

    def test_delete_on_exhaust_reset_raises(self, tmp_path):
        from deeplearning4j_tpu.data import MiniBatchFileDataSetIterator
        it = MiniBatchFileDataSetIterator(self._ds(6), 3,
                                          rootDir=tmp_path / "mbr",
                                          delete_on_exhaust=True)
        assert len([b for b in it]) == 2
        with pytest.raises(RuntimeError, match="delete_on_exhaust"):
            it.reset()


class TestTransformProcessJson:
    """TransformProcess.toJson/fromJson (reference: DataVec
    TransformProcess JSON persistence)."""

    def _schema(self):
        return (Schema.Builder().addColumnDouble("x")
                .addColumnCategorical("c", "a", "b")
                .addColumnString("s").build())

    def test_roundtrip_execution_parity(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        tp = (TP.Builder(self._schema())
              .doubleMathOp("x", "Multiply", 3.0)
              .categoricalToOneHot("c")
              .appendStringColumnTransform("s", "_z")
              .build())
        tp2 = TP.fromJson(tp.toJson())
        rows = [[1.0, "a", "p"], [2.0, "b", "q"]]
        assert tp2.execute([list(r) for r in rows]) == \
            tp.execute([list(r) for r in rows])
        assert tp2.getFinalSchema().getColumnNames() == \
            tp.getFinalSchema().getColumnNames()

    def test_condition_filter_roundtrips(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        from deeplearning4j_tpu.data.transform import (
            ColumnCondition, ConditionFilter, ConditionOp)
        tp = (TP.Builder(self._schema())
              .filter(ConditionFilter(ColumnCondition(
                  "c", ConditionOp.InSet, {"b"})))
              .build())
        tp2 = TP.fromJson(tp.toJson())
        out = tp2.execute([[1.0, "a", "p"], [2.0, "b", "q"]])
        assert out == [[1.0, "a", "p"]]  # 'b' rows removed

    def test_conditional_replace_roundtrips(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        from deeplearning4j_tpu.data.transform import (
            ColumnCondition, ConditionOp)
        tp = (TP.Builder(self._schema())
              .conditionalReplaceValueTransform(
                  "x", -1.0, ColumnCondition("x", ConditionOp.GreaterThan,
                                             5.0))
              .build())
        tp2 = TP.fromJson(tp.toJson())
        assert tp2.execute([[9.0, "a", "p"]]) == [[-1.0, "a", "p"]]

    def test_raw_callable_filter_refuses_loudly(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        tp = (TP.Builder(self._schema())
              .filter(lambda rec: rec["x"] > 0)
              .build())
        with pytest.raises(ValueError, match="cannot be serialized"):
            tp.toJson()

    def test_json_is_plain_data(self):
        import json
        from deeplearning4j_tpu.data import TransformProcess as TP
        tp = (TP.Builder(self._schema())
              .removeColumns("s").renameColumn("x", "y").build())
        d = json.loads(tp.toJson())
        assert [e["op"] for e in d["steps"]] == ["removeColumns",
                                                 "renameColumn"]
        assert d["initialSchema"]["columns"][0] == ["x", "double", None]

    def test_builder_mutation_after_build_stays_consistent(self):
        # _steps/_spec/_unserializable share storage: a builder mutated
        # after build() must not leave the process executing steps its
        # serialized form omits
        from deeplearning4j_tpu.data import TransformProcess as TP
        b = TP.Builder(self._schema())
        tp = b.build()
        b.filter(lambda rec: rec["x"] > 0)
        assert tp.execute([[1.0, "a", "p"], [-1.0, "b", "q"]]) == \
            [[-1.0, "b", "q"]]  # the filter runs
        with pytest.raises(ValueError, match="cannot be serialized"):
            tp.toJson()        # ...so serialization must refuse

    def test_int_keyed_mapping_roundtrips(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        s = Schema.Builder().addColumnInteger("i").build()
        tp = TP.Builder(s).stringMapTransform("i", {1: 99}).build()
        tp2 = TP.fromJson(tp.toJson())
        assert tp2.execute([[1], [2]]) == tp.execute([[1], [2]]) == \
            [[99], [2]]

    def test_arg_mutation_after_record_does_not_leak(self):
        from deeplearning4j_tpu.data import TransformProcess as TP
        m = {"a": "b"}
        tp = TP.Builder(self._schema()).stringMapTransform("s", m).build()
        m["a"] = "CHANGED"
        tp2 = TP.fromJson(tp.toJson())
        assert tp.execute([[1.0, "a", "a"]]) == \
            tp2.execute([[1.0, "a", "a"]]) == [[1.0, "a", "b"]]

    def test_numpy_scalar_arg_serializes(self):
        import numpy as _np
        from deeplearning4j_tpu.data import TransformProcess as TP
        tp = (TP.Builder(self._schema())
              .doubleMathOp("x", "Multiply", _np.float64(2.0)).build())
        tp2 = TP.fromJson(tp.toJson())  # must NOT be "unserializable"
        assert tp2.execute([[3.0, "a", "p"]])[0][0] == 6.0


class TestRecordReaderMultiDataSetIterator:
    """Multi-input/-output reader batches (reference:
    org.deeplearning4j.datasets.datavec.RecordReaderMultiDataSetIterator)."""

    def _csv(self, tmp_path, name, rows):
        p = tmp_path / name
        p.write_text("\n".join(",".join(str(v) for v in r) for r in rows))
        return CSVRecordReader().initialize(p)

    def test_two_readers_sliced_inputs_onehot_output(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        rr1 = self._csv(tmp_path, "a.csv",
                        [[i * 0.1, i * 0.2, i * 0.3] for i in range(10)])
        rr2 = self._csv(tmp_path, "b.csv",
                        [[i * 1.0, i % 3] for i in range(10)])
        it = (RecordReaderMultiDataSetIterator.Builder(4)
              .addReader("a", rr1).addReader("b", rr2)
              .addInput("a", 0, 1)        # two columns
              .addInput("b", 0, 0)        # one column
              .addOutputOneHot("b", 1, 3)
              .build())
        mds = it.next()
        f = mds.getFeatures()
        assert len(f) == 2
        assert f[0].shape() == (4, 2) and f[1].shape() == (4, 1)
        l = mds.getLabels()
        assert len(l) == 1 and l[0].shape() == (4, 3)
        np.testing.assert_allclose(l[0].toNumpy().sum(-1), 1.0)
        np.testing.assert_allclose(f[0].toNumpy()[1], [0.1, 0.2], rtol=1e-6)

    def test_whole_record_input_and_range_output(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        rr = self._csv(tmp_path, "c.csv",
                       [[i, i + 1, i * 0.5] for i in range(6)])
        it = (RecordReaderMultiDataSetIterator.Builder(6)
              .addReader("r", rr)
              .addInput("r", 0, 1)
              .addOutput("r", 2, 2)
              .build())
        mds = it.next()
        assert mds.getLabels()[0].shape() == (6, 1)
        np.testing.assert_allclose(mds.getLabels()[0].toNumpy()[:, 0],
                                   [0, 0.5, 1.0, 1.5, 2.0, 2.5])

    def test_count_mismatch_raises(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        rr1 = self._csv(tmp_path, "d.csv", [[1, 2]] * 4)
        rr2 = self._csv(tmp_path, "e.csv", [[1, 0]] * 5)
        with pytest.raises(ValueError, match="record count"):
            (RecordReaderMultiDataSetIterator.Builder(2)
             .addReader("x", rr1).addReader("y", rr2)
             .addInput("x").addOutputOneHot("y", 1, 2).build())

    def test_validation_errors(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        B = RecordReaderMultiDataSetIterator.Builder
        rr = self._csv(tmp_path, "f.csv", [[1, 2]] * 3)
        with pytest.raises(ValueError, match="unknown reader"):
            B(2).addReader("r", rr).addInput("nope")
        with pytest.raises(ValueError, match="addInput"):
            B(2).addReader("r", rr).addOutput("r", 0, 0).build()
        rr2 = self._csv(tmp_path, "g.csv", [[1, 9]] * 3)
        with pytest.raises(ValueError, match="outside"):
            (B(2).addReader("r", rr2).addInput("r", 0, 0)
             .addOutputOneHot("r", 1, 3).build())

    def test_feeds_two_input_graph(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer,
                                           InputType, MergeVertex,
                                           NeuralNetConfiguration,
                                           OutputLayer, Adam)
        rng = np.random.RandomState(0)
        a = rng.randn(48, 3)
        b = rng.randn(48, 2)
        y = ((a.sum(1) + b.sum(1)) > 0).astype(int)
        rr1 = self._csv(tmp_path, "ga.csv", a.round(4).tolist())
        rr2 = self._csv(tmp_path, "gb.csv",
                        [[*row.round(4), int(lab)]
                         for row, lab in zip(b, y)])
        it = (RecordReaderMultiDataSetIterator.Builder(16)
              .addReader("a", rr1).addReader("b", rr2)
              .addInput("a")
              .addInput("b", 0, 1)
              .addOutputOneHot("b", 2, 2)
              .build())
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("inA", "inB")
                .addLayer("dA", DenseLayer(nIn=3, nOut=8,
                                           activation="tanh"), "inA")
                .addLayer("dB", DenseLayer(nIn=2, nOut=8,
                                           activation="tanh"), "inB")
                .addVertex("merge", MergeVertex(), "dA", "dB")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                          "merge")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(3),
                               InputType.feedForward(2))
                .build())
        net = ComputationGraph(conf).init()
        for _ in range(40):
            net.fit(it)
        out = net.outputSingle(a.astype("float32"), b.astype("float32"))
        acc = (np.asarray(out.toNumpy()).argmax(1) == y).mean()
        assert acc > 0.9, acc


class TestExistingMiniBatchIterator:
    def test_reads_writer_output(self, tmp_path):
        from deeplearning4j_tpu.data import (
            DataSet, ExistingMiniBatchDataSetIterator,
            MiniBatchFileDataSetIterator)
        f = np.arange(12, dtype="float32").reshape(6, 2)
        l = np.eye(2, dtype="float32")[np.arange(6) % 2]
        MiniBatchFileDataSetIterator(DataSet(f, l), 3,
                                     rootDir=tmp_path / "mb")
        it = ExistingMiniBatchDataSetIterator(tmp_path / "mb")
        batches = [b for b in it]
        assert len(batches) == 2
        np.testing.assert_allclose(
            np.concatenate([b.getFeatures().toNumpy() for b in batches]), f)

    def test_missing_dir_and_empty(self, tmp_path):
        from deeplearning4j_tpu.data import ExistingMiniBatchDataSetIterator
        with pytest.raises(ValueError, match="not a directory"):
            ExistingMiniBatchDataSetIterator(tmp_path / "nope")
        (tmp_path / "empty").mkdir()
        with pytest.raises(ValueError, match="no files matching"):
            ExistingMiniBatchDataSetIterator(tmp_path / "empty")

    def test_interop_surface_and_padding(self, tmp_path):
        from deeplearning4j_tpu.data import (
            DataSet, DataSetIterator, ExistingMiniBatchDataSetIterator,
            MiniBatchFileDataSetIterator, MultipleEpochsIterator)
        from deeplearning4j_tpu.data.normalizers import NormalizerStandardize
        f = np.arange(14, dtype="float32").reshape(7, 2)
        l = np.eye(2, dtype="float32")[np.arange(7) % 2]
        MiniBatchFileDataSetIterator(DataSet(f, l), 3,
                                     rootDir=tmp_path / "mb7")
        it = ExistingMiniBatchDataSetIterator(tmp_path / "mb7")
        assert it.batch() == 3 and it.totalExamples() == 7
        assert it.inputColumns() == 2 and it.totalOutcomes() == 2
        batches = [b for b in it]
        # final short file pads at read time with a zero label mask
        assert [b.numExamples() for b in batches] == [3, 3, 3]
        np.testing.assert_allclose(
            batches[-1].getLabelsMaskArray().toNumpy(), [1, 0, 0])
        # wraps in MultipleEpochsIterator, and normalizer stats are
        # unpadded + preprocessor-free
        meit = MultipleEpochsIterator(2, it)
        assert meit.batch() == 3
        n1, n2 = NormalizerStandardize(), NormalizerStandardize()
        it.setPreProcessor(n1)
        n1.fit(it)
        n2.fit(DataSetIterator(f, l, 3))
        np.testing.assert_allclose(np.asarray(n1._mean),
                                   np.asarray(n2._mean))
        with pytest.raises(ValueError, match="re-batch"):
            it.next(2)

    def test_ragged_row_diagnostic(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVRecordReader,
                                             RecordReaderMultiDataSetIterator)
        p = tmp_path / "ragged.csv"
        p.write_text("1,2,3\n4,5\n6,7,8\n")
        # subclass defeats the exact-type bulk fast path so the row loop
        # (whose diagnostics we are testing) actually runs
        class SlowCSV(CSVRecordReader):
            pass
        rr = SlowCSV().initialize(p)
        # the shortest row (2 cols) governs the valid range, so a spec
        # reaching col 2 fails loudly up front instead of IndexError
        # mid-parse
        with pytest.raises(ValueError, match="shortest row"):
            (RecordReaderMultiDataSetIterator.Builder(2)
             .addReader("r", rr).addInput("r", 0, 2)
             .addOutputOneHot("r", 0, 9).build())


class TestSequenceMultiReader:
    """addSequenceReader in RecordReaderMultiDataSetIterator (reference
    overload): sequence specs produce padded+masked [B, C, T] arrays."""

    def _seq_files(self, tmp_path, name, seqs):
        d = tmp_path / name
        d.mkdir()
        for i, rows in enumerate(seqs):
            (d / f"seq_{i:02d}.csv").write_text(
                "\n".join(",".join(str(v) for v in r) for r in rows))
        from deeplearning4j_tpu.data import CSVSequenceRecordReader
        return CSVSequenceRecordReader().initialize(d)

    def test_padded_masked_ncw(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        srr = self._seq_files(tmp_path, "s1", [
            [[1, 10], [2, 20], [3, 30]],     # T=3
            [[4, 40]],                        # T=1
        ])
        it = (RecordReaderMultiDataSetIterator.Builder(2)
              .addSequenceReader("s", srr)
              .addInput("s", 0, 0)
              .addOutput("s", 1, 1)
              .build())
        mds = it.next()
        f = mds.getFeatures()[0].toNumpy()
        assert f.shape == (2, 1, 3)          # NCW, padded to Tmax=3
        np.testing.assert_allclose(f[0, 0], [1, 2, 3])
        np.testing.assert_allclose(f[1, 0], [4, 0, 0])
        fm = mds.getFeaturesMaskArrays()[0].toNumpy()
        np.testing.assert_allclose(fm, [[1, 1, 1], [1, 0, 0]])
        lm = mds.getLabelsMaskArrays()[0].toNumpy()
        np.testing.assert_allclose(lm, fm)

    def test_per_step_onehot_labels(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        srr = self._seq_files(tmp_path, "s2", [
            [[0.5, 0], [0.6, 2]],
            [[0.7, 1], [0.8, 1]],
        ])
        it = (RecordReaderMultiDataSetIterator.Builder(2)
              .addSequenceReader("s", srr)
              .addInput("s", 0, 0)
              .addOutputOneHot("s", 1, 3)
              .build())
        l = it.next().getLabels()[0].toNumpy()
        assert l.shape == (2, 3, 2)          # [B, classes, T]
        np.testing.assert_allclose(l[0, :, 0], [1, 0, 0])
        np.testing.assert_allclose(l[0, :, 1], [0, 0, 1])
        np.testing.assert_allclose(l[1, :, 0], [0, 1, 0])

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_mixed_static_and_sequence_trains_graph(self, tmp_path):
        from deeplearning4j_tpu.data import (CSVRecordReader,
                                             RecordReaderMultiDataSetIterator)
        from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer,
                                           InputType, MergeVertex,
                                           NeuralNetConfiguration,
                                           OutputLayer, Adam)
        from deeplearning4j_tpu.nn.conf.recurrent import LSTM, LastTimeStep
        rng = np.random.RandomState(1)
        n, T = 32, 4
        seqs = rng.rand(n, T, 1).round(3)
        static = rng.randn(n, 2).round(3)
        y = ((seqs.sum((1, 2)) + static.sum(1)) > 2.0).astype(int)
        srr = self._seq_files(tmp_path, "s3",
                              [s.tolist() for s in seqs])
        p = tmp_path / "static.csv"
        p.write_text("\n".join(
            ",".join(str(v) for v in row) + f",{int(l)}"
            for row, l in zip(static, y)))
        it = (RecordReaderMultiDataSetIterator.Builder(16)
              .addSequenceReader("seq", srr)
              .addReader("st", CSVRecordReader().initialize(p))
              .addInput("seq")
              .addInput("st", 0, 1)
              .addOutputOneHot("st", 2, 2)
              .build())
        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("inSeq", "inSt")
                .addLayer("rnn", LastTimeStep(LSTM(nIn=1, nOut=8)), "inSeq")
                .addLayer("dSt", DenseLayer(nIn=2, nOut=8,
                                            activation="tanh"), "inSt")
                .addVertex("m", MergeVertex(), "rnn", "dSt")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                          "m")
                .setOutputs("out")
                .setInputTypes(InputType.recurrent(1, T),
                               InputType.feedForward(2))
                .build())
        net = ComputationGraph(conf).init()
        for _ in range(30):
            net.fit(it)
        assert np.isfinite(net.score())
        out = net.outputSingle(
            np.transpose(seqs, (0, 2, 1)).astype("float32"),
            static.astype("float32"))
        acc = (np.asarray(out.toNumpy()).argmax(1) == y).mean()
        assert acc > 0.85, acc

    def test_inconsistent_seq_widths_raise(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        srr = self._seq_files(tmp_path, "s4",
                              [[[1, 2]], [[1, 2, 3]]])
        with pytest.raises(ValueError, match="inconsistent"):
            (RecordReaderMultiDataSetIterator.Builder(2)
             .addSequenceReader("s", srr)
             .addInput("s").addOutput("s", 0, 0).build())

    def test_padded_final_batch_masks_none_entries(self, tmp_path):
        # a None-mask label padded with duplicate rows must gain a
        # zero-tail mask — unmasked duplicates would count in the loss
        from deeplearning4j_tpu.data.multidataset import MultiDataSetIterator
        seqf = np.random.RandomState(0).rand(3, 1, 2).astype("float32")
        seql = np.ones((3, 2, 2), "float32")
        statl = np.eye(2, dtype="float32")[[0, 1, 0]]
        mask = np.ones((3, 2), "float32")
        it = MultiDataSetIterator([seqf], [seql, statl], 2,
                                  featuresMasks=[mask],
                                  labelsMasks=[mask, None])
        it.next()
        mds = it.next()  # final short batch (1 real + 1 pad)
        lms = mds.getLabelsMaskArrays()
        assert lms[1] is not None
        np.testing.assert_allclose(lms[1].toNumpy(), [1.0, 0.0])

    def test_ragged_sequence_diagnostic(self, tmp_path):
        from deeplearning4j_tpu.data import RecordReaderMultiDataSetIterator
        d = tmp_path / "rg"
        d.mkdir()
        (d / "seq_00.csv").write_text("1,2\n1,2,3")
        from deeplearning4j_tpu.data import CSVSequenceRecordReader
        srr = CSVSequenceRecordReader().initialize(d)
        with pytest.raises(ValueError, match="ragged sequence"):
            (RecordReaderMultiDataSetIterator.Builder(1)
             .addSequenceReader("s", srr)
             .addInput("s").addOutput("s", 0, 0).build())


class TestMultipleEpochsEmptyUnderlying:
    """ADVICE r4: hasNext()==True must guarantee next() succeeds even
    when the underlying iterator is EMPTY and epochs remain."""

    class _Empty:
        def reset(self):
            pass

        def hasNext(self):
            return False

        def next(self, num=None):
            raise StopIteration

    def test_empty_underlying_contract(self):
        from deeplearning4j_tpu.data.dataset import MultipleEpochsIterator

        it = MultipleEpochsIterator(3, self._Empty())
        assert not it.hasNext()
        with pytest.raises(StopIteration):
            it.next()
        assert list(iter(it)) == []
