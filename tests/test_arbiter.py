"""Arbiter hyperparameter search (reference: arbiter-deeplearning4j tests)."""

import numpy as np
import pytest

from deeplearning4j_tpu.arbiter import (
    ContinuousParameterSpace, DiscreteParameterSpace, IntegerParameterSpace,
    RandomSearchGenerator, GridSearchCandidateGenerator,
    TestSetLossScoreFunction, EvaluationScoreFunction,
    MaxCandidatesCondition, MaxTimeCondition,
    OptimizationConfiguration, LocalOptimizationRunner,
)
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, DenseLayer, OutputLayer, MultiLayerNetwork, Adam,
    InputType,
)
from deeplearning4j_tpu.nn.losses import LossFunctions
from deeplearning4j_tpu.data import DataSetIterator

LF = LossFunctions.LossFunction


def _data(seed=0, n=64):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype("float32")
    y = (X.sum(1) > 0).astype(int)
    return DataSetIterator(X, np.eye(2, dtype="float32")[y], 32)


def _builder(candidate):
    conf = (NeuralNetConfiguration.Builder()
            .seed(7).updater(Adam(candidate["lr"]))
            .list()
            .layer(DenseLayer(nIn=6, nOut=candidate.get("hidden", 8),
                              activation=candidate.get("act", "tanh")))
            .layer(OutputLayer(nOut=2, activation="softmax", lossFunction=LF.MCXENT))
            .build())
    return MultiLayerNetwork(conf).init()


class TestSpaces:
    def test_continuous(self):
        rng = np.random.RandomState(0)
        s = ContinuousParameterSpace(0.1, 0.5)
        vals = [s.sample(rng) for _ in range(100)]
        assert all(0.1 <= v <= 0.5 for v in vals)
        assert s.grid(3) == [0.1, pytest.approx(0.3), 0.5]

    def test_continuous_log(self):
        rng = np.random.RandomState(0)
        s = ContinuousParameterSpace(1e-4, 1e-1, log=True)
        vals = [s.sample(rng) for _ in range(200)]
        assert all(1e-4 <= v <= 1e-1 for v in vals)
        # log-uniform: ~half the mass below the geometric midpoint
        mid = 10 ** (-2.5)
        frac = sum(v < mid for v in vals) / len(vals)
        assert 0.35 < frac < 0.65
        g = s.grid(4)
        assert g[0] == pytest.approx(1e-4) and g[-1] == pytest.approx(1e-1)

    def test_discrete_and_integer(self):
        rng = np.random.RandomState(0)
        d = DiscreteParameterSpace("relu", "tanh")
        assert set(d.sample(rng) for _ in range(50)) == {"relu", "tanh"}
        i = IntegerParameterSpace(4, 16)
        vals = [i.sample(rng) for _ in range(100)]
        assert min(vals) >= 4 and max(vals) <= 16
        assert i.grid(3) == [4, 10, 16]
        assert i.grid(100) == list(range(4, 17))


class TestGenerators:
    def test_grid_enumerates_product(self):
        gen = GridSearchCandidateGenerator(
            {"lr": ContinuousParameterSpace(1e-3, 1e-1),
             "act": DiscreteParameterSpace("relu", "tanh")},
            discretizationCount=3)
        seen = []
        while gen.hasMore():
            seen.append(gen.next())
        assert len(seen) == 6
        assert len({(c["lr"], c["act"]) for c in seen}) == 6

    def test_random_reproducible(self):
        spaces = {"lr": ContinuousParameterSpace(1e-3, 1e-1)}
        g1 = RandomSearchGenerator(spaces, seed=9)
        g2 = RandomSearchGenerator(spaces, seed=9)
        assert [g1.next() for _ in range(5)] == [g2.next() for _ in range(5)]


class TestRunner:
    def test_random_search_finds_working_lr(self):
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(RandomSearchGenerator(
                    {"lr": ContinuousParameterSpace(1e-3, 1e-1, log=True)}, seed=1))
                .scoreFunction(TestSetLossScoreFunction(_data(seed=1)))
                .terminationConditions(MaxCandidatesCondition(5))
                .epochsPerCandidate(20)
                .build())
        result = LocalOptimizationRunner(conf, _builder, _data(seed=0)).execute()
        assert len(result.results) == 5
        assert result.bestScore() == min(r.score for r in result.results)
        assert result.bestScore() < 0.5
        assert result.bestModel() is not None

    def test_grid_search_accuracy_maximized(self):
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(GridSearchCandidateGenerator(
                    {"lr": DiscreteParameterSpace(1e-9, 3e-2),
                     "act": DiscreteParameterSpace("relu", "tanh")}))
                .scoreFunction(EvaluationScoreFunction(_data(seed=1), "accuracy"))
                .terminationConditions(MaxCandidatesCondition(100))
                .epochsPerCandidate(15)
                .build())
        result = LocalOptimizationRunner(conf, _builder, _data(seed=0)).execute()
        assert len(result.results) == 4
        assert result.bestScore() == max(r.score for r in result.results)
        # the real lr must beat the degenerate one
        assert result.bestCandidate()["lr"] == pytest.approx(3e-2)

    def test_failed_candidate_does_not_kill_search(self):
        def builder(candidate):
            if candidate["hidden"] == 0:
                raise ValueError("bad config")
            return _builder({"lr": 1e-2, "hidden": candidate["hidden"]})

        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(GridSearchCandidateGenerator(
                    {"hidden": DiscreteParameterSpace(0, 8)}))
                .scoreFunction(TestSetLossScoreFunction(_data(seed=1)))
                .terminationConditions(MaxCandidatesCondition(10))
                .epochsPerCandidate(3)
                .build())
        result = LocalOptimizationRunner(conf, builder, _data(seed=0)).execute()
        assert len(result.results) == 2
        assert result.results[0].error is not None
        assert result.bestCandidate() == {"hidden": 8}

    def test_max_time_condition(self):
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(RandomSearchGenerator(
                    {"lr": ContinuousParameterSpace(1e-3, 1e-1)}))
                .scoreFunction(TestSetLossScoreFunction(_data(seed=1)))
                .terminationConditions(MaxCandidatesCondition(3), MaxTimeCondition(0.0))
                .build())
        with pytest.raises(RuntimeError):
            LocalOptimizationRunner(conf, _builder, _data(seed=0)).execute()


class TestFromUnit:
    def test_continuous_endpoints_and_clamp(self):
        s = ContinuousParameterSpace(0.1, 0.5)
        assert s.from_unit(0.0) == pytest.approx(0.1)
        assert s.from_unit(1.0) == pytest.approx(0.5)
        assert s.from_unit(-0.3) == pytest.approx(0.1)   # clamped
        assert s.from_unit(1.7) == pytest.approx(0.5)

    def test_continuous_log(self):
        s = ContinuousParameterSpace(1e-4, 1e-1, log=True)
        assert s.from_unit(0.0) == pytest.approx(1e-4)
        assert s.from_unit(1.0) == pytest.approx(1e-1)
        # midpoint on the LOG scale is the geometric mean
        assert s.from_unit(0.5) == pytest.approx(np.sqrt(1e-4 * 1e-1))

    def test_discrete(self):
        s = DiscreteParameterSpace("a", "b", "c")
        assert s.from_unit(0.0) == "a"
        assert s.from_unit(0.5) == "b"
        assert s.from_unit(1.0) == "c"      # not one past the end
        assert s.from_unit(-2.0) == "a"     # clamped, NOT values[-1]

    def test_integer(self):
        s = IntegerParameterSpace(2, 5)
        assert s.from_unit(0.0) == 2
        assert s.from_unit(1.0) == 5
        assert s.from_unit(-0.4) == 2       # clamped, stays in range
        assert all(s.from_unit(u) in (2, 3, 4, 5)
                   for u in np.linspace(0, 1, 50))


class _FakeModel:
    """Carries the candidate through the runner's fit/score protocol
    so generator tests don't pay a network compile per candidate."""

    def __init__(self, candidate):
        self.candidate = candidate

    def fit(self, data, epochs=1):
        pass


class _SphereScore:
    """score = sum_i (x_i - target_i)^2, minimized at the target."""

    def __init__(self, targets):
        self.targets = targets

    def minimize(self):
        return True

    def score(self, model):
        return float(sum((model.candidate[k] - t) ** 2
                         for k, t in self.targets.items()))


class TestGeneticSearch:
    SPACES = {
        "a": ContinuousParameterSpace(0.0, 1.0),
        "b": ContinuousParameterSpace(0.0, 1.0),
        "c": ContinuousParameterSpace(0.0, 1.0),
        "d": ContinuousParameterSpace(0.0, 1.0),
    }
    TARGETS = {"a": 0.31, "b": 0.77, "c": 0.12, "d": 0.58}

    def _run(self, gen, budget=120):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator  # noqa: F401
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(gen)
                .scoreFunction(_SphereScore(self.TARGETS))
                .terminationConditions(MaxCandidatesCondition(budget))
                .build())
        return LocalOptimizationRunner(conf, _FakeModel, None).execute()

    def test_beats_random_on_sphere(self):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator
        gen = GeneticSearchCandidateGenerator(self.SPACES, populationSize=15,
                                              seed=11)
        rnd = RandomSearchGenerator(self.SPACES, seed=11)
        g_best = self._run(gen).bestScore()
        r_best = self._run(rnd).bestScore()
        assert g_best < r_best, (g_best, r_best)
        assert g_best < 0.01, g_best  # actually converges to the target

    def test_generations_advance_and_improve(self):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator
        gen = GeneticSearchCandidateGenerator(self.SPACES, populationSize=10,
                                              seed=3)
        res = self._run(gen, budget=80)
        assert gen.generation >= 7
        # mean score of the last generation beats generation 0's mean:
        # selection pressure is actually doing something
        scores = [r.score for r in res.results]
        assert np.mean(scores[-10:]) < np.mean(scores[:10])

    def test_breeding_without_feedback_raises(self):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator
        gen = GeneticSearchCandidateGenerator(self.SPACES, populationSize=2,
                                              seed=0)
        gen.next()
        gen.next()  # generation 0 exhausted, no reportResult calls
        with pytest.raises(RuntimeError, match="reportResult"):
            gen.next()

    def test_failed_candidates_get_worst_fitness(self):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator
        gen = GeneticSearchCandidateGenerator(self.SPACES, populationSize=4,
                                              seed=0)
        c = gen.next()
        gen.reportResult(c, float("inf"), True)  # runner's failure score
        assert gen._scored[-1][1] == float("-inf")

    def test_mixed_space_types_decode(self):
        from deeplearning4j_tpu.arbiter import GeneticSearchCandidateGenerator
        spaces = {"lr": ContinuousParameterSpace(1e-4, 1e-1, log=True),
                  "act": DiscreteParameterSpace("relu", "tanh"),
                  "hidden": IntegerParameterSpace(4, 16)}
        gen = GeneticSearchCandidateGenerator(spaces, populationSize=4, seed=1)
        for _ in range(12):
            c = gen.next()
            gen.reportResult(c, 1.0, True)
            assert 1e-4 <= c["lr"] <= 1e-1
            assert c["act"] in ("relu", "tanh")
            assert 4 <= c["hidden"] <= 16


class TestMultiLayerSpace:
    """The arbiter config-space DSL (reference: arbiter-deeplearning4j
    MultiLayerSpace + DenseLayerSpace/OutputLayerSpace): flattens to the
    named-ParameterSpace dict every generator consumes, and provides the
    modelBuilder for LocalOptimizationRunner."""

    def _space(self):
        from deeplearning4j_tpu.arbiter import (
            MultiLayerSpace, DenseLayerSpace, OutputLayerSpace)
        return (MultiLayerSpace.Builder()
                .seed(7)
                .learningRate(ContinuousParameterSpace(1e-3, 1e-1, log=True))
                .addLayer(DenseLayerSpace(
                    nIn=6, nOut=IntegerParameterSpace(4, 16),
                    activation=DiscreteParameterSpace("relu", "tanh")))
                .addLayer(OutputLayerSpace(nOut=2, activation="softmax"))
                .build())

    def test_parameter_space_keys(self):
        spaces = self._space().parameterSpaces()
        assert set(spaces) == {"learningRate", "0_nOut", "0_activation"}

    def test_model_builder_materializes_candidate(self):
        space = self._space()
        net = space.modelBuilder(
            {"learningRate": 0.01, "0_nOut": 9, "0_activation": "tanh"})
        assert np.asarray(net.getParam("0_W")).shape == (6, 9)
        assert np.asarray(net.getParam("1_W")).shape == (9, 2)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_random_search_over_space_finds_good_model(self):
        space = self._space()
        gen = RandomSearchGenerator(space.parameterSpaces(), seed=4)
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(gen)
                .scoreFunction(EvaluationScoreFunction(_data(seed=1)))
                .terminationConditions(MaxCandidatesCondition(4))
                .epochsPerCandidate(8).build())
        res = LocalOptimizationRunner(conf, space.modelBuilder,
                                      _data(seed=0)).execute()
        assert res.bestScore() > 0.8
        assert set(res.bestCandidate()) == {"learningRate", "0_nOut",
                                            "0_activation"}

    def test_all_fixed_raises(self):
        from deeplearning4j_tpu.arbiter import (
            MultiLayerSpace, DenseLayerSpace, OutputLayerSpace)
        space = (MultiLayerSpace.Builder()
                 .addLayer(DenseLayerSpace(nIn=4, nOut=8))
                 .addLayer(OutputLayerSpace(nOut=2, activation="softmax"))
                 .build())
        with pytest.raises(ValueError, match="nothing to search"):
            space.parameterSpaces()

    def test_add_layer_type_check(self):
        from deeplearning4j_tpu.arbiter import MultiLayerSpace
        with pytest.raises(TypeError, match="LayerSpace"):
            MultiLayerSpace.Builder().addLayer(object())


class TestComputationGraphSpace:
    def _space(self):
        from deeplearning4j_tpu.arbiter import (
            ComputationGraphSpace, DenseLayerSpace, OutputLayerSpace)
        return (ComputationGraphSpace.Builder()
                .seed(7)
                .learningRate(ContinuousParameterSpace(1e-3, 1e-1, log=True))
                .addInputs("in")
                .addLayer("dense", DenseLayerSpace(
                    nIn=6, nOut=IntegerParameterSpace(4, 16),
                    activation="tanh"), "in")
                .addLayer("out", OutputLayerSpace(nOut=2,
                                                  activation="softmax"),
                          "dense")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(6))
                .build())

    def test_keys_are_vertex_named(self):
        assert set(self._space().parameterSpaces()) == {"learningRate",
                                                        "dense_nOut"}

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_search_over_graph_space(self):
        space = self._space()
        gen = RandomSearchGenerator(space.parameterSpaces(), seed=3)
        conf = (OptimizationConfiguration.Builder()
                .candidateGenerator(gen)
                .scoreFunction(EvaluationScoreFunction(_data(seed=1)))
                .terminationConditions(MaxCandidatesCondition(3))
                .epochsPerCandidate(8).build())
        res = LocalOptimizationRunner(conf, space.modelBuilder,
                                      _data(seed=0)).execute()
        assert res.bestScore() > 0.8
        from deeplearning4j_tpu.nn import ComputationGraph
        assert isinstance(res.bestModel(), ComputationGraph)
