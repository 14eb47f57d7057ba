"""GymEnv adapter (reference: rl4j-gym GymEnv): any gym-API object
trains through the MDP-protocol algorithms. The stub envs below speak
both gym API generations locally — no gym package in this image, which
is exactly the adapter's point."""

import numpy as np
import pytest

from deeplearning4j_tpu.rl import (GymEnv, QLearningConfiguration,
                                   QLearningDiscreteDense)


class _Space:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class GymChain:
    """The chain task from test_rl.py, spoken in the gymnasium API:
    reset(seed=...) -> (obs, info); step -> 5-tuple with
    terminated/truncated split. Walk right for the terminal +10."""

    def __init__(self, n=5):
        self.n = n
        self.s = 0
        self.action_space = _Space(n=2)
        self.observation_space = _Space(shape=(n,))
        self.seeded_with = None
        self.closed = False

    def _obs(self):
        o = np.zeros(self.n, "float32")
        o[self.s] = 1.0
        return o

    def reset(self, seed=None):
        if seed is not None:
            self.seeded_with = seed
        self.s = 0
        return self._obs(), {}

    def step(self, action):
        if action == 1:
            self.s += 1
            if self.s >= self.n - 1:
                return self._obs(), 10.0, True, False, {}
            return self._obs(), 0.0, False, False, {}
        self.s = max(0, self.s - 1)
        return self._obs(), (0.2 if self.s == 0 else 0.0), False, False, {}

    def close(self):
        self.closed = True


class ClassicGymChain(GymChain):
    """Same task in the CLASSIC gym API: reset() -> obs, step ->
    4-tuple (obs, reward, done, info)."""

    def reset(self):
        self.s = 0
        return self._obs()

    def step(self, action):
        obs, r, terminated, truncated, info = super().step(action)
        return obs, r, terminated or truncated, info


def _qnet(n_in, n_out):
    from deeplearning4j_tpu.nn import (Adam, DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(5e-3))
            .list()
            .layer(DenseLayer(nOut=24, activation="tanh"))
            .layer(OutputLayer(nOut=n_out, activation="identity",
                               lossFunction="mse"))
            .setInputType(InputType.feedForward(n_in)).build())
    return MultiLayerNetwork(conf).init()


class TestGymEnvAdapter:
    def test_protocol_mapping_gymnasium(self):
        env = GymEnv(GymChain(), seed=42)
        assert env.obsSize() == 5 and env.numActions() == 2
        obs = env.reset()
        assert obs.shape == (5,) and obs[0] == 1.0
        assert env._env.seeded_with == 42  # seed forwarded on first reset
        obs, r, done = env.step(1)
        assert (r, done) == (0.0, False) and obs[1] == 1.0
        for _ in range(3):
            obs, r, done = env.step(1)
        assert (r, done) == (10.0, True)
        env.close()
        assert env._env.closed

    def test_protocol_mapping_classic(self):
        env = GymEnv(ClassicGymChain())
        obs = env.reset()
        assert obs.shape == (5,)
        obs, r, done = env.step(0)
        assert r == pytest.approx(0.2) and not done

    def test_kwargs_reset_wrapper_gets_seed(self):
        """gym>=0.26 wrappers declare reset(self, **kwargs) and forward
        seed= inward — signature detection must treat that as
        seed-accepting (env.seed() no longer exists there)."""
        class Wrapper(GymChain):
            def reset(self, **kwargs):
                return super().reset(**kwargs)
        env = GymEnv(Wrapper(), seed=99)
        env.reset()
        assert env._env.seeded_with == 99

    def test_classic_env_seeds_via_seed_method(self):
        class SeedableClassic(ClassicGymChain):
            def seed(self, s):
                self.seeded_with = s
        env = GymEnv(SeedableClassic(), seed=11)
        env.reset()
        assert env._env.seeded_with == 11  # reset(seed=) fallback path
        env.reset()  # seeds once only
        assert env._env.seeded_with == 11

    def test_truncation_counts_as_done(self):
        class Truncating(GymChain):
            def step(self, action):
                return self._obs(), 0.5, False, True, {}
        _, r, done = GymEnv(Truncating()).step(0)
        assert done and r == 0.5

    def test_flatten_and_shape_passthrough(self):
        class Img(GymChain):
            def __init__(self):
                super().__init__()
                self.observation_space = _Space(shape=(4, 4, 2))
            def reset(self, seed=None):
                return np.ones((4, 4, 2)), {}
        assert GymEnv(Img()).reset().shape == (32,)
        e = GymEnv(Img(), flatten=False)
        assert e.reset().shape == (4, 4, 2)
        assert e.obsShape() == (4, 4, 2)

    def test_rejects_non_discrete_and_shapeless(self):
        class Box(GymChain):
            def __init__(self):
                super().__init__()
                self.action_space = _Space(low=-1.0, high=1.0)
        with pytest.raises(ValueError, match="discrete"):
            GymEnv(Box())
        class NoShape(GymChain):
            def __init__(self):
                super().__init__()
                self.observation_space = _Space()
        with pytest.raises(ValueError, match="observation_space"):
            GymEnv(NoShape())

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_dqn_trains_through_adapter(self):
        """The done-bar: DQN learns the chain THROUGH the
        adapter, same bar as test_rl.py's native-MDP run."""
        env = GymEnv(GymChain(), seed=7)
        net = _qnet(env.obsSize(), env.numActions())
        # same hyperparameters as test_rl.py's native-MDP run
        conf = QLearningConfiguration(
            seed=7, gamma=0.9, batchSize=32, expRepMaxSize=2000,
            targetDqnUpdateFreq=100, updateStart=64, minEpsilon=0.05,
            epsilonNbStep=1200, maxEpochStep=30, doubleDQN=True)
        dqn = QLearningDiscreteDense(env, net, conf)
        dqn.train(maxSteps=2500)
        policy = dqn.getPolicy()
        assert policy.play(env, maxSteps=20) == pytest.approx(10.0)


class TestSeedProbeSemantics:
    def test_env_internal_typeerror_propagates(self):
        """A TypeError raised by a bug INSIDE a seed-accepting reset
        must propagate, not silently re-run reset unseeded."""
        class Buggy(GymChain):
            def reset(self, seed=None):
                raise TypeError("cannot unpack non-iterable NoneType")
        with pytest.raises(TypeError, match="unpack"):
            GymEnv(Buggy(), seed=1).reset()
