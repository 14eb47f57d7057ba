"""Conv-DQN + A3C (reference: rl4j QLearningDiscreteConv with
HistoryProcessor, A3CDiscreteDense). Conv-DQN must solve a pixel-grid
task from raw frames; A3C must solve the same delayed-reward chain DQN
does, with decreasing actor/critic losses.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.rl import (
    MDP, QLearningConfiguration, QLearningDiscreteConv,
    HistoryProcessorConfiguration, A3CConfiguration, A3CDiscreteDense,
)
from tests.test_rl import ChainMDP


class PixelTrackMDP(MDP):
    """Agent on a 1-D track of length `n`, OBSERVED AS PIXELS: a [n, n]
    image whose column `pos` is lit on every row. Action 1 moves right
    (terminal reward 10 at the right edge); action 0 moves left (small
    reward 0.2 at the left edge). Optimal: walk right — same delayed-
    reward structure as ChainMDP but learnable only through convs."""

    def __init__(self, n=5):
        self.n = n
        self.pos = 0

    def obsSize(self):
        return self.n * self.n

    def numActions(self):
        return 2

    def _obs(self):
        img = np.zeros((self.n, self.n), "float32")
        img[:, self.pos] = 1.0
        return img

    def reset(self):
        self.pos = 0
        return self._obs()

    def step(self, action):
        if action == 1:
            self.pos += 1
            if self.pos >= self.n - 1:
                return self._obs(), 10.0, True
            return self._obs(), 0.0, False
        self.pos = max(0, self.pos - 1)
        return self._obs(), (0.2 if self.pos == 0 else 0.0), False


def _conv_qnet(n, hist, n_out):
    from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                       MultiLayerNetwork, ConvolutionLayer,
                                       DenseLayer, OutputLayer, Adam)

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(5e-3))
            .list()
            .layer(ConvolutionLayer(nOut=8, kernelSize=(3, 3),
                                    convolutionMode="same",
                                    activation="relu"))
            .layer(DenseLayer(nOut=32, activation="tanh"))
            .layer(OutputLayer(nOut=n_out, activation="identity",
                               lossFunction="mse"))
            .setInputType(InputType.convolutional(n, n, hist)).build())
    return MultiLayerNetwork(conf).init()


class TestConvDQN:
    @pytest.mark.slow  # tier-1 budget (PR 21): 11 s on 8 CPU cores
    def test_learns_pixel_track_policy(self):
        n, hist = 5, 2
        mdp = PixelTrackMDP(n)
        conf = QLearningConfiguration(
            seed=3, gamma=0.9, batchSize=32, expRepMaxSize=2000,
            targetDqnUpdateFreq=100, updateStart=64, minEpsilon=0.05,
            epsilonNbStep=1000, maxEpochStep=30, doubleDQN=True)
        dqn = QLearningDiscreteConv(
            mdp, _conv_qnet(n, hist, 2),
            HistoryProcessorConfiguration(historyLength=hist), conf)
        dqn.train(maxSteps=2200)
        assert dqn.getPolicy().play(PixelTrackMDP(n), maxSteps=20) == 10.0

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_frame_stack_semantics(self):
        mdp = PixelTrackMDP(4)
        dqn = QLearningDiscreteConv(
            mdp, _conv_qnet(4, 3, 2),
            HistoryProcessorConfiguration(historyLength=3),
            QLearningConfiguration())
        o0 = dqn._reset_env()
        assert o0.shape == (3, 4, 4)
        # episode start repeat-pads: all three frames identical
        np.testing.assert_array_equal(o0[0], o0[2])
        o1, _, _ = dqn._step_env(1)
        # ring shifted: newest frame shows pos=1, oldest still pos=0
        assert o1[2][0, 1] == 1.0 and o1[0][0, 0] == 1.0

    def test_bad_history_length_rejected(self):
        with pytest.raises(ValueError, match="historyLength"):
            HistoryProcessorConfiguration(historyLength=0)


class TestA3C:
    def _train(self, steps=12_000):
        conf = A3CConfiguration(seed=5, gamma=0.9, nStep=10, numThreads=8,
                                learningRate=3e-3, entropyCoef=0.01,
                                valueCoef=0.5, maxEpochStep=30)
        return A3CDiscreteDense(lambda: ChainMDP(5), conf,
                                hiddenSize=32).train(maxSteps=steps)

    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_solves_chain_and_losses_decrease(self):
        a3c = self._train()
        assert a3c.getPolicy().play(ChainMDP(5), maxSteps=20) == 10.0
        # critic converges: late value loss well under early value loss
        v = a3c._value_losses
        early, late = np.mean(v[:10]), np.mean(v[-10:])
        assert late < early * 0.5, (early, late)
        assert np.isfinite(a3c._policy_losses).all()

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_greedy_policy_walks_right_from_every_state(self):
        a3c = self._train()
        pol = a3c.getPolicy()
        mdp = ChainMDP(5)
        for s in range(4):
            mdp.s = s
            assert pol.nextAction(mdp._obs()) == 1, f"state {s}"

    def test_stochastic_policy_samples(self):
        a3c = self._train(steps=800)  # barely trained: still stochastic
        pol = a3c.getPolicy(greedy=False)
        acts = {pol.nextAction(ChainMDP(5).reset()) for _ in range(40)}
        assert acts <= {0, 1} and len(acts) >= 1


class TestAsyncNStepQLearning:
    """Reference: rl4j AsyncNStepQLearningDiscreteDense — the third
    async family, vectorized like A3C but with n-step Q targets and a
    periodically-synced target net."""

    def _train(self, steps=12_000):
        from deeplearning4j_tpu.rl import (AsyncNStepQLConfiguration,
                                           AsyncNStepQLearningDiscreteDense)
        conf = AsyncNStepQLConfiguration(seed=11, gamma=0.9, nStep=10,
                                         numThreads=8, learningRate=3e-3,
                                         targetDqnUpdateFreq=20,
                                         minEpsilon=0.05,
                                         epsilonNbStep=6000,
                                         maxEpochStep=30)
        return AsyncNStepQLearningDiscreteDense(
            lambda: ChainMDP(5), conf, hiddenSize=32).train(maxSteps=steps)

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_solves_chain(self):
        ql = self._train()
        assert ql.getPolicy().play(ChainMDP(5), maxSteps=20) == 10.0
        # TD loss settles: late loss below early loss
        l = ql._losses
        assert np.mean(l[-10:]) < np.mean(l[:10]), (l[:3], l[-3:])

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_greedy_policy_right_from_every_state(self):
        ql = self._train()
        pol = ql.getPolicy()
        mdp = ChainMDP(5)
        for s in range(4):
            mdp.s = s
            assert pol.nextAction(mdp._obs()) == 1, f"state {s}"

    def test_epsilon_anneals(self):
        from deeplearning4j_tpu.rl import (AsyncNStepQLConfiguration,
                                           AsyncNStepQLearningDiscreteDense)
        conf = AsyncNStepQLConfiguration(minEpsilon=0.1, epsilonNbStep=100)
        ql = AsyncNStepQLearningDiscreteDense(lambda: ChainMDP(5), conf)
        assert ql._epsilon() == 1.0
        ql._step = 50
        assert abs(ql._epsilon() - 0.55) < 1e-9
        ql._step = 1000
        assert abs(ql._epsilon() - 0.1) < 1e-9

    def test_target_net_syncs(self):
        ql = self._train(steps=2000)
        # after >= targetDqnUpdateFreq iterations the target equals a
        # recent params snapshot, not the init
        diff = float(np.abs(np.asarray(ql.targetParams["Wq"])
                            - np.asarray(ql.params["Wq"])).max())
        assert diff < 1.0  # moved with training (init target is random-far)
        assert ql._iteration >= ql.conf.targetDqnUpdateFreq


class TestPolicyPersistence:
    """Policy save/load (reference: rl4j DQNPolicy.save/load,
    ACPolicy.save/load)."""

    def test_dqn_policy_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.rl import DQNPolicy
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nOut=8, activation="relu"))
                .layer(OutputLayer(nOut=3, activation="identity",
                                   lossFunction="mse"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pol = DQNPolicy(net)
        p = str(tmp_path / "dqn.zip")
        pol.save(p)
        back = DQNPolicy.load(p)
        obs = np.random.RandomState(0).randn(4).astype("float32")
        assert back.nextAction(obs) == pol.nextAction(obs)

    def test_ac_policy_roundtrip_and_sampling(self, tmp_path):
        from deeplearning4j_tpu.rl import ACPolicy

        rs = np.random.RandomState(2)
        params = {"W1": rs.randn(5, 7).astype("float32"),
                  "b1": np.zeros(7, "float32"),
                  "Wp": rs.randn(7, 3).astype("float32"),
                  "bp": np.zeros(3, "float32"),
                  "Wv": rs.randn(7, 1).astype("float32"),
                  "bv": np.zeros(1, "float32")}
        pol = ACPolicy(params)
        p = str(tmp_path / "ac.bin")  # extension-less-ish path must work
        pol.save(p)
        back = ACPolicy.load(p)
        obs = rs.randn(5).astype("float32")
        assert back.nextAction(obs) == pol.nextAction(obs)
        # stochastic form samples from the actor distribution
        stoch = ACPolicy(params, greedy=False, seed=5)
        acts = {stoch.nextAction(obs) for _ in range(50)}
        assert len(acts) >= 2  # not degenerate argmax

    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_trained_policy_survives_roundtrip(self, tmp_path):
        # the policy from a trained DQN must keep solving the MDP
        from deeplearning4j_tpu.rl import (DQNPolicy,
                                           QLearningConfiguration,
                                           QLearningDiscreteDense)
        from test_rl import ChainMDP, _qnet

        mdp = ChainMDP(4)
        trainer = QLearningDiscreteDense(
            mdp, _qnet(4, 2),
            QLearningConfiguration(seed=7, maxEpochStep=20,
                                   expRepMaxSize=2000, batchSize=32,
                                   targetDqnUpdateFreq=50,
                                   epsilonNbStep=800, gamma=0.9))
        trainer.train(maxSteps=2500)
        pol = trainer.getPolicy()
        score = pol.play(mdp, maxSteps=30)
        p = str(tmp_path / "solved.zip")
        pol.save(p)
        back = DQNPolicy.load(p)
        assert back.play(mdp, maxSteps=30) == score
