"""DQN (reference: rl4j QLearningDiscreteDense) on a deterministic
chain MDP: the greedy policy must learn to walk right for the terminal
reward instead of taking the small immediate left reward."""

import numpy as np
import pytest

from deeplearning4j_tpu.rl import (MDP, QLearningConfiguration,
                                   QLearningDiscreteDense)


class ChainMDP(MDP):
    """States 0..n-1, one-hot observations. Action 1 moves right
    (terminal reward 10.0 at the end), action 0 moves left (reward 0.2
    at state 0, episode continues). Discounted optimum: go right."""

    def __init__(self, n=5):
        self.n = n
        self.s = 0

    def obsSize(self):
        return self.n

    def numActions(self):
        return 2

    def _obs(self):
        o = np.zeros(self.n, "float32")
        o[self.s] = 1.0
        return o

    def reset(self):
        self.s = 0
        return self._obs()

    def step(self, action):
        if action == 1:
            self.s += 1
            if self.s >= self.n - 1:
                return self._obs(), 10.0, True
            return self._obs(), 0.0, False
        self.s = max(0, self.s - 1)
        return self._obs(), (0.2 if self.s == 0 else 0.0), False


def _qnet(n_in, n_out):
    from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                       MultiLayerNetwork, DenseLayer,
                                       OutputLayer, Adam)

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(5e-3))
            .list()
            .layer(DenseLayer(nOut=24, activation="tanh"))
            .layer(OutputLayer(nOut=n_out, activation="identity",
                               lossFunction="mse"))
            .setInputType(InputType.feedForward(n_in)).build())
    return MultiLayerNetwork(conf).init()


class TestDQN:
    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_learns_chain_policy(self):
        mdp = ChainMDP(5)
        conf = QLearningConfiguration(
            seed=7, gamma=0.9, batchSize=32, expRepMaxSize=2000,
            targetDqnUpdateFreq=100, updateStart=64, minEpsilon=0.05,
            epsilonNbStep=1200, maxEpochStep=30, doubleDQN=True)
        dqn = QLearningDiscreteDense(mdp, _qnet(5, 2), conf)
        dqn.train(maxSteps=2500)
        policy = dqn.getPolicy()
        # greedy policy walks right from every state
        for s in range(4):
            mdp.s = s
            assert policy.nextAction(mdp._obs()) == 1, f"state {s}"
        assert policy.play(ChainMDP(5), maxSteps=20) == 10.0

    def test_epsilon_anneals(self):
        dqn = QLearningDiscreteDense(
            ChainMDP(4), _qnet(4, 2),
            QLearningConfiguration(minEpsilon=0.1, epsilonNbStep=100))
        assert dqn._epsilon() == 1.0
        dqn._step = 50
        assert abs(dqn._epsilon() - 0.55) < 1e-6
        dqn._step = 1000
        assert abs(dqn._epsilon() - 0.1) < 1e-6

    def test_requires_initialized_net(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer)

        conf = (NeuralNetConfiguration.Builder().list()
                .layer(DenseLayer(nOut=4))
                .layer(OutputLayer(nOut=2, activation="identity",
                                   lossFunction="mse"))
                .setInputType(InputType.feedForward(3)).build())
        with pytest.raises(RuntimeError, match="init"):
            QLearningDiscreteDense(ChainMDP(3), MultiLayerNetwork(conf),
                                   QLearningConfiguration())
