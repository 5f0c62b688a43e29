"""DQN on a chain MDP — replay buffer, target network, epsilon-greedy
(reference: example/reinforcement-learning/dqn — the same agent loop:
online Q-network trained on TD targets from a periodically-synced
target network over replayed transitions).

Environment (self-contained, no gym in this image): an N-state chain.
Action 1 moves right, action 0 teleports back to the start with a small
immediate reward; only reaching the far end pays 10.  Greedy play on
the optimal policy walks the whole chain, which epsilon-greedy
exploration must discover past the distractor reward.

Framework surface exercised: two Modules sharing an architecture,
``get_params -> set_params`` for the target sync, gather via ``pick``
for Q(s, a), and a custom TD-loss training loop.

Run:  python examples/reinforcement_learning/dqn_chain.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402


class ChainEnv:
    """N states in a row; right-moves reach the +10 goal, action 0
    pays +0.1 but resets (3.2/episode max) — the exploration trap."""

    def __init__(self, n=8):
        self.n = n
        self.state = 0

    def reset(self):
        self.state = 0
        return self.state

    def step(self, action):
        if action == 1:
            self.state += 1
            if self.state >= self.n - 1:
                return self.state, 10.0, True
            return self.state, 0.0, False
        self.state = 0
        return self.state, 0.1, False


def q_net(n_actions=2):
    data = mx.sym.Variable('data')
    h = mx.sym.FullyConnected(data, num_hidden=32, name='q1')
    h = mx.sym.Activation(h, act_type='relu')
    return mx.sym.FullyConnected(h, num_hidden=n_actions, name='q2')


def make_module(n_states, batch):
    mod = mx.mod.Module(q_net(), context=mx.cpu(), label_names=None)
    mod.bind(data_shapes=[('data', (batch, n_states))],
             label_shapes=None, for_training=True,
             inputs_need_grad=False)
    mod.init_params(mx.initializer.Xavier())
    return mod


def one_hot(idx, n):
    out = np.zeros((len(idx), n), np.float32)
    out[np.arange(len(idx)), idx] = 1.0
    return out


def run(episodes=250, n_states=8, batch=32, gamma=0.95, lr=5e-3,
        sync_every=20, seed=0, log=print):
    rng = np.random.RandomState(seed)
    mx.random.seed(seed)
    env = ChainEnv(n_states)

    online = make_module(n_states, batch)
    online.init_optimizer(optimizer='adam',
                          optimizer_params={'learning_rate': lr})
    target = make_module(n_states, batch)
    target.set_params(*online.get_params())
    # batch-1 policy head so greedy actions never force the batch-32
    # training executor to rebind; synced from the online params each
    # episode (jax-array handle swaps, no compute — the per-forward
    # copy BucketingModule does, at episode granularity)
    policy = mx.mod.Module(q_net(), context=mx.cpu(), label_names=None)
    policy.bind(data_shapes=[('data', (1, n_states))], label_shapes=None,
                for_training=False, shared_module=online)

    replay = []
    eps = 1.0
    returns = []
    for ep in range(episodes):
        policy._exec.copy_params_from(*online.get_params(),
                                      allow_extra_params=True)
        s = env.reset()
        total = 0.0
        for _ in range(4 * n_states):
            if rng.uniform() < eps:
                a = rng.randint(2)
            else:
                policy.forward(mx.io.DataBatch(
                    data=[nd.array(one_hot([s], n_states))]),
                    is_train=False)
                a = int(policy.get_outputs()[0].asnumpy()[0].argmax())
            s2, r, done = env.step(a)
            replay.append((s, a, r, s2, done))
            total += r
            s = s2
            if done:
                break
        returns.append(total)
        eps = max(0.05, eps * 0.97)
        replay = replay[-2000:]

        if len(replay) >= batch:
            idx = rng.choice(len(replay), batch)
            ss, aa, rr, s2s, dd = zip(*[replay[i] for i in idx])
            # TD target from the frozen network
            target.forward(mx.io.DataBatch(
                data=[nd.array(one_hot(s2s, n_states))]), is_train=False)
            q_next = target.get_outputs()[0].asnumpy().max(axis=1)
            y = np.array(rr, np.float32) + gamma * q_next * \
                (1.0 - np.array(dd, np.float32))
            # gradient of the TD error only through Q(s, a)
            online.forward(mx.io.DataBatch(
                data=[nd.array(one_hot(ss, n_states))]), is_train=True)
            q = online.get_outputs()[0]
            q_sa = nd.pick(q, nd.array(np.array(aa, np.float32)), axis=1)
            grad_q = np.zeros((batch, 2), np.float32)
            td = q_sa.asnumpy() - y
            grad_q[np.arange(batch), list(aa)] = td / batch
            online.backward(out_grads=[nd.array(grad_q)])
            online.update()

        if (ep + 1) % sync_every == 0:
            target.set_params(*online.get_params())

    tail = float(np.mean(returns[-20:]))
    log("mean return (last 20 episodes): %.3f" % tail)
    return tail


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--episodes', type=int, default=250)
    a = ap.parse_args()
    tail = run(episodes=a.episodes)
    print("final dqn mean return %.3f" % tail)


if __name__ == '__main__':
    main()
