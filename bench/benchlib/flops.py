"""Operations and bytes the algorithms need, from their shapes.

Policy FLOPs count the matrix products of the Table-6 actor-critic MLP
(``in:h1:...:hk:act`` trunk + action head + value head off the last hidden
layer) at 2 FLOPs per multiply-add.  A backward pass computes every
layer's weight gradient and every layer's input gradient except the first
layer's (observations take no gradient).  Nothing recomputed is counted.
Kernel bytes are the least an algorithm must move: each input read once,
each output written once, float32.
"""
from __future__ import annotations


def layers(dims):
    """(fan_in, fan_out) of every matrix product of the policy."""
    dims = list(dims)
    trunk = list(zip(dims[:-2], dims[1:-1]))
    return trunk + [(dims[-2], dims[-1]), (dims[-2], 1)]


def forward(dims) -> int:
    """FLOPs of one sample's forward pass."""
    return sum(2 * i * o for i, o in layers(dims))


def value_forward(dims) -> int:
    """FLOPs of a forward pass that needs only the value (a bootstrap):
    the trunk and the value head."""
    return forward(dims) - 2 * dims[-2] * dims[-1]


def backward(dims) -> int:
    """FLOPs of one sample's backward pass: weight gradients of every
    product, input gradients of all but the first layer."""
    return 2 * forward(dims) - 2 * dims[0] * dims[1]


def sync_ppo_per_sample(dims, num_steps: int, num_epochs: int) -> float:
    """Acting forward per step, the bootstrap value once per rollout
    (1/num_steps per sample), then ``num_epochs`` forward+backward passes
    over every sample."""
    f = forward(dims)
    return f + value_forward(dims) / num_steps \
        + num_epochs * (f + backward(dims))


def a3c_per_sample(dims, num_steps: int) -> float:
    """Acting forward, the bootstrap value once per rollout, and one
    training forward+backward per sample."""
    f = forward(dims)
    return 2 * f + value_forward(dims) / num_steps + backward(dims)


def nstep_bytes(T: int, N: int) -> int:
    """n-step returns: read rewards, dones (T, N) and the bootstrap (N,);
    write the returns (T, N)."""
    return 4 * (2 * T * N + N) + 4 * T * N
