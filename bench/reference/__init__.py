"""Plain references of the benchmark's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``, following the published description (the paper's
Table-6 MLP policies, PPO and A3C as the cited papers define them, Adam)
and the chain-physics env family the configurations name.  Nothing here
imports the system under test or takes an array it made: the references
build their own weights and envs from the seed.
"""
