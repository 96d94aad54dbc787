"""cxxnet_tpu — a TPU-native, config-driven CNN training framework.

A ground-up JAX/XLA re-architecture with the capabilities of the reference
cxxnet (see SURVEY.md): the ``.conf`` network language, train/pred/extract/
finetune tasks, the full layer zoo, SGD/NAG/Adam updaters with schedules and
tag-scoped hyperparameters, a chained-iterator data pipeline, checkpointing,
and data-parallel scaling over a ``jax.sharding.Mesh``.
"""

__version__ = '0.1.0'

