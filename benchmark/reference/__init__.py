"""Plain references: straightforward ``jax.numpy`` in float32, no
kernels, no cache, no batching tricks. They import nothing of the
program and take nothing the program made; weights come from
``benchmark/weights.py`` and the seed."""
