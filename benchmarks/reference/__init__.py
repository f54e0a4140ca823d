"""Plain references: float32, ``jax.numpy`` and ``lax`` only, written from the
published descriptions. Nothing here imports the program under test."""
