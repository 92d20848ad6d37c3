"""Plain references: straightforward jax.numpy forward passes written from
the published descriptions, importing nothing of the program."""
