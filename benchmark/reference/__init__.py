"""Plain float32 references: the published equations in jax.numpy under
``jax.default_matmul_precision("highest")``, no kernels, no cache, nothing
imported from paddle_tpu."""
