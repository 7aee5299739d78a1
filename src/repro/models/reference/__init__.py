"""Plain references of the backbones the encoder runs: straightforward
``jax.numpy`` in float32, no kernels, no packing, no batching."""
