"""Pallas TPU kernels for the sparse hot spots; ``ops`` holds the
dispatchers and ``ref`` the plain-jnp oracles."""
