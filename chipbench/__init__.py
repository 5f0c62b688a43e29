"""chipbench: the repository's benchmark, driven by data (see README.md).

Importing this package touches no jax device and starts nothing."""
