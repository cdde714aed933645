"""Plain float32 references: each architecture's forward pass in
straightforward ``jax.numpy`` with no kernels, cache or batching, for
the tests that hold the served programs to the published equations."""
