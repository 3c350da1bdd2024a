"""Plain float32 references: no kernels, no cache, no batching tricks."""
