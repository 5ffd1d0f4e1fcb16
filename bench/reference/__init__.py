"""Plain float32 reference of the benchmark's model configurations."""
