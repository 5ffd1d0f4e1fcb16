"""The benchmark's harness: cells found by name, traffic, weights, the
wall-clock driver around the program's serving loop, trace reduction,
costs and the correctness check."""
