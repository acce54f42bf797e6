"""Part of the benchmark; see benchmark/README.md."""
