"""The benchmark: harness, yardstick and data (see PERF.md)."""
