"""The benchmark of lazzaro-tpu: harness, yardstick and data (see PERF.md)."""
