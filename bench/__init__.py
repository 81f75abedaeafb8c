"""The benchmark of the graph middleware's device path (see PERF.md)."""
