"""Per-layer metric readers, one per file, found by the metric's name."""
