"""End-to-end metric readers, one module per metric name."""
