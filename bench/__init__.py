"""The on-chip benchmark: harness, drivers, metric readers, reference."""
