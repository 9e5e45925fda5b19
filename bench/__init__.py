"""Chip benchmark of the fleet sweep: one cell per process (see `bench.run`)."""
