"""Input generators of the benchmark, copied from the program so that the inputs never change when the program does."""
