"""One file per kind of program the benchmark drives."""
