"""Plain references the benchmark compares the program with."""
