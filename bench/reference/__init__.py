"""Plain references the benchmark compares the program's answers with."""
