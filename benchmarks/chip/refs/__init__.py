"""Plain references the benchmark compares the program with; numpy only,
independent of the program's sources."""
