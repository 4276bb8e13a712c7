"""Plain references the benchmark's checks compare the program with."""
