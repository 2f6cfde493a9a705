"""Plain references the benchmark compares the program's output with.
They import nothing of the program under test."""
