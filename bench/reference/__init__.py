"""Plain references the benchmark holds the system to. They import nothing
of the program and take nothing it made."""
