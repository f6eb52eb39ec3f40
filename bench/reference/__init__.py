"""Plain float32 references the benchmark compares the program with.

They import nothing of the program under test and take nothing it made.
"""
