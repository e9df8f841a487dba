"""Plain references of the benchmark.  They import nothing of the
program under test."""
