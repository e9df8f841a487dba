"""Seeded data generators of the benchmark, run on the device."""
