"""Shared machinery of the chip benchmark: spec loading, traffic
generation, weights, the system under test, trace reduction, operation
counts and the output check."""
