"""Harness code of the benchmark: resolution by name, data and traffic
generation, the measured window, trace reduction and the output check."""
