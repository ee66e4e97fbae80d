"""Checks of the benchmark's comparison: its control and its planted faults."""
