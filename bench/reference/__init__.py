"""The plain reference the benchmark compares served answers with."""
