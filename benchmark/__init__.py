"""The benchmark: harness, yardstick, references, data (see README.md)."""
