"""The yardstick: owned by the benchmark, not by the program."""
