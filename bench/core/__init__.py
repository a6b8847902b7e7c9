"""The yardstick: generator, reductions, peaks, counters and comparisons."""
