"""The benchmark's yardstick: peaks, operation and byte counts, trace
reduction, compile counting and the comparison that decides ``correct``."""
