"""The yardstick: cell resolution, peaks, required work, traffic, trace
reduction and the correctness comparison. Nothing here imports the
program under test."""
