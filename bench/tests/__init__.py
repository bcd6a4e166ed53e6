"""Tests of the benchmark, on the CPU."""
