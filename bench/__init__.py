"""Blaze's benchmark: the runner, its cells' files and its yardstick."""
