"""The benchmark: one cell, one run, one result line (see README.md)."""
