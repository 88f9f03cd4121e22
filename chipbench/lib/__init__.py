"""Shared machinery of the on-chip benchmark: harness, model inputs,
plain references, comparisons, FLOP counts and trace reduction."""
