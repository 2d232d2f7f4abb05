"""Shared utilities: pytree helpers, HLO analysis, roofline math, host spans."""
