"""Chip benchmark of the CARE balancer: cells, drivers, references, metrics."""
