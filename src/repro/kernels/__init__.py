"""Pallas TPU kernels for the serving hot spots."""
