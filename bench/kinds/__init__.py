"""Kinds of traffic, one module each, loaded by name by ``bench.traffic``."""
