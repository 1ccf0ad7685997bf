"""Traffic kinds, one module each, loaded by name by ``bench.traffic``."""
