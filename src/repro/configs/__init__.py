"""Workload configs: ``cumbe`` holds the paper's graph-scale classes
(``CONFIG``, the 16k x 16k production class; ``SMOKE``, its CPU-sized
twin)."""
