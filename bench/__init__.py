"""The chip benchmark of the MBE service: ``python bench/run.py --help``."""
