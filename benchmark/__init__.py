"""The benchmark: yardstick, cells as data, and the command ``run.py``."""
