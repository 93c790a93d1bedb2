"""The chip benchmark of this repository: ``python bench/run.py --workload <cell> ...``."""
