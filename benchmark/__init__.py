"""The chip benchmark of sheep-tpu: ``python3 benchmark/run.py --help``."""
