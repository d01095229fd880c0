"""The chip benchmark of this repo: `python3 bench/run.py --help`."""
