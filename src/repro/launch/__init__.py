"""Launchers: serve.py, train.py, maker_worker.py and the fleet supervisor.
Importing the package imports no JAX, so a supervisor that only starts
processes never holds a chip."""
