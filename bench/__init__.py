"""Chip benchmark of the LUT serving path.

One run serves one cell (a model configuration under a traffic mix) through
``make_engine`` -> ``Scheduler`` for a fixed window, checks what the window
produced against a plain reference, and prints one JSON result line.  See
``bench/run.py`` for the command line and ``BENCHMARK.json`` for the cells.
"""
