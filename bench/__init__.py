"""Chip benchmark of the solver: cells, traffic mixes, references and trace
reduction. ``BENCHMARK.json`` at the repository root names the cells;
``bench/run.py`` runs one of them."""
