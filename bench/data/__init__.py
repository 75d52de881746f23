"""Seeded data generators, one module per design kind. A configuration names
its kind in ``"generator"``; ``bench.harness.load_generator`` imports
``bench/data/<kind>.py`` by that name."""
