"""The chip benchmark of the detector engine: ``python3 chipbench/run.py``.

``BENCHMARK.json`` at the checkout root names the cells; ``catalog.py`` finds
each one's configuration, traffic mix, metrics and kernel counts by name.
"""
