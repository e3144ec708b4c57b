"""On-chip benchmark of the PIC-MC engine step (see BENCHMARK.json).

``run.py`` runs one cell once. Cells, configurations, traffic mixes and
per-layer metrics are data found by name: ``configs/<config>.json``,
``traffic/<mix>.json`` and ``layer_metrics/<metric>.py``.
"""
