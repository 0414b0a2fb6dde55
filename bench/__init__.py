"""On-chip benchmark of the served path (``QoSServer`` on ``StreamEngine``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON line.  Every
configuration, traffic mix and per-layer metric is a file of its own under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by name,
and every architecture a module under ``bench/archs``, found by the
configuration's ``model_type``.
"""
from pathlib import Path

#: the checkout's root, which holds ``BENCHMARK.json``
ROOT = Path(__file__).resolve().parents[1]
