"""Chip benchmark of the served feature path.

One command runs one cell (one deployment under one traffic mix) once:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells. Everything that
belongs to one configuration, traffic mix or per-layer metric lives in a
file of its own, found by name:

- ``chipbench/configs/<config>.json`` holds the deployment's sizes,
  ``<config>_ref.py`` makes its raw data from the seed and holds the plain
  numpy reference, and ``<config>.py`` loads that data into the system;
- ``chipbench/traffic/<mix>.json`` holds a mix's parameters; it names its
  driver, ``chipbench/loops/<loop>.py``, which finds the laws and ops the
  mix names (``arrivals/``, ``sizes/``, ``keys/``, ``ops/``) by name too
  (:mod:`chipbench.load`);
- ``chipbench/metrics/<metric>.py`` reads one metric.
"""
