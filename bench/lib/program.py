"""Where the benchmark meets the program under test, and how it finds the
pieces of a configuration by name.

The program sits under ``src/`` of the checkout.  A configuration file
names its pieces: ``program.family`` the adapter between the benchmark's
weights and the program's parameters (``bench/adapters/<family>.py``),
``reference`` the plain model (``bench/reference/<name>.py``), ``cost``
its operation counts (``bench/cost/<name>.py``).  A traffic mix names its
data source (``bench/sources/<name>.py``).  A new configuration or mix
adds files under those directories and edits none.
"""
from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")


def import_program():
    """Put the program on the path; False where the checkout lacks it."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return False
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    return True


def adapter(conf: dict):
    """``model_config``, ``params_from`` and ``leaf_names`` of the
    configuration's family."""
    return importlib.import_module("bench.adapters."
                                   + conf["program"]["family"])


def reference(conf: dict):
    """The plain reference model: ``shapes``, ``NORMS``, ``logits``,
    ``row_losses``, ``MATMULS`` and ``lower`` (the control's weights)."""
    return importlib.import_module("bench.reference." + conf["reference"])


def cost(conf: dict):
    """Operation counts of the configuration's model."""
    return importlib.import_module("bench.cost." + conf["cost"])


def source(data: dict, seed: int, conf: dict):
    """The data source a training mix names, made from the seed."""
    return importlib.import_module("bench.sources."
                                   + data["source"]).make(data, seed, conf)
