"""Planted faults of the cells added after ``test_chipbench_check.py``.

That file's ``FAULTS`` maps every cell to a fault planted in the system
under test before a rehearsal run, and checks that each cell has one. A
cell added later is added as files and entries only, so its fault comes
from here and is entered in the map before any test runs."""
from __future__ import annotations

import test_chipbench_check

# lineitem-extract: every launch's first served row has its discount
# one-hot moved one column over
SHIFTED_ONEHOT = """
import jax.numpy as jnp
from repro.core import pipeline
_gather = pipeline._packed_split_rows
def altered(*a, **k):
    out = _gather(*a, **k)
    return out.at[0, 3:14].set(jnp.roll(out[0, 3:14], 1))
pipeline._packed_split_rows = altered
"""

LATER_FAULTS = {"lineitem-extract": SHIFTED_ONEHOT}

for _cell, _fault in LATER_FAULTS.items():
    test_chipbench_check.FAULTS.setdefault(_cell, _fault)
