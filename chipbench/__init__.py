"""Chip benchmark: serving cells driven from data files.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

  configs/<config>.json     model sizes as run, source and departures
  traffic/<mix>.json        parameters of the one traffic generator
  workloads/<cell>.json     the cell's fixed rate and correctness limits
  metrics/<metric>.py       one reader: ``read(record) -> float | None``

Run a cell with ``python3 -m chipbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.
"""

import sys
from pathlib import Path

# The system under test is imported from the checkout's ``src``.
_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
