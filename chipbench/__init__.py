"""The on-chip benchmark: ``python3 chipbench/run.py --workload <cell> ...``."""

from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
