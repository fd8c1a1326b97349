"""HBM-resident compressed series store.

Keeps sealed blocks' M3TSZ bytes resident in device memory (a paged pool
under a byte budget, pool.py) and decodes them on read (scan.py): warm
scans move zero block bytes host->device, and series selection is a
device gather of page rows instead of a host select/pack. The design of
the reference TSDB's in-memory tier (M3/M3TSZ after Pelkonen et al.'s
Gorilla), restated as a paged KV-cache-style memory manager for the
scan-and-aggregate hot path.

The package's own names are the pool's, which import no jax: a process that
only stores (or only routes) pays nothing for the scan side. gather.py (the
read side of the page format) and scan.py (the scans over it) import jax and
are imported by what dispatches them (query/m3_storage.py, query/plan.py).
"""

from .heat import ShardHeat
from .pool import (
    AdmitResult,
    ResidentChunkedPlan,
    ResidentEntry,
    ResidentOptions,
    ResidentPool,
    ResidentPoolError,
)

__all__ = [
    "AdmitResult",
    "ResidentChunkedPlan",
    "ResidentEntry",
    "ResidentOptions",
    "ResidentPool",
    "ResidentPoolError",
    "ShardHeat",
]
