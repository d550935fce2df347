"""MITHRIL core: sporadic-association mining for cache prefetching (paper Sec. 4).

The paper's primary contribution as a composable, jit-safe JAX module:
fixed-shape recording/mining/prefetching tables, the mining procedure
(dense vectorized + sequential oracle), and the Alg. 3 access API.
"""

from .config import MithrilConfig
from .state import MithrilState, init_state
from .mithril import (access, add_association, init, lookup, maybe_mine,
                      mine, mine_batched, record, record_event,
                      record_event_batched)
from .mining import (associations_dense, mine_reference_sequential,
                     pairwise_codes, select_pairs, sort_by_first_ts)
from .hashindex import EMPTY

__all__ = [
    "MithrilConfig", "MithrilState", "init_state", "init",
    "access", "add_association", "lookup", "mine", "record",
    "record_event", "record_event_batched", "maybe_mine", "mine_batched",
    "associations_dense", "mine_reference_sequential", "pairwise_codes",
    "select_pairs", "sort_by_first_ts", "EMPTY",
]
