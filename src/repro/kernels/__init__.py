"""Pallas TPU kernels for the MITHRIL hot paths, plus their jnp oracles.

Every kernel has a pure-jnp reference (``ref.py`` or the ``core``
implementation it replaces) that is bit-identical (exact for int32
kernels, tolerance-checked for the float decode kernel) and a jit'd
public wrapper in ``ops.py``. Backend dispatch is uniform
(``backend.py``): ``interpret=None`` resolves to *compiled* on TPU and
*interpreted* elsewhere, and the sweep/serving engines go one step
further — off TPU they skip the kernels entirely and run the pure-jnp
forms, which are faster than interpretation (interpret mode exists for
correctness tests, never for performance numbers — DESIGN.md §11).

Backend-dispatch table (who selects what, where):

=======================  ==========================  =====================
kernel (``ops`` wrapper)  on TPU                      off TPU
=======================  ==========================  =====================
``mithril_record_fused``  fused record path, one      ``vmap(record_event)``
(``mithril_record.py``)   launch per request slab     scatter form (via
                          via ``sweep.               ``mithril.
                          _batched_record_fn``        record_event_batched``
                                                      default)
``mithril_pairwise``      mining barrier, one         ``core.mining``
(``mithril_mine.py``)     launch per flagged lane     pairwise oracle (via
                          via ``sweep.               ``mine_batched``
                          _batched_pairwise_fn``      default)
``prefetch_lookup``       batched pFlag probe         same kernel,
(``hash_lookup.py``)      (serving layer)             interpreted
``paged_decode``          flash-decode over paged     same kernel,
(``paged_decode.py``)     KV (``cache/tiered.py``)    interpreted
=======================  ==========================  =====================

Per-kernel cost accounting (bytes moved, arithmetic intensity,
machine-peak fraction) lives in ``repro.roofline.analysis`` and is
reported/gated by ``benchmarks/kernel_micro.py`` + ``benchmarks/
compare.py``.
"""

from . import ops, ref
from .ops import (mithril_pairwise, mithril_record_fused, paged_decode,
                  prefetch_lookup)

__all__ = ["ops", "ref", "mithril_pairwise", "mithril_record_fused",
           "paged_decode", "prefetch_lookup"]
