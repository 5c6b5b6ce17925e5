"""repro.serve — the serving subsystem (see README.md in this directory).

Two serving engines over one fused sampling+decode step:

* :mod:`repro.serve.engine` — :class:`ServeEngine`, static-batch generation
  (one rectangular prompt batch, dense KV cache, optional EOS masking).
* :mod:`repro.serve.continuous` — :class:`ContinuousBatchingEngine`,
  request queue + slot table over the paged KV cache
  (:mod:`repro.serve.kvcache`): admit into free slots, retire on EOS or
  budget, pages freed mid-flight.
  :mod:`repro.fleet.serve`'s ``ShardedFleetServeEngine`` is this engine
  over a chip axis placed on the pop mesh: the same programs and round
  loop, one ragged request stream and slot table per chip.
"""
from repro.serve.continuous import (
    ContinuousBatchingEngine,
    Request,
    RequestOutput,
    ServeStats,
)
from repro.serve.engine import GenerateResult, ServeEngine, make_sample_decode
from repro.serve.kvcache import (
    DEFAULT_PAGE_SIZE,
    PageAllocator,
    dense_kv_bytes,
    page_bytes,
    pages_needed,
    round_up_to_page,
)

__all__ = [
    "ContinuousBatchingEngine",
    "DEFAULT_PAGE_SIZE",
    "GenerateResult",
    "PageAllocator",
    "Request",
    "RequestOutput",
    "ServeEngine",
    "ServeStats",
    "dense_kv_bytes",
    "make_sample_decode",
    "page_bytes",
    "pages_needed",
    "round_up_to_page",
]
