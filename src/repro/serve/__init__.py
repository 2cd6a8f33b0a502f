"""Continuous-batching serving subsystem.

- ``scheduler``: request queue, slot-table lifecycle, SLA accounting,
  ``lib.cost()``-driven admission (host-side control plane, no jax) — plus
  ``PagedAdmission``: page-count admission (admit on pages available now)
  with defer-not-refuse semantics and preemption bookkeeping;
- ``slots``: slot-level state access — read a slot back out, validate a
  donor against the slot table (the insert/reset surgery itself lives on
  ``Model.insert_slot``/``reset_slot``, uniform over all four families) —
  plus the paged-memory host primitives (``PageAllocator``, ``SlotPages``);
- ``paging``: the paged slot store — per-leaf row pools gathered/scattered
  through the ``cache_page_read/write`` UPD primitives, content-addressed
  copy-on-write prefix sharing, opt-in int8 pages;
- ``engine``: the per-step continuous-batching loop (jit-stable shapes,
  per-slot positions, TTFT / decode-t/s / SLA metrics); ``paged=`` switches
  residency from max-bucket lanes to page accounting with parking and
  preemption;
- ``spec``: speculative decoding — drafters (n-gram prompt-lookup / small
  draft model), the longest-accepted-prefix rule, and UPD-cost-priced
  per-slot speculation depth (``attention_verify``'s serve block + cost
  terms drive both the span bound and the depth decision).

See README.md in this directory for the slot/state-surgery contract.
"""

from .engine import SamplingConfig, ServeEngine
from .paging import (PagedConfig, PagedKVStore, PrefixStore, prefix_key,
                     selected_page_size)
from .scheduler import (BucketPolicy, CostModelAdmission, PagedAdmission,
                        Request, RequestMetrics, Scheduler,
                        upd_serve_defaults)
from .slots import (PageAllocator, PagesExhausted, SlotPages,
                    assert_span_fits, take_slot, validate_donor)
from .spec import (DraftModelDrafter, NGramDrafter, SpeculationConfig,
                   SpeculationPolicy, accept_span, upd_verify_defaults)

__all__ = [
    "BucketPolicy",
    "CostModelAdmission",
    "DraftModelDrafter",
    "NGramDrafter",
    "PageAllocator",
    "PagedAdmission",
    "PagedConfig",
    "PagedKVStore",
    "PagesExhausted",
    "PrefixStore",
    "Request",
    "RequestMetrics",
    "SamplingConfig",
    "Scheduler",
    "ServeEngine",
    "SlotPages",
    "SpeculationConfig",
    "SpeculationPolicy",
    "accept_span",
    "assert_span_fits",
    "prefix_key",
    "selected_page_size",
    "take_slot",
    "upd_serve_defaults",
    "upd_verify_defaults",
    "validate_donor",
]
