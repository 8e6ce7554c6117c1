"""Paged KV cache + radix prefix reuse for the serve engine.

The dense engine gives every slot a private ``[max_len]`` KV row and
prefills every request from scratch. At production traffic that is the
two biggest serving wastes at once: HBM is RESERVED at worst case per
slot (a 12-token request holds a 1024-token row), and shared prompt
prefixes (system prompts, few-shot headers, multi-turn conversations)
are RECOMPUTED per request. This package replaces the row cache with a
page pool and a host-side prefix cache:

- :mod:`pool` — the allocator: the KV cache becomes a
  ``[num_pages, page_size, ...]`` pytree; slots hold page tables
  (``[num_slots, max_pages]`` int32 fed to the jitted programs), pages
  are refcounted, and page 0 is the write-off page freed slots ride.
- :mod:`radix` — the prefix cache: a radix tree over token-id blocks
  maps a new request's longest cached prefix to refcounted pages, so
  prefill runs only on the uncached tail; multi-turn ``session``
  requests re-attach their conversation's pages (partial tail page
  included, copy-on-write when shared); refcount-0 cached pages evict
  LRU under pool pressure.
- :mod:`engine` — :class:`~engine.PagedSlotEngine`, the drop-in
  :class:`~tensorflow_distributed_tpu.serve.engine.SlotDecodeEngine`
  subclass dispatching the paged decode/verify/prefill executables
  (same one-program static-shape discipline, censused as
  ``serve_*_paged`` in the jaxpr goldens, zero collectives).

``--serve.paged`` arms it (default off: the dense engine code path is
untouched — byte-identical to the pre-paging tree); both engines are
held to one-shot ``generate()`` in tests/test_serve_ahead.py.
"""

from tensorflow_distributed_tpu.serve.paging.pool import (  # noqa: F401
    GARBAGE_PAGE, PagePool, PoolExhausted)
from tensorflow_distributed_tpu.serve.paging.radix import (  # noqa: F401
    RadixCache)
