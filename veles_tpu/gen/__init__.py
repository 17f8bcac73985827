"""veles_tpu.gen — continuously-batched generative serving.

The autoregressive half of the serving stack (ROADMAP item 3): the
request/response engine (:mod:`veles_tpu.serve`) answers one forward
per request; this package serves token STREAMS from a device-resident
KV cache with iteration-level scheduling.  Pieces:

- :mod:`model` — the generative model protocol (prefill + one decode
  step over a slot-major KV cache) and
  :class:`~veles_tpu.gen.model.TransformerGenModel`, the adapter for
  the ``samples/transformer.py`` LM family.
- :mod:`hybrid` — :class:`~veles_tpu.gen.hybrid.HybridGenModel`: a
  second model behind the same protocol, with three kinds of layer in
  one stack (Mamba-2 mixers, a chip's share of a latent mixture of
  experts, grouped-query attention) and a cache of two kinds of state
  (recurrent state beside keys and values); contiguous mode only.
- :mod:`window_moe` — :class:`~veles_tpu.gen.window_moe
  .WindowMoEGenModel`: a third model behind the same protocol, sliding
  window layers with rotary positions among full layers without, a
  parallel block, a chip's share of gated experts; its cache is a tree
  of rings and full-length rows, filled by chunks; contiguous mode only.
- :mod:`experts` — the chip's share of a mixture of experts that both
  of those classes run, for two forms of expert.
- :mod:`engine` — :class:`~veles_tpu.gen.engine.GenerativeEngine`:
  AOT-compiled prefill buckets + ONE fixed-shape decode program,
  KV cache in the HBM ledger's ``kv`` category, tensor-parallel
  sharded forward over a ``model``-axis mesh with transparent
  single-device fallback.
- :mod:`scheduler` — :class:`~veles_tpu.gen.scheduler
  .GenerativeScheduler`: continuous batching (admit into open slots
  every decode iteration, evict at finish, stream tokens per
  request) and :func:`~veles_tpu.gen.scheduler.static_generate`, the
  pad-to-slowest baseline it is benchmarked against.
- :mod:`paged` — the block-pool paged KV cache
  (``root.common.gen.kv = "paged"``): a shared device page pool +
  per-slot block tables replace the per-slot ``max_seq``
  reservation, chunked prefill (``root.common.gen.prefill_chunk``)
  interleaves admissions with decode steps, and pool exhaustion
  preempts the youngest sequence losslessly.  See
  ``docs/services.md`` § Paged KV.
- :mod:`prefix` — the radix prefix cache
  (``root.common.gen.prefix_cache = "on"``): refcounted
  copy-on-write page sharing across admissions of a common prompt
  prefix; admission prices only the unshared suffix and eviction is
  LRU-leaf, never a referenced page.
- speculative decode (``root.common.gen.speculative = "ngram"`` or a
  registered draft model, ``root.common.gen.draft_k``): draft K
  tokens per slot, verify them all in ONE fixed-shape dispatch,
  accept greedily — the emitted stream stays BITWISE plain decode.
  See ``docs/services.md`` § Prefix cache & speculative decode.

Deployment rides the existing registry
(``ModelRegistry.deploy_generative`` — analyzer rule V-S01 preflights
the KV footprint and model shape) and the HTTP front-end
(``POST /generate[/<model>]``, optionally streaming ndjson).  See
``docs/services.md`` § Generative serving.

``python -m veles_tpu.gen --smoke`` is the CI gate: warmup, then a
mixed-length closed-loop session with ZERO steady-state compiles.
"""

from veles_tpu.gen.engine import (  # noqa: F401
    DRAFT_MODELS, DraftModelProposer, GenerativeEngine, NGramProposer,
    register_draft_model)
from veles_tpu.gen.hybrid import HybridGenModel  # noqa: F401
from veles_tpu.gen.model import TransformerGenModel  # noqa: F401
from veles_tpu.gen.paged import BlockPool, PoolExhausted  # noqa: F401
from veles_tpu.gen.prefix import PrefixCache  # noqa: F401
from veles_tpu.gen.scheduler import (  # noqa: F401
    GenerativeScheduler, static_generate)
from veles_tpu.gen.window_moe import WindowMoEGenModel  # noqa: F401

__all__ = [
    "BlockPool", "DRAFT_MODELS", "DraftModelProposer",
    "GenerativeEngine", "GenerativeScheduler", "HybridGenModel",
    "NGramProposer",
    "PoolExhausted", "PrefixCache", "TransformerGenModel",
    "WindowMoEGenModel", "register_draft_model", "static_generate",
]
