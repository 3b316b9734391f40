"""``mxnet_tpu.serving.decode`` — autoregressive decode runtime with
continuous batching and a paged, slot-generation KV cache.

One-shot serving (:class:`~mxnet_tpu.serving.ModelRuntime` +
``Batcher``) answers a request with one compiled forward; generative
decode answers with a *loop* whose per-step shapes must never leave the
compiled bucket set.  This package applies the framework's whole-graph
discipline (PAPER.md design point #2) to that loop:

- :class:`CausalLM` (``model.py``) — decoder-only transformer whose
  prefill and per-token step are built from ONE set of pure layer
  functions, written row-stable so a request's tokens are bitwise
  independent of batch composition.
- :class:`LatentMoELM` (``latent_moe.py``) — the DeepSeek-V3 family's
  block behind the same runtime: RMSNorm, YaRN rotary, latent (MLA)
  attention over a ONE-pool cache (expanded in prefill, absorbed in the
  step), a dense FFN then routed + shared experts as one chip's share of
  an expert-parallel deployment (``parallel.moe.routed_expert_share``).
  bfloat16 products on the MXU; held to its plain reference within
  tolerances, not to bitwise row stability.
- :class:`HybridSSMMoELM` (``hybrid_moe.py``) — the ``nemotron_h``
  family's block, built from a pattern string: Mamba-2 state-space mixers
  (``mxnet_tpu.ops.ssm``: a chunked scan in prefill, one recurrence a row
  in the step) whose recurrent state and convolution tail live a SLOT in
  the cache's state pools, grouped-query attention layers that alone
  page, and un-gated ``relu^2`` routed + shared experts as a chip's share.
  No drafter, no quantized pools, no mesh; prefix sharing is skipped.
- :class:`WindowMoELM` (``window_moe.py``) — the ``mimo_v2`` family's
  block, built from ``hybrid_layer_pattern`` and ``moe_layer_freq``:
  sliding-window layers (a learned sink bias in the softmax, their own
  count of K/V heads) whose last ``window`` tokens live in a RING a slot of
  the cache's state pools, bounded whatever the context, beside global
  grouped-query layers that page; keys wider than values, partial rotary
  with a base a kind; a dense SwiGLU layer, then routed experts chosen
  through a selection bias as a chip's share.  Prefill goes by query blocks
  and holds no ``(heads, S, S)`` array.  No drafter, no quantized pools, no
  mesh; prefix sharing is skipped.
- :class:`LinearMoELM` (``linear_moe.py``) — the ``solar_open2``
  family's block: gated delta-rule ("KDA") linear-attention layers
  (``mxnet_tpu.ops.delta_rule``: a chunked form with a triangular solve in
  prefill, one rank-one update a row in the step) whose ``(heads, dk, dv)``
  matrix state and convolution tails live a SLOT in the cache's state
  pools, three to each NoPE grouped-query layer with an output gate, which
  alone pages; routed + shared SwiGLU experts in every layer as a chip's
  share.  No drafter, no quantized pools, no mesh; prefix sharing is
  skipped.
- :class:`EvaLM` (``eva_lm.py``) — the ``evabyte`` family's block, dense and
  byte-level: every layer attends exactly inside the query's own window of
  ``window_size`` positions and, in the SAME softmax, over one learned
  summary a ``chunk_size`` chunk of every window before it.  A slot keeps a
  RING of the open window's keys and values (entry ``e`` live iff ``e <=
  position mod window``); the summaries page, a ROW standing for
  ``chunk_size`` tokens (the layout's ``row_tokens``, which the cache's
  reservations and the scheduler's page arithmetic follow).  Eight
  prediction heads, head 0 served.  No drafter, no quantized pools, no mesh;
  prefix sharing is skipped.
- :class:`PagedKVCache` (``kv_cache.py``) — device-resident page pools
  with a trash page for padding, generation-stamped slots (the ShmRing
  discipline: a post-free read raises ``StaleKVSlotError`` under
  ``MXNET_SANITIZE=slots``), refcounted **shared-prefix pages**
  (content-hashed at prefill commit, acquired by page-table update on a
  hit, copy-on-write on divergence) and optional ``NamedSharding`` over
  the heads axis so the cache scales with the mesh.
- :class:`PageFormat` (``kv_format.py``) — what the pools store (raw, or
  int8 / fp8 e4m3 codes with per-row sidecars: ``kv_dtype``) and the only
  two functions that index one; quantization is fused into whichever
  program writes and reads.  :class:`SlotState`, beside it, is the same
  for state that is per sequence and not per token: pools of one row a
  slot, the allocator's slots owning the rows (a state-space layer's
  recurrent state; a window layer's ring).
- :class:`DecodeRuntime` (``runtime.py``) — the 2-D *(batch x seqlen)*
  prefill grid warmed through ``HybridBlock.compile_grid`` plus ONE
  fused donated step program per batch bucket; ``decode.compile_miss``
  must stay zero in steady state across arbitrary join/evict patterns.
- :class:`DecodeScheduler` / :class:`DecodeSession` (``scheduler.py``) —
  continuous batching: requests join the running batch at step
  boundaries, finished sequences free their KV slots immediately, and
  the serving backpressure/deadline/circuit-breaker machinery carries
  over with KV exhaustion as a new shed condition.
- :class:`NgramDrafter` (``speculate.py``) —
  speculative decoding over the fused per-bucket **verify** program:
  a drafter proposes ``k`` tokens, one donated step scores them all,
  and deterministic-equality acceptance commits the matching prefix —
  the emitted stream stays bitwise-identical to non-speculative
  decode (greedy and sampled), the draft only changes tokens/step.

Minimal use::

    import mxnet_tpu as mx

    net = mx.serving.decode.get_decode_model("decode_small")
    net.initialize()
    sess = mx.serving.decode.DecodeSession(net, page_size=16)
    fut = sess.submit([5, 9, 2], max_new_tokens=32, temperature=0.8,
                      seed=7, deadline_ms=5000)
    print(fut.result().token_ids)
    sess.close()
"""
from .kv_cache import (  # noqa: F401
    KVCacheExhausted,
    KVSlot,
    PagedKVCache,
    pages_needed,
)
from .kv_format import (  # noqa: F401
    PageFormat,
    SlotState,
    kv_dequantize,
    kv_dequantize_fp8,
    kv_quantize_rows,
    kv_quantize_rows_fp8,
)
from .model import (  # noqa: F401
    CausalLM,
    get_decode_model,
    rowdot,
    sample_math,
)
from .latent_moe import LatentMoELM  # noqa: F401
from .hybrid_moe import HybridSSMMoELM  # noqa: F401
from .window_moe import WindowMoELM  # noqa: F401
from .linear_moe import LinearMoELM  # noqa: F401
from .eva_lm import EvaLM  # noqa: F401
from .runtime import DecodeRuntime, seq_bucket_ladder  # noqa: F401
from .scheduler import (  # noqa: F401
    DecodeScheduler,
    DecodeSession,
    GenerationResult,
    TokenStream,
)
from .speculate import (  # noqa: F401
    Drafter,
    NgramDrafter,
    SpecState,
)

__all__ = ["CausalLM", "LatentMoELM", "HybridSSMMoELM", "WindowMoELM",
           "LinearMoELM", "EvaLM",
           "get_decode_model", "rowdot",
           "sample_math",
           "kv_quantize_rows", "kv_dequantize",
           "kv_quantize_rows_fp8", "kv_dequantize_fp8",
           "PagedKVCache", "PageFormat", "SlotState", "KVSlot", "KVCacheExhausted",
           "pages_needed",
           "DecodeRuntime", "seq_bucket_ladder",
           "DecodeScheduler", "DecodeSession", "GenerationResult",
           "TokenStream",
           "Drafter", "NgramDrafter", "SpecState"]
