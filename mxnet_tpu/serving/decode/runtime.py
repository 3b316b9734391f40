"""DecodeRuntime — the compiled-shape side of generative serving.

One-shot serving needs one ladder (batch buckets); autoregressive decode
needs two compiled surfaces with different shape disciplines:

- **Prefill** pads each prompt group to ``(batch_bucket, seq_bucket)`` — a
  2-D grid warmed at load through the CachedOp path
  (``HybridBlock.compile_grid``), paired with a *commit* program per grid
  point that scatters the emitted K/V into cache pages and samples the
  first token.
- **The decode step** is ONE fused donated program per *batch bucket*:
  write new K/V into pages, gather the fixed-length paged context, attend,
  sample.  Sequence length never appears in its shape — the page table
  indirection keeps every step of every request inside the same handful of
  executables, which is what makes ``decode.compile_miss == 0`` steady
  state possible across arbitrary join/evict patterns.

The page pools are donated to both the commit and step programs
(functionally updated in place); under ``MXNET_SANITIZE=donation`` the
pre-call arrays are poisoned at sites ``decode.prefill_commit`` /
``decode.step`` exactly like the aggregated-optimizer and engine-segment
donation sites.

How the pools are stored is the cache's page format (``kv_format``): the
runtime threads ``cache.pools`` through every program as one donated,
poisoned tuple and hands the block ``cache.pages`` to write and read them
with, so a quantized format costs no extra program and ``warm()`` covers
it exactly like the raw one.  Per-sequence state (a state-space layer's)
rides the same way: its pools sit behind the page pools in the one donated
tuple, and each row of ``tables`` ends with the row's state slot
(``kv_format``), so no program takes an argument more for it.
"""
from __future__ import annotations

import numpy as np

from ... import autograd
from ... import ndarray as nd
from ...analysis import sanitizer as _san
from ...gluon.block import io_signature
from ...telemetry import bus as _tel
from ..aot import as_program_cache
from ..runtime import default_buckets, place_block
from .kv_cache import PagedKVCache
from .kv_format import PageFormat

__all__ = ["DecodeRuntime", "StepFlight", "seq_bucket_ladder"]


def seq_bucket_ladder(max_seqlen, min_bucket=8):
    """Power-of-two sequence-length ladder capped at ``max_seqlen`` (the
    cap itself is always a bucket) — the second axis of the prefill grid."""
    max_seqlen = int(max_seqlen)
    if max_seqlen < 1:
        raise ValueError(f"max_seqlen must be >= 1, got {max_seqlen}")
    ladder, b = [], max(int(min_bucket), 1)
    while b < max_seqlen:
        ladder.append(b)
        b *= 2
    ladder.append(max_seqlen)
    return tuple(sorted(set(ladder)))


def _state_structs(spec):
    """``block.prefill_state``'s answer as what a commit program is lowered
    with: one ``(shape, dtype)`` or a tuple of them."""
    import jax
    if isinstance(spec[1], str):
        return jax.ShapeDtypeStruct(*spec)
    return tuple(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in spec)


class StepFlight:
    """A launched decode step: ``tokens``, its sampled next token per row,
    int32 ``(b,)`` and still on the device, and ``counts``, empty or the one
    vector of the block's counts."""

    __slots__ = ("tokens", "counts")

    def __init__(self, tokens, counts):
        self.tokens = tokens
        self.counts = counts


class DecodeRuntime:
    """A decode block plus a :class:`PagedKVCache`, compiled into the 2-D
    prefill grid and per-batch-bucket step programs described in the module
    docstring.

    **What a block is to the runtime** (:class:`~mxnet_tpu.serving.decode.
    model.CausalLM`, :class:`~mxnet_tpu.serving.decode.latent_moe.
    LatentMoELM`, :class:`~mxnet_tpu.serving.decode.hybrid_moe.
    HybridSSMMoELM`, :class:`~mxnet_tpu.serving.decode.window_moe.
    WindowMoELM` and :class:`~mxnet_tpu.serving.decode.linear_moe.
    LinearMoELM` are): a hybridizable block whose forward is the
    prefill ``(tokens (B, S), lengths (B,)) -> (last_logits, state)``
    (``state`` one array, or several behind the logits), with
    ``vocab_size``, ``param_leaves()`` / ``_params_dict(leaves)``,
    ``cache_layout()`` (the pools the cache builds, whether they may be
    quantized or sharded, and ``max_length``, the context the block is
    good for), ``max_prefill_batch`` (the most prompts one prefill call may
    hold; None: as many as a step), ``prefill_state(b, s)`` (shape and
    dtype of that ``state``, or a tuple of such pairs),
    ``commit_program(state, lengths, tables, pools, pages) -> pools``,
    ``step_program(params, tokens, positions, tables, pools, pages)
    -> (logits, pools, extras)`` and ``sample_math``; ``pages`` is the
    cache's ``PageFormat``, the block's only way into ``pools``; ``tables
    (B, cache.table_width)`` holds each row's page table and, for a block
    with per-sequence state, its state row behind it (``pages.addresses``
    splits them; ``pages.state`` reads and writes the rows).  ``extras``
    are int32 arrays that leave the step program as one vector behind the
    pools; it is fetched, and handed to ``block.record_step_extras``, only
    when telemetry is on.  A
    verify ladder (``spec_buckets``) also needs ``verify_program(params,
    tokens, positions, n_draft, tables, pools, pages) -> (logits (B, K+1,
    vocab), pools)``.

    Parameters
    ----------
    block : CausalLM or LatentMoELM
        Initialized decode model (hybridized in place if needed).
    cache : PagedKVCache, optional
        Built from the block's ``cache_layout()`` when omitted
        (``page_size`` / ``num_pages`` / ``max_slots`` forwarded).
    batch_buckets : sequence of int
        Decode-batch ladder; the cap is the max concurrent batch.  The
        prefill grid takes the buckets up to the block's
        ``max_prefill_batch`` (all of them where that is None).
    seq_buckets : sequence of int, optional
        Prompt-length ladder; defaults to :func:`seq_bucket_ladder` over
        the cache's context length.  Prompts longer than the cap are
        rejected at submit.
    warm : bool
        Compile the full grid + step ladder now (default).  Serving cold
        shapes later is counted as ``decode.compile_miss``.
    aot_cache : str or ProgramCache, optional
        Persistent program cache (``serving.aot``): a directory path (a
        cache is derived from the model signature + full serving
        geometry) or a ready :class:`~mxnet_tpu.serving.aot.ProgramCache`.
        With a warm cache, :meth:`warm` deserializes the whole
        prefill/commit grid + step ladder off disk — a restarted process
        answers its first request without a single XLA compile, with
        bitwise-identical outputs.  Ignored under a ``mesh`` (sharded
        executables are not portably serializable).
    """

    def __init__(self, block, cache=None, batch_buckets=(1, 2, 4, 8),
                 seq_buckets=None, page_size=16, num_pages=None,
                 max_slots=None, kv_dtype=None, prefix_sharing=True,
                 mesh=None, name=None, warm=True, aot_cache=None,
                 spec_buckets=()):
        if not getattr(block, "_active", False):
            block.hybridize()
        self._block = block
        layout = block.cache_layout()
        if spec_buckets and not hasattr(block, "verify_program"):
            raise ValueError(
                f"{type(block).__name__} has no verify program: a session "
                f"of this block cannot speculate (drafter=None only)")
        self.name = name or getattr(block, "name", "decode")
        self.batch_buckets = tuple(sorted(set(
            int(b) for b in batch_buckets)))
        if self.batch_buckets[0] < 1:
            raise ValueError(f"batch buckets {self.batch_buckets} must "
                             f"be >= 1")
        self.max_batch = self.batch_buckets[-1]
        # the prefill grid's rows: the ladder as far as the block's own cap
        cap = block.max_prefill_batch
        self.prefill_batch_buckets = tuple(
            b for b in self.batch_buckets if cap is None or b <= cap)
        if not self.prefill_batch_buckets:
            raise ValueError(
                f"{type(block).__name__} prefills at most {cap} prompts a "
                f"call: batch buckets {self.batch_buckets} need one that "
                f"small")
        self.max_prefill_batch = self.prefill_batch_buckets[-1]
        # the context the block is good for: a position table's length, or
        # the context a rotary block was built for
        max_length = int(layout["max_length"])
        if cache is None:
            # floor, not ceil: the derived context (max_pages * the tokens
            # a page stands for) must never exceed the model's max_length
            max_pages = max_length // PageFormat.tokens_a_page(layout,
                                                               page_size)
            if max_pages < 1:
                raise ValueError(
                    f"page_size={page_size} exceeds the model's "
                    f"max_length={max_length} — no whole page fits "
                    f"the position table")
            cache = PagedKVCache(
                layout=layout, page_size=page_size,
                num_pages=(num_pages if num_pages is not None
                           else max_pages * 2 * self.max_batch + 1),
                max_pages_per_seq=max_pages,
                # a slot of per-sequence state is megabytes whether or not
                # a sequence holds it: such a block gets a slot a row
                max_slots=(max_slots if max_slots is not None
                           else self.max_batch if layout.get("state")
                           else 2 * self.max_batch),
                kv_dtype=kv_dtype, prefix_sharing=prefix_sharing,
                mesh=mesh)
        if cache.context_length > max_length:
            raise ValueError(
                f"cache context {cache.context_length} exceeds the model's "
                f"position table ({max_length})")
        if [(w, d) for _n, w, d in cache.pool_layout] != \
                [(w, d) for _n, w, d in layout["pools"]]:
            raise ValueError(
                f"cache pools {cache.pool_layout} are not the block's "
                f"{tuple(layout['pools'])}")
        if cache.max_slots < self.max_batch:
            raise ValueError(
                f"cache max_slots={cache.max_slots} < largest batch "
                f"bucket {self.max_batch}")
        self.cache = cache
        self.seq_buckets = tuple(sorted(set(
            int(s) for s in (seq_buckets if seq_buckets is not None
                             else seq_bucket_ladder(cache.context_length)))))
        if self.seq_buckets[-1] > cache.context_length:
            raise ValueError(
                f"seq buckets {self.seq_buckets} exceed the cache context "
                f"({cache.context_length} tokens)")
        self.max_prompt_len = self.seq_buckets[-1]
        # speculative-decode ladder: one fused verify program per
        # (batch bucket, k bucket) — empty tuple means no speculative
        # programs are built or warmed (zero cost for plain decode)
        self.spec_buckets = tuple(sorted(set(
            int(k) for k in spec_buckets)))
        if self.spec_buckets and self.spec_buckets[0] < 1:
            raise ValueError(
                f"spec buckets {self.spec_buckets} must be >= 1")
        if self.spec_buckets and \
                self.spec_buckets[-1] >= cache.context_length:
            raise ValueError(
                f"spec bucket cap {self.spec_buckets[-1]} exceeds the "
                f"cache context ({cache.context_length} tokens)")
        self.max_spec_k = self.spec_buckets[-1] if self.spec_buckets else 0
        import jax
        # sharded cache: the page pools live distributed over the mesh,
        # while the block's params (and the CachedOp prefill outputs) are
        # committed to one device — jit refuses mixed committed placements.
        # Replicate the params once and each prefill's K/V at the commit
        # boundary; everything downstream is then mesh-consistent.
        self._replicate = None
        if getattr(cache, "mesh", None) is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(cache.mesh, PartitionSpec())
            self.device = cache.mesh.devices.flat[0]
            self._params = [jax.device_put(p, rep)
                            for p in block.param_leaves()]
            self._replicate = lambda x: jax.device_put(x, rep)
            self._place = self._replicate
        else:
            # one device for parameters, page pools and every program:
            # committing the pools beside the placed block leaves jit no
            # choice of where to run
            self.device = place_block(block)
            self._params = block.param_leaves()
            self._place = lambda x: jax.device_put(x, self.device)
            cache.set_pools(jax.device_put(p, self.device)
                            for p in cache.pools)
        self._step_fns = {}       # batch_bucket -> donated jit
        self._commit_fns = {}     # (batch_bucket, seq_bucket) -> donated jit
        self._verify_fns = {}     # (batch_bucket, spec_k) -> donated jit
        self._sample_fn = None    # batch-1 first-token sampler (prefix hits)
        self._prefill_sigs = set()
        # every piece of serving geometry below shapes a compiled program
        # — all of it salts the cache key, so e.g. a page_size change
        # can never replay last deployment's executables
        if self._replicate is not None:
            aot_cache = None     # sharded: executables are mesh-bound
        self.aot_cache = as_program_cache(
            aot_cache, block,
            salt=f"decode:{self.batch_buckets}:{self.seq_buckets}"
                 f":pg{cache.page_size}:np{cache.num_pages}"
                 f":mp{cache.max_pages_per_seq}:sl{cache.max_slots}"
                 f":kv{cache.kv_dtype}:pfx{cache.prefix_sharing}"
                 f":spec{self.spec_buckets}:counts-behind-pools"
                 f":pools{[(p.shape, str(p.dtype)) for p in cache.pools]}")
        self._warmed = False
        if warm:
            self.warm()

    @property
    def block(self):
        return self._block

    # -------------------------------------------------------------- ladders
    def batch_bucket_for(self, n):
        for b in self.batch_buckets:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds bucket cap {self.max_batch}")

    def seq_bucket_for(self, n):
        for s in self.seq_buckets:
            if s >= n:
                return s
        raise ValueError(
            f"prompt of {n} tokens exceeds the largest seq bucket "
            f"{self.max_prompt_len}")

    def spec_bucket_for(self, k):
        """Smallest warmed verify-k bucket covering ``k`` drafted tokens
        (callers clamp per-row k to ``max_spec_k``, so the cap always
        covers)."""
        for kb in self.spec_buckets:
            if kb >= k:
                return kb
        raise ValueError(
            f"draft of {k} tokens exceeds the spec bucket cap "
            f"{self.max_spec_k}")

    # --------------------------------------------------------------- warmup
    def warm(self):
        """AOT-compile the whole 2-D prefill/commit grid and every step
        bucket before taking traffic.

        The prefill block rides ``HybridBlock.compile_grid``; the commit
        and step programs are then *driven* once per bucket with all-trash
        page tables — every row scatters into the reserved trash page, so
        warming executes the real donated programs without touching a
        single allocated page.  (Building the ``jax.jit`` objects alone
        would defer XLA compilation to the first mid-traffic call.)"""
        grid = [(b, s) for b in self.prefill_batch_buckets
                for s in self.seq_buckets]
        with _tel.span("decode.warmup", model=self.name,
                       grid=len(grid), steps=len(self.batch_buckets)):
            def make_example(b, s):
                return [nd.array(np.zeros((b, s), "int32"), ctx=self.device),
                        nd.array(np.ones((b,), "int32"), ctx=self.device)]

            with autograd.pause(train_mode=False):
                self._prefill_sigs.update(
                    self._block.compile_grid(
                        make_example, grid, cache=self.aot_cache).values())
            if self.aot_cache is not None:
                self._warm_aot(grid)
            np_ = self.cache.table_width
            for b, s in grid:
                self.prefill(np.zeros((b, s), "int32"),
                             np.ones((b,), "int32"),
                             np.zeros((b, np_), "int32"),
                             np.zeros((b, 2), "uint32"),
                             np.zeros((b,), "float32"))
            for b in self.batch_buckets:
                self.step(np.zeros((b,), "int32"), np.zeros((b,), "int32"),
                          np.zeros((b, np_), "int32"),
                          np.zeros((b, 2), "uint32"),
                          np.zeros((b,), "int32"), np.zeros((b,), "float32"))
            # speculative verify ladder: one fused program per (batch, k)
            # bucket, driven with n_draft=0 against all-trash tables —
            # exactly like the step programs above
            for b in self.batch_buckets:
                for k in self.spec_buckets:
                    self.verify(np.zeros((b, k + 1), "int32"),
                                np.zeros((b,), "int32"),
                                np.zeros((b,), "int32"),
                                np.zeros((b, np_), "int32"),
                                np.zeros((b, 2), "uint32"),
                                np.zeros((b,), "int32"),
                                np.zeros((b,), "float32"))
            # the two programs OUTSIDE the bucket grid: the batch-1
            # first-token sampler (prefix-hit admissions) and the cache's
            # CoW page copy — drive both so no prefix hit compiles
            # anything mid-traffic
            self.sample_first(
                np.zeros((self._block.vocab_size,), "float32"),
                np.zeros((2,), "uint32"), 0.0)
            if self.cache.prefix_sharing:
                self.cache.warm_programs()
        self._warmed = True
        if _tel.enabled:
            _tel.count("decode.warmup_compiles",
                       2 * len(grid) + len(self.batch_buckets)
                       * (1 + len(self.spec_buckets)),
                       model=self.name)

    def _warm_aot(self, grid):
        """Resolve every step / commit / first-token-sample program through
        the persistent program cache: a valid on-disk entry deserializes
        the byte-exact executable (zero trace, zero XLA compile); a miss
        AOT-compiles and commits it for the next process.  The warm()
        drive that follows then executes already-resolved programs."""
        import jax
        pc = self.aot_cache
        block, cache = self._block, self.cache
        np_ = cache.table_width
        pools = tuple(cache.pools)
        for b in self.batch_buckets:
            if b in self._step_fns:
                continue
            args = (self._params, np.zeros((b,), "int32"),
                    np.zeros((b,), "int32"), np.zeros((b, np_), "int32"),
                    np.zeros((b, 2), "uint32"), np.zeros((b,), "int32"),
                    np.zeros((b,), "float32")) + pools
            fn, _, _ = pc.load_or_build(
                f"step-b{b}", self._build_step(), args)
            self._step_fns[b] = fn
        for b in self.batch_buckets:
            for k in self.spec_buckets:
                if (b, k) in self._verify_fns:
                    continue
                args = (self._params, np.zeros((b, k + 1), "int32"),
                        np.zeros((b,), "int32"), np.zeros((b,), "int32"),
                        np.zeros((b, np_), "int32"),
                        np.zeros((b, 2), "uint32"),
                        np.zeros((b,), "int32"),
                        np.zeros((b,), "float32")) + pools
                fn, _, _ = pc.load_or_build(
                    f"verify-b{b}-k{k}", self._build_verify(), args)
                self._verify_fns[(b, k)] = fn
        for b, s in grid:
            if (b, s) in self._commit_fns:
                continue
            args = (self._params, _state_structs(block.prefill_state(b, s)),
                    np.zeros((b, block.vocab_size), "float32"),
                    np.zeros((b,), "int32"), np.zeros((b, np_), "int32"),
                    np.zeros((b, 2), "uint32"), np.zeros((b,), "int32"),
                    np.zeros((b,), "float32")) + pools
            fn, _, _ = pc.load_or_build(
                f"commit-b{b}-s{s}", self._build_commit(), args)
            self._commit_fns[(b, s)] = fn
        if self._sample_fn is None:
            args = (np.zeros((1, block.vocab_size), "float32"),
                    np.zeros((1, 2), "uint32"), np.zeros((1,), "int32"),
                    np.zeros((1,), "float32"))
            fn, _, _ = pc.load_or_build(
                "sample_first", jax.jit(block.sample_math), args)
            self._sample_fn = fn

    def _miss(self, kind, key):
        if _tel.enabled:
            _tel.count("decode.compile_miss", model=self.name, kind=kind)
            _tel.instant("decode.compile_miss", model=self.name, kind=kind,
                         bucket=str(key))

    # ------------------------------------------------------- program builds
    def _step_fn(self, bucket):
        fn = self._step_fns.get(bucket)
        if fn is None:
            if self._warmed:
                self._miss("step", bucket)
            fn = self._build_step()
            self._step_fns[bucket] = fn
        return fn

    def _commit_fn(self, bucket_b, bucket_s):
        key = (bucket_b, bucket_s)
        fn = self._commit_fns.get(key)
        if fn is None:
            if self._warmed:
                self._miss("prefill_commit", key)
            fn = self._build_commit()
            self._commit_fns[key] = fn
        return fn

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        block, pages = self._block, self.cache.pages

        def step(params, tokens, positions, tables, keys, steps, temps,
                 *pools):
            p = block._params_dict(params)
            logits, pools, extras = block.step_program(
                p, tokens, positions, tables, pools, pages)
            nxt = block.sample_math(logits, keys, steps, temps)
            # the tokens are an output of their own, int32 (b,): the next
            # step's ``tokens`` as they are, without a visit to the host.
            # Whatever the block counts (nothing for CausalLM) is one more
            # vector behind the pools, fetched only where it is recorded
            counts = (jnp.concatenate(
                [e.astype("int32").reshape(-1) for e in extras]),) \
                if extras else ()
            return (nxt.astype("int32"),) + tuple(pools) + counts

        n = len(self.cache.pools)
        return jax.jit(step, donate_argnums=tuple(range(7, 7 + n)))

    def _verify_fn(self, bucket_b, bucket_k):
        key = (bucket_b, bucket_k)
        fn = self._verify_fns.get(key)
        if fn is None:
            if self._warmed:
                self._miss("verify", key)
            fn = self._build_verify()
            self._verify_fns[key] = fn
        return fn

    def _build_verify(self):
        """The fused speculative verify program: score ``k`` drafted
        tokens (plus the current one) in ONE donated call, sample the
        target's token at every offset through the per-request
        ``fold_in(key, step + j)`` streams, and count the accepted
        prefix — never a Python loop per token.

        Acceptance is *deterministic equality*: offset ``j``'s target
        sample uses exactly the fold the non-speculative step ``j``
        would, over bitwise the same logits (see
        :meth:`CausalLM.verify_program`), so the emitted stream —
        ``target[0 .. n_acc]`` — is always bitwise the non-speculative
        stream, for greedy AND sampled temperatures."""
        import jax
        import jax.numpy as jnp
        block, pages = self._block, self.cache.pages

        def verify(params, tokens, positions, n_draft, tables, keys,
                   steps, temps, *pools):
            p = block._params_dict(params)
            logits, pools = block.verify_program(
                p, tokens, positions, n_draft, tables, pools, pages)
            B, K1 = tokens.shape
            flat = logits.reshape(B * K1, -1)
            # per-offset fold: row (b, j) samples with (key_b, step_b + j)
            # — bitwise the fold non-speculative step j would use
            target = block.sample_math(
                flat, jnp.repeat(keys, K1, axis=0),
                (steps[:, None]
                 + jnp.arange(K1, dtype="int32")[None, :]).reshape(-1),
                jnp.repeat(temps, K1)).reshape(B, K1)
            ok = ((tokens[:, 1:] == target[:, :-1])
                  & (jnp.arange(1, K1, dtype="int32")[None, :]
                     <= n_draft[:, None]))
            n_acc = jnp.cumprod(ok.astype("int32"), axis=1).sum(axis=1)
            return (target, n_acc) + tuple(pools)

        n = len(self.cache.pools)
        return jax.jit(verify, donate_argnums=tuple(range(8, 8 + n)))

    def _build_commit(self):
        import jax
        block, pages = self._block, self.cache.pages

        def commit(params, kv, logits, lengths, tables, keys, steps, temps,
                   *pools):
            pools = block.commit_program(kv, lengths, tables, pools, pages)
            first = block.sample_math(logits, keys, steps, temps)
            return (first,) + tuple(pools)

        n = len(self.cache.pools)
        return jax.jit(commit, donate_argnums=tuple(range(8, 8 + n)))

    # ------------------------------------------------------------ execution
    def prefill(self, tokens, lengths, tables, keys, temps):
        """Prefill + commit one padded prompt group.

        ``tokens (B, S)`` / ``lengths (B,)`` padded to a grid bucket
        (padded rows: length 1, all-trash table).  Returns ``(first,
        logits)`` — the sampled first token per row (host int32 array)
        plus, when the cache shares prefixes, the host copy of the
        last-position logits (``(B, vocab) float32``; the scheduler
        publishes each row to the prefix index so an exact-repeat prompt
        can skip this whole call).  The page pools are functionally
        updated in place (donated)."""
        b, s = tokens.shape
        # the span is the whole call as the scheduler sees it: .dispatch
        # runs from the arguments' staging until both programs have been
        # handed over, .fetch is where this thread waits for the chip
        with _tel.span("decode.prefill", model=self.name, batch=b, seq=s):
            with _tel.span("decode.prefill.dispatch"):
                tok_nd = nd.array(tokens, ctx=self.device)
                len_nd = nd.array(lengths, ctx=self.device)
                sig = io_signature([tok_nd, len_nd])
                if sig not in self._prefill_sigs:
                    if sig in self._block.compiled_signatures(
                            training=False):
                        self._prefill_sigs.add(sig)
                    elif self._warmed:
                        self._miss("prefill", (b, s))
                with autograd.pause(train_mode=False):
                    logits, *state = self._block(tok_nd, len_nd)
                self._prefill_sigs.add(sig)
                commit = self._commit_fn(b, s)
                cache = self.cache
                pools = cache.pools
                # one array as it is (the programs of the blocks that emit
                # one do not change), several as a tuple
                kv_raw = state[0].data if len(state) == 1 \
                    else tuple(x.data for x in state)
                logits_raw = logits.data
                if self._replicate is not None:
                    kv_raw = self._replicate(kv_raw)
                    logits_raw = self._replicate(logits_raw)
                out = commit(
                    self._params, kv_raw, logits_raw,
                    lengths.astype("int32"), tables.astype("int32"),
                    keys.astype("uint32"), np.zeros((b,), "int32"),
                    temps.astype("float32"), *pools)
                if _san.donation:
                    # the commit donated the page pools: poison the
                    # pre-call arrays so any stray alias raises naming
                    # this site
                    _san.poison(list(pools), "decode.prefill_commit")
                cache.set_pools(out[1:])
            with _tel.span("decode.prefill.fetch"):
                # the commit donates the pools only, so the logits are
                # still readable after it
                logits_host = (np.asarray(logits_raw, "float32")
                               if cache.prefix_sharing else None)
                first = np.asarray(out[0])
        return first, logits_host

    def launch(self, tokens, positions, tables, keys, steps, temps):
        """Hand one decode step to the device and return without waiting
        for it: the first half of :meth:`step`.  The batch is padded to a
        batch bucket (padded rows: position 0, all-trash table, any token).
        ``tokens`` is a host array, or the ``tokens`` of the
        :class:`StepFlight` an earlier launch returned: still a future on
        the device, it becomes this step's input unread, so a caller may
        launch step n+1 before it collects step n.  The page pools are
        donated and replaced by this step's (futures too: whatever is
        launched next is ordered behind it by the device)."""
        fn = self._step_fn(tokens.shape[0])
        # cpu_ms: in a .dispatch nothing waits for the device, so wall minus
        # CPU is the time this thread stood off the CPU while launching (the
        # runtime's blocking, the interpreter's lock)
        with _tel.span("decode.step.dispatch", cpu=True):
            if isinstance(tokens, np.ndarray):
                # placed like a step's own result, so that a program has
                # ONE signature whichever side its tokens come from
                tokens = self._place(tokens.astype("int32"))
            cache = self.cache
            pools = cache.pools
            n = len(pools)
            out = fn(
                self._params, tokens,
                positions.astype("int32"), tables.astype("int32"),
                keys.astype("uint32"), steps.astype("int32"),
                temps.astype("float32"), *pools)
            if _san.donation:
                # the step donated the page pools (see prefill above)
                _san.poison(list(pools), "decode.step")
            cache.set_pools(out[1:1 + n])
        return StepFlight(out[0], out[1 + n:])

    def collect(self, flight):
        """Wait for a launched step and return its sampled next token per
        row (host int32 array): the second half of :meth:`step`.  A
        program's failure surfaces here."""
        with _tel.span("decode.step.fetch"):
            nxt = np.asarray(flight.tokens)
        if flight.counts and _tel.enabled:
            # the block's counts: the program that made the tokens made them
            self._block.record_step_extras(np.asarray(flight.counts[0]),
                                           self.name)
        return nxt

    def step(self, tokens, positions, tables, keys, steps, temps):
        """One decode step, launched and collected: returns the sampled
        next token per row (host int32 array)."""
        with _tel.span("decode.step", model=self.name,
                       batch=tokens.shape[0]):
            return self.collect(self.launch(tokens, positions, tables, keys,
                                            steps, temps))

    def verify(self, tokens, positions, n_draft, tables, keys, steps,
               temps):
        """One fused speculative verify step for a batch padded to a
        batch bucket.  ``tokens (B, K+1)`` is ``[cur, d_1 .. d_K]`` per
        row (draft columns past ``n_draft`` padded with 0; rows that are
        not speculating this boundary ride with ``n_draft = 0`` — their
        result is bitwise the plain step's).  Returns host arrays
        ``(target (B, K+1) int32, n_acc (B,) int32)``: the target-model
        samples at every offset and the accepted-draft count — the row's
        emitted tokens are ``target[:n_acc + 1]``."""
        b, k1 = tokens.shape
        fn = self._verify_fn(b, k1 - 1)
        with _tel.span("decode.verify", model=self.name, batch=b,
                       k=k1 - 1):
            with _tel.span("decode.verify.dispatch"):
                cache = self.cache
                pools = cache.pools
                out = fn(
                    self._params, tokens.astype("int32"),
                    positions.astype("int32"), n_draft.astype("int32"),
                    tables.astype("int32"), keys.astype("uint32"),
                    steps.astype("int32"), temps.astype("float32"),
                    *pools)
                if _san.donation:
                    # the verify donated the page pools (see step above)
                    _san.poison(list(pools), "decode.verify")
                cache.set_pools(out[2:])
            with _tel.span("decode.verify.fetch"):
                target, n_acc = np.asarray(out[0]), np.asarray(out[1])
        return target, n_acc

    def sample_first(self, logits_row, key, temp):
        """Sample a prefix-hit admission's first token from the cached
        last-position logits — the batch-1 analog of the commit program's
        sampler.  ``sample_math`` is row-stable, so given the bitwise-
        identical logits row this returns the bitwise-identical token a
        cold prefill would have sampled (step index 0, same fold-in)."""
        if self._sample_fn is None:
            import jax
            self._sample_fn = jax.jit(self._block.sample_math)
        tok = self._sample_fn(
            np.asarray(logits_row, "float32")[None],
            np.asarray(key, "uint32")[None],
            np.zeros((1,), "int32"),
            np.asarray([temp], "float32"))
        return int(np.asarray(tok)[0])
