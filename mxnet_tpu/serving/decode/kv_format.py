"""The KV page format: what a page pool stores, and the only doors (three,
and a fourth for a block whose slots hold rings) that reach into one.

A cache's attention state is a tuple of device arrays, the *pools*, each
``(layers, num_pages, page_size, row width)`` (the row by heads, ``(heads,
head_dim)``, where the layout states a ``row_shape``): the value pools the
block's ``cache_layout()`` states, in its order, then the format's float32
*sidecars* ``(layers, num_pages, page_size)``, each value pool's side by
side: ``(k, v)`` raw, ``(k, v, k_scale, k_mid, v_scale, v_mid)`` in int8,
``(k, v, k_scale, v_scale)`` in fp8_e4m3, ``(latent,)`` for a
latent-attention block.  Every program threads the whole tuple through
and donates it; only :class:`PageFormat` knows which array is what.

**Three doors.**  :meth:`PageFormat.write` stores token rows at ``(page,
offset)``; :meth:`PageFormat.read` gathers a layer's whole paged context
through page tables, every RESERVED page of every row of the program,
dequantized where the format is quantized; :meth:`PageFormat.attend` (raw
pools only) is one decode step's attention of one query token a row over the
pools where they lie: lowered for the chip, one Pallas kernel that brings
each live row's LIVE pages from the pool, once, and nothing for a padded row
or a reserved page that holds no token yet; lowered for the CPU, ``read``
and the block's own attention over the gathered context.  Which kernel is
what the format observes of its pools: K and V pools are grouped-query
attention (``ops.pallas_kernels.paged_attention``), ONE pool is a latent
row that is keys and values at once (``paged_latent_attention``).
``WindowMoELM``'s global layers, ``HybridSSMMoELM``'s attention layers,
``LinearMoELM``'s gated grouped-query layers and every layer of
``LatentMoELM`` step through ``attend``; their prefills' commits through
``write``.  One block stays on ``read``, and shares nothing with the
kernels: ``CausalLM`` (float32 K/V through ``rowdot`` under the row-stable
and shared-vs-cold bitwise contracts, and int8 / fp8 pools, whose
dequantization is ``read``'s).

Three formats, chosen by ``kv_dtype``:

- **raw** (``"float32"``, the default): each pool in the dtype the block
  states (float32 K/V, a bfloat16 latent row); no sidecars.
- **int8**: affine codes with a per-token-row ``(scale, mid)`` pair
  (:func:`kv_quantize_rows`); about a quarter of the float32 bytes.
- **fp8_e4m3**: e4m3 codes with a per-token-row absmax ``scale``
  (:func:`kv_quantize_rows_fp8`; the sign bit and mantissa make a midpoint
  unnecessary).

Quantization happens at :meth:`PageFormat.write` and dequantization at
:meth:`PageFormat.read`, inside whichever commit, step or verify program
calls them, so a format costs no program of its own.  Both are elementwise
per token row, so the row-stable contract (``model.py``) and the
shared-vs-cold bitwise contract of prefix sharing hold in every format;
what a quantized format relaxes is fidelity *versus the raw pools*
(``docs/serving.md``).

**The pool's minor axis is the whole token row** (``heads * head_dim``
values), not ``head_dim``.  A TPU array lives in (8, 128) tiles of its two
minor dimensions; with ``head_dim`` = 64 minor-most the client pads the
resident pool and stores it pages-minor, a layout no scatter or gather
computes in, so every program that took the donated pools began and ended
with a copy of each whole pool.  A row that fills whole lane tiles is
resident unpadded in the layout the programs compute in, and they update
the donated buffers in place.  **A pool is indexed once**: ``pool[layer,
tables]`` / ``pool.at[layer, pages, offsets]``, never
``pool[layer][tables]`` — the chained form materialises all ``num_pages``
pages of the layer before gathering a row's few.
``tests/test_chip_compile.py`` holds every program (step, verify, commit,
copy-on-write; each format) to this: no temporary the size of a pool or of
one layer of one.

**State that is per sequence, not per token** (a state-space layer's
recurrent state, its convolution's tail; a sliding-window layer's ring of
its last ``window`` tokens' keys and values, which is bounded whatever the
context) lives beside the pages in *state
pools* ``(state layers, max_slots + 1) + shape``, one row a slot and row 0
the trash slot, as page 0 is the trash page.  A block whose
``cache_layout()`` has a ``state`` section gets them behind the page pools
in the same donated tuple, and :class:`SlotState` (``pages.state``) is
their only ``read`` and ``write``, and the one door (``in_place``) through
which a kernel gets a whole state pool to WRITE.  A sequence's row of
``tables`` then ends with its state row (:meth:`PageFormat.addresses`).
:meth:`PageFormat.attend_window` is the fourth door: rings and pages read.
"""
from __future__ import annotations

import math

__all__ = ["PageFormat", "SlotState", "kv_quantize_rows", "kv_dequantize",
           "kv_quantize_rows_fp8", "kv_dequantize_fp8"]


def kv_quantize_rows(x):
    """Affine int8 quantization of K/V token rows ``x (..., H, D)`` —
    one ``(scale, mid)`` pair per leading index, reduced over the last
    two axes only.  Returns ``(q int8, scale, mid)`` with
    ``scale/mid`` of shape ``x.shape[:-2]``.

    The reduction never crosses a leading axis, so quantization is
    *row-stable* exactly like ``rowdot``: a token row's int8 codes are
    a pure elementwise function of that row's fp32 values, independent of
    batch composition, seq bucket, or physical page — which is why the
    shared-vs-cold bitwise contract survives int8 pools.  An all-zero row
    (the trash page, uninitialized pool entries) maps to
    ``scale = mid = 0`` and dequantizes to exact ``0.0``."""
    import jax.numpy as jnp
    lo = x.min(axis=(-2, -1))
    hi = x.max(axis=(-2, -1))
    scale = (hi - lo) / 254.0
    mid = (hi + lo) * 0.5
    q = jnp.round((x - mid[..., None, None])
                  / jnp.where(scale > 0, scale, 1.0)[..., None, None])
    return jnp.clip(q, -127.0, 127.0).astype("int8"), scale, mid


def kv_dequantize(q, scale, mid):
    """Inverse of :func:`kv_quantize_rows` — elementwise, row-stable:
    ``q * scale + mid`` broadcast over the trailing ``(H, D)`` axes."""
    return (q.astype("float32") * scale[..., None, None]
            + mid[..., None, None])


def kv_quantize_rows_fp8(x):
    """fp8 (e4m3) quantization of K/V token rows ``x (..., H, D)`` —
    per-row *scale only* (e4m3 keeps a sign bit and enough mantissa that
    a symmetric absmax scale suffices; no ``mid``), reduced over the last
    two axes.  Returns ``(q float8_e4m3fn, scale)`` with ``scale`` of
    shape ``x.shape[:-2]``.  Row-stable like :func:`kv_quantize_rows`;
    an all-zero row maps to ``scale = 0`` and dequantizes to exact 0."""
    import jax.numpy as jnp
    amax = jnp.abs(x).max(axis=(-2, -1))
    scale = amax / 448.0                 # e4m3fn finite max
    q = x / jnp.where(scale > 0, scale, 1.0)[..., None, None]
    return q.astype(jnp.float8_e4m3fn), scale


def kv_dequantize_fp8(q, scale):
    """Inverse of :func:`kv_quantize_rows_fp8` — ``q * scale`` broadcast
    over the trailing ``(H, D)`` axes."""
    return q.astype("float32") * scale[..., None, None]


_ALIASES = {"fp32": "float32", "float": "float32",
            "fp8": "fp8_e4m3", "float8_e4m3fn": "fp8_e4m3"}

#: kv_dtype -> (stored dtype, rows -> (codes, *sidecars), its inverse,
#: sidecars a value pool); None: raw
_CODECS = {
    "float32": None,
    "int8": ("int8", kv_quantize_rows, kv_dequantize, 2),
    "fp8_e4m3": ("float8_e4m3fn", kv_quantize_rows_fp8, kv_dequantize_fp8,
                 1),
}


class SlotState:
    """The state pools of one cache: what a block's ``cache_layout()``
    states under ``state`` (``layers``: how many layers keep such state;
    ``arrays``: ``(name, shape, dtype)`` of each array a layer keeps for one
    sequence), stored ``(layers, max_slots + 1) + shape`` behind the
    ``first`` page pools of the cache's tuple.  Row ``slot_id + 1`` is a
    slot's; row 0 takes the writes of padded batch rows.  A layer here is
    the block's count among its state layers, not its depth.

    ``read`` and ``write`` move rows' state out of a pool and into it (a
    prefill's commit, the convolution's tail, the CPU's form of the step);
    ``write_at`` updates one entry of a row's array (the newest token of a
    window layer's ring); ``in_place`` hands a kernel the pool itself, and in a step program
    built for the chip that kernel is the one reader and writer of the
    recurrent state."""

    def __init__(self, spec, first):
        import jax.numpy as jnp
        self.num_layers = int(spec["layers"])
        self.arrays = tuple((str(n), tuple(int(d) for d in shape),
                             jnp.dtype(dt)) for n, shape, dt
                            in spec["arrays"])
        self.first = int(first)
        #: device bytes one slot's state costs (every array, all layers)
        self.bytes_per_slot = self.num_layers * sum(
            math.prod(shape) * dt.itemsize for _n, shape, dt in self.arrays)

    def new_pools(self, max_slots):
        """Zeroed state pools for ``max_slots`` slots and the trash row."""
        import jax.numpy as jnp
        return tuple(jnp.zeros((self.num_layers, int(max_slots) + 1) + shape,
                               dt) for _n, shape, dt in self.arrays)

    def _which(self, names):
        every = [n for n, _s, _d in self.arrays]
        return range(len(every)) if names is None \
            else [every.index(n) for n in names]

    def read(self, pools, layer, rows, names=None):
        """One state layer's arrays (all, or those of ``names``) for the
        state rows ``rows (B,)``: a tuple, each ``(B,) + shape``.  Indexed
        once, as a page pool is."""
        return tuple(pools[self.first + j][layer, rows]
                     for j in self._which(names))

    def read_all(self, pools, layer, names=None):
        """One state layer's arrays for EVERY state row, the trash row
        first: a tuple, each ``(max_slots + 1,) + shape``.  No program reads
        a layer whole since the step's recurrence became a kernel; kept
        because the benchmark's ``tests/perf/test_nemotron3_cell.py``
        patches it by name (``PERF.md`` section 7)."""
        return tuple(pools[self.first + j][layer]
                     for j in self._which(names))

    def in_place(self, pools, layer, rows, name, fn):
        """Hand the WHOLE pool of the state array ``name`` to ``fn(pool,
        layer, rows) -> (pool, out)``, a kernel that finds ``rows``' state
        of ``layer`` where it lies and gives the pool back in the buffer it
        came in (``ops.pallas_kernels.ssm_step_slots``, ``kda_step_slots``);
        returns ``(pools, out)``.  Never a slice: ``pool[layer]`` handed to a custom call is a
        copy of every slot's state in, and another out."""
        (j,) = self._which((name,))
        pools = list(pools)
        pools[self.first + j], out = fn(pools[self.first + j], layer, rows)
        return tuple(pools), out

    def write(self, pools, layer, rows, values, names=None):
        """Store ``values`` (one array a state array, or a named one,
        ``(B,) + shape``) as the whole of that state of ``rows`` in one
        layer; returns the pools."""
        pools = list(pools)
        for j, x in zip(self._which(names), values):
            k = self.first + j
            pools[k] = pools[k].at[layer, rows].set(x.astype(pools[k].dtype))
        return tuple(pools)

    def write_at(self, pools, layer, rows, index, values, names=None):
        """Store ``values`` (``(B,) + shape[1:]``) as entry ``index (B,)``
        along the first axis of that state of ``rows`` in one layer, where
        it lies: ONE token's keys and values in a window layer's ring, the
        rest of the ring untouched.  Indexed once, as :meth:`write` is."""
        pools = list(pools)
        for j, x in zip(self._which(names), values):
            k = self.first + j
            pools[k] = pools[k].at[layer, rows, index].set(
                x.astype(pools[k].dtype))
        return tuple(pools)


class PageFormat:
    """How one cache's pages are stored; see the module docstring.

    Built by :class:`~mxnet_tpu.serving.decode.kv_cache.PagedKVCache` from
    the block's ``cache_layout()`` and ``kv_dtype``; the commit, step and
    verify programs of a block receive it as ``pages`` and reach the pools
    only through ``write``, ``read``, ``attend`` and ``attend_window``."""

    def __init__(self, layout, kv_dtype=None, page_size=16):
        import jax.numpy as jnp
        kv_dtype = _ALIASES.get(str(kv_dtype), str(kv_dtype)) \
            if kv_dtype is not None else "float32"
        if kv_dtype not in _CODECS:
            raise ValueError(
                f"kv_dtype must be 'float32', 'int8' or 'fp8_e4m3', "
                f"got {kv_dtype!r}")
        self.pool_layout = tuple((str(n), int(w), str(d))
                                 for n, w, d in layout["pools"])
        codec = self._codec = _CODECS[kv_dtype]
        if codec and not layout.get("quantizable"):
            raise ValueError(
                f"kv_dtype={kv_dtype!r}: the block's pools "
                f"{[n for n, _w, _d in self.pool_layout]} are stored as the "
                f"block states them; an int8/fp8 pool of these rows is not "
                f"supported")
        self.kv_dtype = kv_dtype
        self.quantized = codec is not None
        #: ROWS a page holds; a row stands for ``row_tokens`` tokens, so a
        #: page for ``page_tokens`` of them
        self.page_size = int(page_size)
        self.page_tokens = self.tokens_a_page(layout, page_size)
        self.row_tokens = self.page_tokens // self.page_size
        self.num_layers = int(layout["layers"])
        self._per = codec[3] if codec else 0
        self.num_sidecars = self._per * len(self.pool_layout)
        self._stored = tuple(jnp.dtype(codec[0] if codec else d)
                             for _n, _w, d in self.pool_layout)
        #: device bytes one row costs across every pool (all layers),
        #: sidecars included, and one token position's part of it
        self.row_bytes = self.num_layers * sum(
            w * dt.itemsize + 4 * self._per
            for (_n, w, _d), dt in zip(self.pool_layout, self._stored))
        self.kv_bytes_per_token = self.row_bytes // self.row_tokens
        # a row is stored flat, ``(width,)``, unless the layout states a
        # ``row_shape`` for its pools (``(heads, head_dim)``: the chip tiles
        # an array's two minor axes, and a flat row split into heads after
        # the gather is a copy of everything gathered)
        shape = tuple(int(d) for d in layout.get("row_shape") or ())
        if shape and any(math.prod(shape) != w
                         for _n, w, _d in self.pool_layout):
            raise ValueError(
                f"row_shape={shape} is not the {self.pool_layout} rows'")
        self._stored_rows = tuple(shape or (w,)
                                  for _n, w, _d in self.pool_layout)
        # a flat row that is a concatenation of heads reads back as (heads,
        # head_dim): the axes a quantized format reduces over
        heads = layout.get("shard_heads")
        self._row_shapes = tuple(
            (heads, w // heads) if heads else stored
            for (_n, w, _d), stored in zip(self.pool_layout,
                                           self._stored_rows))
        #: the per-sequence state pools behind the page pools, or None
        self.state = SlotState(
            layout["state"], len(self.pool_layout) + self.num_sidecars) \
            if layout.get("state") else None

    @staticmethod
    def tokens_a_page(layout, page_size):
        """Tokens a page of ``page_size`` rows stands for under a block's
        ``layout``: a row is one token's, or, where the layout states
        ``row_tokens`` (a chunk summary: one row for every ``chunk_size``
        tokens), that many tokens'.  The ONE place that reads the key; the
        cache's reservations, the scheduler's page index of a position and
        the runtime's context all go through it."""
        return int(page_size) * int(layout.get("row_tokens", 1))

    def addresses(self, tables):
        """``(page tables (B, pages a row), state rows (B,) or None)`` of a
        program's ``tables``: with state pools a row's last entry is its
        state row."""
        if self.state is None:
            return tables, None
        return tables[:, :-1], tables[:, -1]

    def new_pools(self, num_pages):
        """Zeroed pools of ``num_pages`` pages: the value pools, then the
        sidecars.  An all-zero row reads back as exact zeros in every
        format (the trash page, pages never written)."""
        import jax.numpy as jnp
        shape = (self.num_layers, int(num_pages), self.page_size)
        return tuple(jnp.zeros(shape + row, dt) for row, dt
                     in zip(self._stored_rows, self._stored)) + \
            tuple(jnp.zeros(shape, "float32")
                  for _ in range(self.num_sidecars))

    def write(self, pools, layer, page, offset, rows):
        """Store one layer's new token rows at ``(page, offset)`` and return
        the updated pools.  ``rows`` holds one array a value pool, each with
        the leading axes of ``page`` / ``offset`` (``(B,)`` for a step,
        ``(B, K+1)`` for a verify, ``(B, S)`` for a prefill's commit) and
        the row behind them, flat or as ``(heads, head_dim)`` — the axes a
        quantized format reduces over before the row is flattened."""
        pools = list(pools)
        n = len(self.pool_layout)

        def put(j, x):
            pools[j] = pools[j].at[layer, page, offset].set(x)

        def flat(j, x):
            return x.reshape(page.shape + self._stored_rows[j])

        if self._codec is None:
            for j, x in enumerate(rows):
                put(j, flat(j, x).astype(pools[j].dtype))
            return tuple(pools)
        coded = [self._codec[1](x) for x in rows]
        for j, c in enumerate(coded):
            put(j, flat(j, c[0]))
        for j, c in enumerate(coded):
            for s, side in enumerate(c[1:]):
                put(n + j * self._per + s, side)
        return tuple(pools)

    def read(self, pools, layer, tables):
        """Gather one layer's whole paged context for every row of
        ``tables (B, pages a row)``: one array a value pool, ``(B, pages a
        row * page_size) + row`` (a row of heads as ``(heads, head_dim)``),
        dequantized to float32 where the format is quantized.  Each pool is
        indexed ONCE (the module docstring says why)."""
        n = len(self.pool_layout)
        lead = (tables.shape[0], tables.shape[1] * self.page_size)

        def gather(j, row=()):
            return pools[j][layer, tables].reshape(lead + row)

        if self._codec is None:
            return tuple(gather(j, row)
                         for j, row in enumerate(self._row_shapes))
        return tuple(
            self._codec[2](gather(j, row),
                           *(gather(n + j * self._per + s)
                             for s in range(self._per)))
            for j, row in enumerate(self._row_shapes))

    def attend(self, pools, layer, tables, positions, q, plain, scale=None):
        """Attention of ONE query token a row at ``positions (B,)`` over the
        row's tokens ``0 .. position`` of one layer (the token this step
        wrote among them: hand over the pools :meth:`write` returned).  Raw
        pools only, and what they are decides the form:

        - **K and V pools**: grouped-query attention, ``q (B, g, r, dk)``
          float32, the scale ``dk ** -0.5``; returns the heads' outputs side
          by side, ``(B, g * r * dv)`` float32.  ``plain(k, v, mask)``.
        - **one pool** (a latent row, keys and values at once and shared by
          every head): ``q (B, heads, row width)`` float32, folded into the
          row's space by the block, the softmax ``scale`` the block's to
          give; returns the context ``(B, heads, row width)`` float32, of
          which the block keeps the columns that are values.  ``plain(rows,
          mask)``.

        Where the program is lowered for the chip this is ONE kernel
        (``ops.pallas_kernels.paged_attention`` / ``paged_latent_attention``)
        that reads the live rows' LIVE pages out of the whole pools where
        they lie and nothing else: no page past a row's position, nothing
        of a padded row.  Where it is lowered for the CPU it is :meth:`read`
        and the block's own attention over the gathered context,
        ``plain(*gathered, mask (B, 1, reserved context))``: what every test
        on the CPU and the plain references read.  The counter
        ``decode.attn.paged.lowered`` (``kind="kernel" | "plain"``) says
        which was lowered; nothing a caller sets chooses."""
        import jax.numpy as jnp
        from ...ops.pallas_kernels import (by_platform, paged_attention,
                                           paged_latent_attention)
        n = len(self.pool_layout)
        if self._codec is not None or n not in (1, 2):
            raise ValueError(
                f"attend reads raw K and V pools or one raw pool that is "
                f"both, not {self.kv_dtype} pools of "
                f"{[name for name, _w, _d in self.pool_layout]}")
        if (scale is None) != (n == 2):
            raise ValueError(
                "the softmax scale is the key width's over K and V pools "
                "and the block's to give over one pool")

        def kernel(*value_pools):
            if n == 2:
                return paged_attention(q, *value_pools, layer, tables,
                                       positions).reshape(q.shape[0], -1)
            return paged_latent_attention(q, *value_pools, layer, tables,
                                          positions, scale=scale)

        def gathered(*value_pools):
            context = self.read(value_pools, layer, tables)
            reserved = jnp.arange(tables.shape[1] * self.page_size)
            return plain(*context,
                         (reserved[None, :] <= positions[:, None])[:, None])

        return by_platform("decode.attn.paged.lowered", *pools[:n],
                           kernel=kernel, plain=gathered, rows=q.shape[0])

    def attend_window(self, pools, layer, tables, rows, positions, q, plain):
        """Attention of ONE query token a row at ``positions (B,)`` for a
        block that keeps the OPEN window's exact keys and values in a ring a
        slot (the layout's ``state``: two arrays ``(window,) + row``) and,
        in its K and V pages, one row for every ``row_tokens`` positions of
        the windows before: ONE softmax over the ring's entries ``0 ..
        position mod window`` of state row ``rows (B,)`` and the page rows
        ``0 .. position // window * (window // row_tokens) - 1`` of
        ``tables (B, pages a row)`` (hand over the pools that the step's
        ``state.write_at`` and :meth:`write` returned).  ``q (B, heads,
        head_dim)`` float32; returns ``(B, heads, head_dim)`` float32.  The
        window and ``row_tokens`` are the layout's; nothing is an argument.

        Where the program is lowered for the chip this is ONE kernel
        (``ops.pallas_kernels.eva_attention``) that reads the live rows'
        live ring blocks and live pages out of the whole pools where they
        lie and nothing else.  Where it is lowered for the CPU it is the
        definition: a row's whole ring (indexed out of the pool once, as
        ``state.read`` does), :meth:`read` of the reserved pages and the
        block's own ``plain(q (heads, head_dim),
        ring_k, ring_v, live (window,), summary keys, summary values, seen
        (reserved rows,))`` a row.  The counter ``decode.attn.eva.lowered``
        (``kind="kernel" | "plain"``) says which was lowered; nothing a
        caller sets chooses."""
        import jax.numpy as jnp
        from ...ops.pallas_kernels import by_platform, eva_attention
        n, state = len(self.pool_layout), self.state
        rings = [shape for _n, shape, _d in state.arrays] if state else []
        if self._codec is not None or n != 2 or len(rings) != 2 or any(
                ring[1:] != row or ring[0] % self.row_tokens
                for ring, row in zip(rings, self._stored_rows)):
            raise ValueError(
                f"attend_window reads raw K and V pools of rows by head and "
                f"a slot's two rings of such rows, a window of whole rows' "
                f"tokens; not {self.kv_dtype} pools of {self.pool_layout} "
                f"with the state {state and state.arrays}")
        window = rings[0][0]

        def kernel(*arrays):
            return eva_attention(q, *arrays, layer, tables, rows, positions,
                                 row_tokens=self.row_tokens)

        def gathered(*arrays):
            went = positions % window
            closed = positions // window * (window // self.row_tokens)
            live = jnp.arange(window)[None, :] <= went[:, None]
            sk, sv = self.read(arrays[:n], layer, tables)
            seen = jnp.arange(sk.shape[1])[None, :] < closed[:, None]
            return jnp.stack([
                plain(q[b], *(ring[layer, rows[b]] for ring in arrays[n:]),
                      live[b], sk[b], sv[b], seen[b])
                for b in range(q.shape[0])])

        return by_platform(
            "decode.attn.eva.lowered", *pools[:n],
            *pools[state.first:state.first + 2], kernel=kernel,
            plain=gathered, rows=q.shape[0])
