"""What the decode-block test files share (``test_window_moe_lm.py``,
``test_hybrid_moe_lm.py``, ``test_linear_moe_lm.py``, ``test_latent_moe.py``):
a tiny block built through the benchmark's system file around its plain
reference's seeded weights, a cache of one geometry, and the block's prefill,
commit and step compiled as the runtime compiles them.  Imported, never
collected.

A file states what differs in one :class:`Kit` (its reference, its system
file, its tolerances, its default seed) and builds through it; the rest are
plain functions::

    KIT = Kit(ref, system_mod, TOL)
    build = KIT.build

**What is built once.**  ``build`` keeps one ``(block, weights)`` for each
(system file, reference, configuration, seed, ``max_length``) and
``programs`` one ``(prefill, commit, step)`` for each (block, page format), so
a worker traces and compiles a block's programs once per argument shape and
not once a test.  The kept block and its weights are never changed: a test
that reads them (its parameters, its layout, its pure methods, its compiled
programs) takes the kept one.  A test that CHANGES its block asks for
``build(cfg, fresh=True)`` and gets a block no other test sees:

- the ``session`` fixtures, ``test_what_the_block_does_not_support_says_so``,
  the ``test_mesh_and_bad_*`` tests, ``test_runtime_*_from_the_block``,
  ``test_cache_builds_paged_and_slot_pools_from_the_layout`` (linear) and
  ``test_served_weights_are_held_once``: a ``DecodeRuntime`` hybridizes the
  block it is handed, in place, and binds its prefill's ``CachedOp`` to it;
- ``test_prompt_attention_by_query_blocks_is_the_whole_attention`` and
  ``test_the_block_chooses_the_share_by_its_rows`` (linear): they set
  ``attention_block`` / ``few_rows`` on the block.

``test_a_block_with_the_wrong_window_fails_the_reference`` (window) holds one
configuration's weights in another's block and builds it itself.  The
reference's controls (``window_off``, ``sink_off``, ``sinkhorn_off``,
``hc_static``, ``decay_off``, ``weights_fp8``, ...) are arguments of the
REFERENCE's forward over the kept weights and change no block.  Pools are
values: every ``new_cache`` is a new one, and no test sees another's.
"""
import dataclasses
import json

import numpy as np

import jax
import jax.numpy as jnp

from mxnet_tpu.serving.decode import PagedKVCache
from perf.harness.weights import seed_key

#: every cache of these tests: pages of 8 tokens, 8 to a sequence, 24 in all
PAGE = 8
MAX_PAGES = 8

_BLOCKS = {}
_PROGRAMS = {}


def relative_errors(got, want):
    """The largest error of each position, as a share of the largest
    logit."""
    return np.abs(got - want).max(1) / np.abs(want).max()


def new_cache(net, max_slots=4):
    return PagedKVCache(layout=net.cache_layout(), page_size=PAGE,
                        num_pages=24, max_pages_per_seq=MAX_PAGES,
                        max_slots=max_slots)


def table_row(pages, slot_row=None):
    """A row of a program's ``tables``: the physical pages and, for a
    block that keeps per-sequence state, the state row behind them."""
    row = np.zeros((MAX_PAGES + (slot_row is not None),), "int32")
    row[:len(pages)] = pages
    if slot_row is not None:
        row[-1] = slot_row
    return row


def programs(net, pages):
    """The block's prefill, commit and step as the runtime runs them:
    compiled, the cache's page format closed over; one triple a block
    and format.  (Run op by op, the conditionals of the products by
    expert are traced anew at every call and a test takes minutes.)"""
    key = (net, pages.kv_dtype, pages.page_size)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (
            jax.jit(net.prefill_math),
            jax.jit(lambda *a: net.commit_program(*a, pages)),
            jax.jit(lambda *a: net.step_program(*a, pages)))
    return _PROGRAMS[key]


def decode_logits(net, tokens, n_prompt, pages, slot_row=None, batch=1,
                  row=0, seq_pad=16, cache=None, pools=None):
    """Prefill ``tokens[:n_prompt]`` (padded to ``seq_pad``) and decode
    the rest, in row ``row`` of a batch of ``batch`` (the other rows are
    padding) with the paged rows in physical ``pages`` and the
    per-sequence state, where the block keeps one, in state row
    ``slot_row``: logits of positions ``n_prompt - 1 .. len(tokens) -
    1``, the last step's counts, and the pools as the last step left
    them."""
    p = net._params_dict(net.param_leaves())
    if cache is None:
        cache = new_cache(net)
        pools = cache.pools
    prefill, commit, step = programs(net, cache.pages)
    table = table_row(pages, slot_row)[None]
    prompt = np.zeros((1, seq_pad), "int32")
    prompt[0, :n_prompt] = tokens[:n_prompt]
    lengths = jnp.asarray([n_prompt], "int32")
    logits, *state = prefill(p, jnp.asarray(prompt), lengths)
    # a block with one array of cache rows hands it over bare
    state = state[0] if len(state) == 1 else tuple(state)
    pools = commit(state, lengths, jnp.asarray(table), pools)
    out = [np.asarray(logits[0])]
    tables = np.zeros((batch, table.shape[1]), "int32")
    tables[row] = table[0]
    extras = None
    for t in range(n_prompt, len(tokens)):
        tok = np.zeros((batch,), "int32")
        pos = np.zeros((batch,), "int32")
        tok[row], pos[row] = tokens[t], t
        logits, pools, extras = step(
            p, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables),
            pools)
        out.append(np.asarray(logits[row]))
    return np.stack(out), extras, pools


@dataclasses.dataclass(frozen=True)
class Kit:
    """One file's constants: the plain reference (``weights(cfg, key)``),
    the system file (``block(cfg, max_length, weights, device)``), the
    tolerance a dtype and the default seed."""
    ref: object
    system: object
    tol: dict
    seed: int = 8

    def build(self, cfg, seed=None, max_length=64, fresh=False):
        """``(block, reference weights)``: the block holds the reference's
        own seeded tensors, loaded as the benchmark's system file loads
        them.  The kept pair of its key, or with ``fresh`` a new one that is
        the caller's to change (module docstring)."""
        seed = self.seed if seed is None else seed
        key = (self.system.__name__, self.ref.__name__,
               json.dumps(cfg, sort_keys=True), seed, max_length)
        if not fresh and key in _BLOCKS:
            return _BLOCKS[key]
        w = self.ref.weights(cfg, seed_key(seed, stream=1))
        # a loader may empty what it is handed: a copy of the table, not of
        # the arrays
        made = self.system.block(cfg, max_length, dict(w),
                                 jax.devices()[0]), w
        if not fresh:
            _BLOCKS[key] = made
        return made

    def assert_close(self, got, want, dtype):
        """Every position within the dtype's tolerance; in bfloat16, but
        for the one position in ten that an expert choice's flip may move
        (``test_window_moe_lm.py``'s docstring)."""
        assert np.abs(want).max() > 0.5     # logits of order 1, not zeros
        err = relative_errors(got, want)
        allowed = 0 if dtype == "float32" else -(-len(err) // 10)
        assert (err > self.tol[dtype]).sum() <= allowed, err
        assert np.median(err) <= self.tol[dtype] / 2
