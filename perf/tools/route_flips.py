"""How many tokens of a latent-attention / routed-expert cell get an expert
choice that the plain reference does not make: a builder's tool, one
process on the chip.

    python perf/tools/route_flips.py --workload axk1_ep16.assist_steady \
        --seed 2600000031 --sequences 24

The router's scores are float32 in the program and in the reference alike,
but the state they are computed from carries the program's bfloat16
products, so where a token's 8th and 9th score lie closer than that noise
the two choose differently.  The tool takes the first ``--sequences``
requests of the cell's design that fit ``--pad`` tokens (the prompt's ids
as the design draws them, the answer's ids drawn from the seed: the weights
are random, so a drawn id is as good as a served one), runs the program's
block over each whole sequence in its serving precision (the expanded
attention of its prefill; the decode step's absorbed form differs from it
by the same bfloat16 noise) and the reference in float32, and compares the
sets of chosen experts at the positions that score an answer token, layer
by layer.  One ``FLIPS`` line of JSON.

Reaches into the program past its public entry points: ``LatentMoELM``'s
pure-math methods (``attend_expanded``, ``_ffn``) and the module's ``_rms``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def program_choices(net):
    """A jitted ``(leaves, tokens (1, S), length) -> ids (expert layers, S,
    top_k)``: the experts the block's own arithmetic chooses."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import group_limited_topk
    from mxnet_tpu.serving.decode.latent_moe import _rms

    def run(leaves, tokens, length):
        p = net._params_dict(leaves)
        B, S = tokens.shape
        h = p["embed"][tokens].astype(jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        causal = jnp.tril(jnp.ones((S, S), bool))
        valid = (pos < length).reshape(-1)
        chosen = []
        for i in range(net.num_layers):
            a = _rms(h, p[f"l{i}_norm_attn"], net.eps)
            o, _rows = net.attend_expanded(p, i, a, pos, causal)
            flat = (h + o).reshape(B * S, -1)
            if i in net.moe_layers:
                m = _rms(flat, p[f"l{i}_norm_ffn"], net.eps)
                scores = jax.nn.sigmoid(jnp.dot(
                    m, p[f"l{i}_router"],
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32))
                chosen.append(group_limited_topk(
                    scores, net.top_k, net.n_group, net.topk_group)[0])
            h = net._ffn(p, i, flat, valid, []).reshape(B, S, -1)
        return jnp.stack(chosen)

    return jax.jit(run)


def reference_choices(reference, w, cfg, tokens):
    """``ids (expert layers, T, top_k)`` of the plain reference over one
    sequence ``tokens (T,)``."""
    import jax
    import jax.numpy as jnp
    key, eps = reference._freeze(cfg), cfg["rms_norm_eps"]
    chosen = []
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens"][tokens].astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        for i in range(cfg["n_layer"]):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            h = reference._attention(lw, h, pos, cfg_key=key,
                                     precision="float32")
            if i < cfg["first_k_dense_replace"]:
                h = reference._dense_ffn(lw, h, eps=eps, precision="float32")
            else:
                chosen.append(reference._route_and_shared(
                    lw, h, cfg_key=key, precision="float32")[1])
                h = reference._moe_ffn(lw, h, cfg_key=key,
                                       precision="float32")
    return jnp.stack(chosen)


def count_flips(cfg, traffic, system_mod, seed, seconds, sequences, pad,
                device):
    """The ``FLIPS`` record of the module docstring."""
    import numpy as np
    import jax.numpy as jnp
    from perf.harness import traffic as traffic_mod
    from perf.harness.weights import host_rng
    from perf.reference import axk1 as reference

    w = system_mod.weights(cfg, seed, device)
    net = system_mod.block(cfg, pad, w, device)
    choose = program_choices(net)
    leaves = net.param_leaves()
    held = np.asarray(cfg["held_experts"])
    rng = host_rng(seed, stream=14)
    out = {"sequences": 0, "answer_positions": 0, "layer_choices": 0,
           "layer_choices_flipped": 0, "positions_flipped": 0,
           "positions_flipped_at_a_held_expert": 0}
    for req in traffic_mod.design(traffic, seconds, seed, cfg["vocab_size"]):
        n = len(req["prompt"]) + req["max_new_tokens"]
        if n > pad:
            continue
        seq = np.zeros((pad,), "int32")
        seq[:len(req["prompt"])] = req["prompt"]
        seq[len(req["prompt"]):n] = rng.integers(
            0, cfg["vocab_size"], req["max_new_tokens"])
        mine = np.sort(np.asarray(choose(
            leaves, jnp.asarray(seq[None]), jnp.int32(n))), -1)
        ref = np.sort(np.asarray(reference_choices(
            reference, w, cfg, jnp.asarray(seq))), -1)
        # the rows that score an answer token, as served_token_gaps has them
        lo, hi = len(req["prompt"]) - 1, n - 1
        differs = (mine != ref).any(-1)[:, lo:hi]          # (layers, pos)
        at_held = np.zeros_like(differs)
        for layer, t in zip(*np.nonzero(differs)):
            moved = np.setxor1d(mine[layer, lo + t], ref[layer, lo + t])
            at_held[layer, t] = np.isin(moved, held).any()
        out["sequences"] += 1
        out["answer_positions"] += hi - lo
        out["layer_choices"] += differs.size
        out["layer_choices_flipped"] += int(differs.sum())
        out["positions_flipped"] += int(differs.any(0).sum())
        out["positions_flipped_at_a_held_expert"] += int(at_held.any(0).sum())
        if out["sequences"] == sequences:
            break
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--sequences", type=int, default=24)
    ap.add_argument("--pad", type=int, default=1024)
    args = ap.parse_args()
    import importlib
    from mxnet_tpu.runtime import compile_cache
    from perf.harness import device as device_mod
    from perf.harness.spec import Cell
    cell = Cell(args.workload)
    devices = device_mod.require_chips(cell.chips)
    compile_cache()
    system_mod = importlib.import_module(
        "perf.systems." + cell.traffic["system"])
    out = count_flips(cell.config, cell.traffic, system_mod, args.seed,
                      args.seconds, args.sequences, args.pad, devices[0])
    print("FLIPS " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
