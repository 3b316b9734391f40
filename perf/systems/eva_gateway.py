"""The system under test for byte-level EVA-attention serving cells (EvaByte):
``serving.decode.EvaLM`` in a ``DecodeSession`` behind
``serving.gateway.Gateway`` over HTTP/SSE on localhost, in the run's own
process — the same gateway, scheduler, runtime and cache the other serving
cells use, handed the benchmark's weights the way a deployment loads a
checkpoint.

The weights are made one tensor at a time from the seed's key by the plain
reference's own table (``perf/reference/evabyte.py`` ``weights``); the program
is given those arrays and keeps them (no copy), and the reference makes them
again after the window.  The one reach past the program's public entry points
is ``Parameter._load_init``.
"""
import os

# at import, before any weight is made: a program without the block (the
# parent of the PR that added it) fails here, in seconds
from mxnet_tpu.serving.decode import EvaLM

from ..harness.weights import seed_key
from ..reference import evabyte as reference
from .decode_gateway import DecodeGateway


def weights(cfg, seed, device=None):
    """The run's seeded weights by the benchmark's (published) names."""
    return reference.weights(cfg, seed_key(seed, stream=1), device)


def reference_gaps(cfg, traffic, seed, prompts, served, device,
                   precisions=("float32",)):
    """As ``decode_gateway.reference_gaps``: per precision ``{"max", "mean",
    "moved"}`` of the served bytes' head-0 logit gaps under the plain
    reference (for a lower precision or a broken mechanism: of the bytes that
    it puts first), and the number of bytes compared."""
    w = weights(cfg, seed, device)
    every = {p: reference.served_token_gaps(
        w, cfg, prompts, served, traffic["check"]["pad_to"],
        precision=p).tolist() for p in precisions}
    out = {p: {"max": max(g, default=0.0),
               "mean": sum(g) / max(len(g), 1),
               "moved": sum(x > 0 for x in g)} for p, g in every.items()}
    return out, len(every[precisions[0]])


_LAYER = {"input_layernorm": "norm_attn", "self_attn.q_proj": "wq",
          "self_attn.k_proj": "wk", "self_attn.v_proj": "wv",
          "self_attn.o_proj": "wo", "self_attn.adaptive_phi": "phi",
          "self_attn.adaptive_mu_k": "mu",
          "post_attention_layernorm": "norm_ffn",
          "mlp.gate_proj": "wg", "mlp.up_proj": "wu", "mlp.down_proj": "wd"}


def program_name(name):
    """Benchmark tensor name -> ``EvaLM`` parameter name."""
    fixed = {"model.embed_tokens": "embed", "lm_head": "head",
             "model.norm": "norm_f"}
    if name in fixed:
        return fixed[name]
    _model, _layers, i, rest = name.split(".", 3)
    return f"l{i}_{_LAYER[rest]}"


def block(cfg, context_tokens, weights, device):
    """``EvaLM`` at the configuration's sizes, holding ``weights`` (the very
    arrays: the checkpoint-load path, no host initialiser and no copy)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads={cfg['num_key_value_heads']} differs from "
            f"num_attention_heads={cfg['num_attention_heads']}: not a block "
            f"EvaLM builds")
    net = EvaLM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["n_layer"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        window_size=cfg["window_size"], chunk_size=cfg["chunk_size"],
        num_pred_heads=cfg["num_pred_heads"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], max_length=context_tokens,
        dtype=cfg["precision"]["weights"])
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name in list(weights):
        params[net.prefix + program_name(name)]._load_init(
            NDArray(weights.pop(name)), ctx)
    return net


class EvaGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``close``) around the sixth block."""

    def __init__(self, cfg, session, weights, device, cache_dir):
        from mxnet_tpu.serving.decode import DecodeSession
        from mxnet_tpu.serving.gateway import Gateway

        net = block(cfg, session["context_tokens"], weights, device)
        self.model = "perf"
        self.session = DecodeSession(
            net, batch_buckets=tuple(session["batch_buckets"]),
            seq_buckets=tuple(session["seq_buckets"]),
            page_size=session["page_size"],
            num_pages=session.get("num_pages"),
            max_slots=session.get("max_slots"),
            kv_dtype=session.get("kv_dtype"),
            prefix_sharing=session.get("prefix_sharing", True),
            queue_depth=session.get("queue_depth", 256),
            aot_cache=os.path.join(cache_dir, "aot") if cache_dir else None,
            drafter=None)
        # ``stats()``' live_tokens: the TOKENS the pages in use stand for, a
        # row of a page being a chunk's summary
        self.page_size = self.session.cache.page_tokens
        self.gateway = Gateway(name="perf",
                               capacity=session.get("gateway_capacity", 64))
        self.gateway.add_decode(self.model, self.session)
        self.port = self.gateway.port


def build(cfg, traffic, weights, device, cache_dir):
    return EvaGateway(cfg, traffic["session"], weights, device, cache_dir)
