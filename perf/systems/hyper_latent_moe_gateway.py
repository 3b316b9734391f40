"""The system under test for latent-attention / routed-expert serving cells
whose residual path is hyper-connected streams (Xing4.0-29B-A4B):
``serving.decode.LatentMoELM(hc_mult=4, select_bias=True)`` in a
``DecodeSession`` behind ``serving.gateway.Gateway`` over HTTP/SSE on
localhost, in the run's own process — the block, gateway, scheduler, runtime
and paged cache of ``latent_moe_gateway.py``, handed the benchmark's weights
the way a deployment loads a checkpoint.

The weights are made one tensor at a time from the seed's key by the plain
reference's own table (``perf/reference/xing4.py`` ``weights``); the program
is given those arrays and keeps them (no copy), and the reference makes them
again after the window.  The one reach past the program's public entry
points is ``Parameter._load_init``.
"""
import os

from mxnet_tpu.serving.decode import LatentMoELM

from ..harness.weights import seed_key
from ..reference import xing4 as reference
from . import latent_moe_gateway
from .decode_gateway import DecodeGateway

# at import, before any weight is made: a program whose block has no
# hyper-connected residual path (the parent of the PR that added it) fails
# here, in seconds.  Declaring a tiny block makes no array.
LatentMoELM(hc_mult=2, select_bias=True)


def weights(cfg, seed, device=None):
    """The run's seeded weights by the benchmark's (published) names."""
    return reference.weights(cfg, seed_key(seed, stream=1), device)


def reference_gaps(cfg, traffic, seed, prompts, served, device,
                   precisions=("float32",)):
    """As ``decode_gateway.reference_gaps``: per precision ``{"max", "mean",
    "moved"}`` of the served tokens' logit gaps under the plain reference
    (for a lower precision or a broken mechanism: of the tokens that it puts
    first), and the number of tokens compared."""
    w = weights(cfg, seed, device)
    every = {p: g.tolist() for p, g in reference.gaps_by_precision(
        w, cfg, prompts, served, traffic["check"]["pad_to"],
        precisions).items()}
    out = {p: {"max": max(g, default=0.0),
               "mean": sum(g) / max(len(g), 1),
               "moved": sum(x > 0 for x in g)} for p, g in every.items()}
    return out, len(every[precisions[0]])


_LAYER = {"mlp.gate.e_score_correction_bias": "select_bias"}
_LAYER.update({f"hc_{sub}.{theirs}": f"hc_{sub}_{ours}"
               for sub in ("attn", "ffn")
               for theirs, ours in (("phi", "phi"), ("alpha", "a"),
                                    ("bias", "b"))})


def program_name(name):
    """Benchmark tensor name -> ``LatentMoELM`` parameter name: the family's
    (``latent_moe_gateway.program_name``) plus the hyper-connections' and
    the selection bias."""
    parts = name.split(".", 2)
    if len(parts) == 3 and parts[2] in _LAYER:
        return f"l{parts[1]}_{_LAYER[parts[2]]}"
    return latent_moe_gateway.program_name(name)


def block(cfg, context_tokens, weights, device):
    """``LatentMoELM`` at the configuration's sizes, holding ``weights``
    (the very arrays: the checkpoint-load path, no host initialiser and no
    copy)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    net = LatentMoELM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["n_layer"], num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"], max_length=context_tokens,
        dtype=cfg["precision"]["weights"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_res_clamp=(cfg["mhc_h_res_clamp_min"],
                      cfg["mhc_h_res_clamp_max"]),
        select_bias=cfg["topk_method"] == "noaux_tc")
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name, arr in weights.items():
        params[net.prefix + program_name(name)]._load_init(NDArray(arr), ctx)
    return net


class HyperLatentMoEGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``stats`` and ``close``) around the
    hyper-connected block."""

    def __init__(self, cfg, session, weights, device, cache_dir):
        from mxnet_tpu.serving.decode import DecodeSession
        from mxnet_tpu.serving.gateway import Gateway

        net = block(cfg, session["context_tokens"], weights, device)
        self.model = "perf"
        self.page_size = session["page_size"]
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
        self.gateway = Gateway(name="perf",
                               capacity=session.get("gateway_capacity", 64))
        self.gateway.add_decode(self.model, self.session)
        self.port = self.gateway.port


def build(cfg, traffic, weights, device, cache_dir):
    return HyperLatentMoEGateway(cfg, traffic["session"], weights, device,
                                 cache_dir)
