"""The system under test for latent-attention / routed-expert serving cells
(A.X-K1): ``serving.decode.LatentMoELM`` in a ``DecodeSession`` behind
``serving.gateway.Gateway`` over HTTP/SSE on localhost, in the run's own
process — the same gateway, scheduler, runtime and paged cache the GPT-2
cells use, handed the benchmark's weights the way a deployment loads a
checkpoint.

The weights are bfloat16 tensors made one at a time from the seed's key by
the plain reference's own table (``perf/reference/axk1.py`` ``weights``):
``perf/harness/weights.make_weights`` would build one float32 tree, 14 GB
here.  The program is given those arrays and keeps them (no copy); the
reference makes them again after the window.  The one reach past the
program's public entry points is ``Parameter._load_init``.
"""
import os

# at import, before any weight is made: a program without the block (the
# parent of the PR that added it) fails here, in seconds
from mxnet_tpu.serving.decode import LatentMoELM

from ..harness.weights import seed_key
from ..reference import axk1 as reference
from .decode_gateway import DecodeGateway


def weights(cfg, seed, device=None):
    """The run's seeded weights by the benchmark's (published) names."""
    return reference.weights(cfg, seed_key(seed, stream=1), device)


def reference_gaps(cfg, traffic, seed, prompts, served, device,
                   precisions=("float32",)):
    """As ``decode_gateway.reference_gaps``: per precision ``{"max", "mean",
    "moved"}`` of the served tokens' logit gaps under the plain reference
    (for a lower precision: of the tokens that precision puts first), and
    the number of tokens compared."""
    w = weights(cfg, seed, device)
    every = {p: reference.served_token_gaps(
        w, cfg, prompts, served, traffic["check"]["pad_to"],
        precision=p).tolist() for p in precisions}
    out = {p: {"max": max(g, default=0.0),
               "mean": sum(g) / max(len(g), 1),
               "moved": sum(x > 0 for x in g)} for p, g in every.items()}
    return out, len(every[precisions[0]])


_ATTN = {"input_layernorm": "norm_attn", "self_attn.q_a_proj": "wqa",
         "self_attn.q_a_layernorm": "norm_q", "self_attn.q_b_proj": "wqb",
         "self_attn.kv_a_proj_with_mqa": "wkva",
         "self_attn.kv_a_layernorm": "norm_kv",
         "self_attn.kv_b_proj": "wkvb", "self_attn.o_proj": "wo",
         "post_attention_layernorm": "norm_ffn",
         "mlp.gate_proj": "wg", "mlp.up_proj": "wu", "mlp.down_proj": "wd",
         "mlp.gate": "router", "mlp.experts.gate_proj": "exp_wg",
         "mlp.experts.up_proj": "exp_wu", "mlp.experts.down_proj": "exp_wd",
         "mlp.shared_experts.gate_proj": "sh_wg",
         "mlp.shared_experts.up_proj": "sh_wu",
         "mlp.shared_experts.down_proj": "sh_wd"}


def program_name(name):
    """Benchmark tensor name -> ``LatentMoELM`` parameter name."""
    fixed = {"embed_tokens": "embed", "lm_head": "head", "norm": "norm_f"}
    if name in fixed:
        return fixed[name]
    _layers, i, rest = name.split(".", 2)
    return f"l{i}_{_ATTN[rest]}"


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
        dtype=cfg["precision"]["weights"])
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name, arr in weights.items():
        params[net.prefix + program_name(name)]._load_init(NDArray(arr), ctx)
    return net


class LatentMoEGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``stats`` and ``close``) around the
    other block."""

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
    return LatentMoEGateway(cfg, traffic["session"], weights, device,
                            cache_dir)
