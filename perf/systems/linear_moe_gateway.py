"""The system under test for linear-attention / gated grouped-query
attention, routed-expert serving cells (Solar-Open2-250B):
``serving.decode.LinearMoELM`` in a ``DecodeSession`` behind
``serving.gateway.Gateway`` over HTTP/SSE on localhost, in the run's own
process — the same gateway, scheduler, runtime and cache the other serving
cells use, handed the benchmark's weights the way a deployment loads a
checkpoint.

The weights are made one tensor at a time from the seed's key by the plain
reference's own table (``perf/reference/solar_open2.py`` ``weights``); the
program is given those arrays and keeps them (no copy), and the reference
makes them again after the window.  The one reach past the program's public
entry points is ``Parameter._load_init``.
"""
import os

# at import, before any weight is made: a program without the block (the
# parent of the PR that added it) fails here, in seconds
from mxnet_tpu.serving.decode import LinearMoELM

from ..harness.weights import seed_key
from ..reference import solar_open2 as reference
from .decode_gateway import DecodeGateway


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
    every = {p: reference.served_token_gaps(
        w, cfg, prompts, served, traffic["check"]["pad_to"],
        precision=p).tolist() for p in precisions}
    out = {p: {"max": max(g, default=0.0),
               "mean": sum(g) / max(len(g), 1),
               "moved": sum(x > 0 for x in g)} for p, g in every.items()}
    return out, len(every[precisions[0]])


_LAYER = {"input_layernorm": "norm_mix", "self_attn.q_proj": "wq",
          "self_attn.k_proj": "wk", "self_attn.v_proj": "wv",
          "self_attn.g_proj": "wgate", "self_attn.o_proj": "wo",
          "self_attn.q_conv1d": "conv_q", "self_attn.k_conv1d": "conv_k",
          "self_attn.v_conv1d": "conv_v", "self_attn.f_a_proj": "wf1",
          "self_attn.f_b_proj": "wf2", "self_attn.A_log": "A_log",
          "self_attn.dt_bias": "dt_bias", "self_attn.b_proj": "wb",
          "self_attn.g_a_proj": "wg1", "self_attn.g_b_proj": "wg2",
          "self_attn.g_b_proj.bias": "bg", "self_attn.o_norm": "norm_o",
          "post_attention_layernorm": "norm_ffn", "mlp.gate": "router",
          "mlp.experts.gate_proj": "exp_wg", "mlp.experts.up_proj": "exp_wu",
          "mlp.experts.down_proj": "exp_wd",
          "mlp.shared_experts.gate_proj": "sh_wg",
          "mlp.shared_experts.up_proj": "sh_wu",
          "mlp.shared_experts.down_proj": "sh_wd"}


def program_name(name):
    """Benchmark tensor name -> ``LinearMoELM`` parameter name."""
    fixed = {"embed_tokens": "embed", "lm_head": "head", "norm": "norm_f"}
    if name in fixed:
        return fixed[name]
    _layers, i, rest = name.split(".", 2)
    return f"l{i}_{_LAYER[rest]}"


def block(cfg, context_tokens, weights, device):
    """``LinearMoELM`` at the configuration's sizes, holding ``weights`` (the
    very arrays: the checkpoint-load path, no host initialiser and no
    copy).  ``weights`` is emptied."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    z = reference.sizes(cfg)
    net = LinearMoELM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["n_layer"], gqa_layers=reference.gqa_layers(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], kda_num_heads=z["H"],
        kda_head_dim=z["dk"], conv_kernel=z["K"], gate_rank=z["rank"],
        chunk_size=cfg["kda_chunk_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_eps=cfg["rms_norm_eps"], max_length=context_tokens,
        dtype=cfg["precision"]["weights"])
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name in list(weights):
        params[net.prefix + program_name(name)]._load_init(
            NDArray(weights.pop(name)), ctx)
    return net


class LinearMoEGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``close``) around the fifth block."""

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
    return LinearMoEGateway(cfg, traffic["session"], weights, device,
                            cache_dir)
