"""The system under test for sliding-window / global attention, routed-expert
serving cells (MiMo-V2.5): ``serving.decode.WindowMoELM`` in a
``DecodeSession`` behind ``serving.gateway.Gateway`` over HTTP/SSE on
localhost, in the run's own process — the same gateway, scheduler, runtime
and cache the other serving cells use, handed the benchmark's weights the
way a deployment loads a checkpoint.

The weights are made one tensor at a time from the seed's key by the plain
reference's own table (``perf/reference/mimo_v2.py`` ``weights``); the
program is given those arrays and keeps them (no copy), and the reference
makes them again after the window.  The one reach past the program's public
entry points is ``Parameter._load_init``.
"""
import os

# at import, before any weight is made: a program without the block (the
# parent of the PR that added it) fails here, in seconds
from mxnet_tpu.serving.decode import WindowMoELM

from ..harness.weights import seed_key
from ..reference import mimo_v2 as reference
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


_LAYER = {"input_layernorm": "norm_attn", "self_attn.q_proj": "wq",
          "self_attn.k_proj": "wk", "self_attn.v_proj": "wv",
          "self_attn.o_proj": "wo", "self_attn.attention_sink_bias": "sink",
          "post_attention_layernorm": "norm_ffn",
          "mlp.gate_proj": "wg", "mlp.up_proj": "wu", "mlp.down_proj": "wd",
          "mlp.gate": "router",
          "mlp.gate.e_score_correction_bias": "router_bias",
          "mlp.experts.gate_proj": "exp_wg", "mlp.experts.up_proj": "exp_wu",
          "mlp.experts.down_proj": "exp_wd"}


def program_name(name):
    """Benchmark tensor name -> ``WindowMoELM`` parameter name."""
    fixed = {"embed_tokens": "embed", "lm_head": "head", "norm": "norm_f"}
    if name in fixed:
        return fixed[name]
    _layers, i, rest = name.split(".", 2)
    return f"l{i}_{_LAYER[rest]}"


def block(cfg, context_tokens, weights, device):
    """``WindowMoELM`` at the configuration's sizes, holding ``weights`` (the
    very arrays: the checkpoint-load path, no host initialiser and no
    copy)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    # the block has one count of query heads and one pair of head widths:
    # the published config gives the window layers the global layers'
    for swa, whole in (("swa_num_attention_heads", "num_attention_heads"),
                       ("swa_head_dim", "head_dim"),
                       ("swa_v_head_dim", "v_head_dim"),
                       ("sliding_window_size", "sliding_window")):
        if cfg.get(swa, cfg[whole]) != cfg[whole]:
            raise ValueError(f"{swa}={cfg[swa]} differs from {whole}="
                             f"{cfg[whole]}: not a block WindowMoELM builds")
    n = cfg["n_layer"]
    net = WindowMoELM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_pattern=cfg["hybrid_layer_pattern"][:n],
        moe_layer_freq=cfg["moe_layer_freq"][:n],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        swa_num_key_value_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"], swa_rope_theta=cfg["swa_rope_theta"],
        sliding_window=cfg["sliding_window"],
        attention_value_scale=cfg["attention_value_scale"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_eps=cfg["layernorm_epsilon"], max_length=context_tokens,
        dtype=cfg["precision"]["weights"])
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name in list(weights):
        params[net.prefix + program_name(name)]._load_init(
            NDArray(weights.pop(name)), ctx)
    return net


class WindowMoEGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``close``) around the fourth block."""

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
    return WindowMoEGateway(cfg, traffic["session"], weights, device,
                            cache_dir)
