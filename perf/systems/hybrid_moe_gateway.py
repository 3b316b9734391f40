"""The system under test for hybrid state-space / attention / routed-expert
serving cells (Nemotron-3-Nano): ``serving.decode.HybridSSMMoELM`` in a
``DecodeSession`` behind ``serving.gateway.Gateway`` over HTTP/SSE on
localhost, in the run's own process — the same gateway, scheduler, runtime
and cache the other serving cells use, handed the benchmark's weights the
way a deployment loads a checkpoint.

The weights are made one tensor at a time from the seed's key by the plain
reference's own table (``perf/reference/nemotron_h.py`` ``weights``); the
program is given those arrays and keeps them (no copy), and the reference
makes them again after the window.  The one reach past the program's public
entry points is ``Parameter._load_init``.

``stats()`` is what the driver samples at 2 Hz: beside the pages it reads
there, each call notes the share of state slots that are live in the
telemetry histogram ``perf.state_slots_live_pct`` (``state_slots_live_share``
reads its mean).
"""
import os

# at import, before any weight is made: a program without the block (the
# parent of the PR that added it) fails here, in seconds
from mxnet_tpu.serving.decode import HybridSSMMoELM

from ..harness.weights import seed_key
from ..reference import nemotron_h as reference
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


_MIXER = {"norm": "norm", "mixer.in_proj": "w_in",
          "mixer.conv1d.weight": "conv_w", "mixer.conv1d.bias": "conv_b",
          "mixer.dt_bias": "dt_bias", "mixer.A_log": "A_log", "mixer.D": "D",
          "mixer.norm": "norm_gate", "mixer.out_proj": "w_out",
          "mixer.q_proj": "wq", "mixer.k_proj": "wk", "mixer.v_proj": "wv",
          "mixer.o_proj": "wo", "mixer.gate": "router",
          "mixer.experts.up_proj": "exp_wu",
          "mixer.experts.down_proj": "exp_wd",
          "mixer.shared_experts.up_proj": "sh_wu",
          "mixer.shared_experts.down_proj": "sh_wd"}


def program_name(name):
    """Benchmark tensor name -> ``HybridSSMMoELM`` parameter name."""
    fixed = {"embeddings": "embed", "lm_head": "head", "norm_f": "norm_f"}
    if name in fixed:
        return fixed[name]
    _layers, i, rest = name.split(".", 2)
    return f"l{i}_{_MIXER[rest]}"


def block(cfg, context_tokens, weights, device):
    """``HybridSSMMoELM`` at the configuration's sizes, holding ``weights``
    (the very arrays, but for the experts' padded ones: the checkpoint-load
    path, no host initialiser).  ``weights`` is emptied."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    net = HybridSSMMoELM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=reference.pattern(cfg),
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_eps=cfg["norm_eps"], max_length=context_tokens,
        dtype=cfg["precision"]["weights"])
    ctx = mx.context.context_from_jax_device(device)
    params = net.collect_params()
    params.setattr("grad_req", "null")          # a server keeps no gradients
    for name in list(weights):
        mine = program_name(name)
        # as the block stores it: the experts' hidden axis padded to whole
        # lane tiles, so those two tensors a layer are new arrays; each is
        # taken OUT of ``weights`` first, so that the tensor it was made
        # from is freed and 10.5 GB are never held beside their padded copy
        params[net.prefix + mine]._load_init(
            NDArray(net.stored(mine, weights.pop(name))), ctx)
    return net


class HybridMoEGateway(DecodeGateway):
    """``DecodeGateway``'s server (its ``close``) around the third block."""

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

    def stats(self):
        import mxnet_tpu as mx
        s = super().stats()
        # kept only while telemetry is on (a traced run)
        mx.telemetry.observe("perf.state_slots_live_pct",
                             100.0 * s["state_slots_live"] / s["max_slots"])
        return s


def build(cfg, traffic, weights, device, cache_dir):
    return HybridMoEGateway(cfg, traffic["session"], weights, device,
                            cache_dir)
