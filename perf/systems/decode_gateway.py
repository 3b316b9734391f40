"""The system under test for GPT-2 serving cells: ``serving.decode.CausalLM``
in a ``DecodeSession`` behind ``serving.gateway.Gateway`` over HTTP/SSE on
localhost, in the run's own process — the program's normal entry points,
handed the benchmark's weights the way a deployment loads a checkpoint.

Everything that belongs to this family sits here, behind the name a traffic
file gives as ``system``: the weights, the call of the plain reference and
the server.  ``perf/drivers/serve_open_loop.py`` holds the window only and
asks a system module for ``weights``, ``build`` and ``reference_gaps``;
another family is another file.  The one reach past the program's public
entry points is ``Parameter._load_init`` (PERF.md section 3).
"""
import os

from ..harness.weights import make_weights
from ..reference import gpt2 as reference


def weights(cfg, seed, device=None):
    """The run's seeded weights by the benchmark's names (float32)."""
    return make_weights(reference.shapes(cfg), cfg["initializer_range"],
                        seed, device)


def reference_gaps(cfg, traffic, seed, prompts, served, device,
                   precisions=("float32",)):
    """Over the served tokens of finished requests (``prompts`` and
    ``served`` are lists of token lists): the widest and the mean gap by
    which a served token's logit lies below the plain reference's best, and
    how many tokens are not the reference's choice — and, for each lower
    precision, the same for the token that precision puts first (the
    control).  The reference makes its own weights from the seed.  Returns
    ``({precision: {"max", "mean", "moved"}}, tokens compared)``."""
    w = weights(cfg, seed, device)
    every = {p: reference.served_token_gaps(
        w, cfg, prompts, served, traffic["check"]["pad_to"],
        precision=p).tolist() for p in precisions}
    out = {p: {"max": max(g, default=0.0),
               "mean": sum(g) / max(len(g), 1),
               "moved": sum(x > 0 for x in g)} for p, g in every.items()}
    return out, len(every[precisions[0]])


def program_name(name):
    """Benchmark tensor name -> ``CausalLM`` parameter name."""
    fixed = {"wte": "embed", "wpe": "pos_embed", "ln_f.g": "lnf_g",
             "ln_f.b": "lnf_b"}
    if name in fixed:
        return fixed[name]
    layer, rest = name.split(".", 1)
    rest = {"ln_1.g": "ln1_g", "ln_1.b": "ln1_b",
            "attn.c_attn.w": "wqkv", "attn.c_attn.b": "bqkv",
            "attn.c_proj.w": "wo", "attn.c_proj.b": "bo",
            "ln_2.g": "ln2_g", "ln_2.b": "ln2_b",
            "mlp.c_fc.w": "w1", "mlp.c_fc.b": "b1",
            "mlp.c_proj.w": "w2", "mlp.c_proj.b": "b2"}[rest]
    return f"l{layer[1:]}_{rest}"


class DecodeGateway:
    def __init__(self, cfg, session, weights, device, cache_dir):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving.decode import CausalLM, DecodeSession
        from mxnet_tpu.serving.gateway import Gateway

        net = CausalLM(vocab_size=cfg["vocab_size"], units=cfg["n_embd"],
                       num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                       max_length=cfg["n_positions"],
                       hidden_size=cfg["n_inner"] or 4 * cfg["n_embd"])
        ctx = mx.context.context_from_jax_device(device)
        params = net.collect_params()
        params.setattr("grad_req", "null")      # a server keeps no gradients
        for name, arr in weights.items():
            # the checkpoint-load path: no host initialiser runs
            params[net.prefix + program_name(name)]._load_init(
                NDArray(arr), ctx)
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
        """The session's own stats, plus ``live_tokens``: the tokens the
        pages in use can hold."""
        s = self.session.stats()
        return dict(s, live_tokens=s["pages_in_use"] * self.page_size)

    def close(self):
        self.gateway.close()
        self.session.close(drain=False)


def build(cfg, traffic, weights, device, cache_dir):
    return DecodeGateway(cfg, traffic["session"], weights, device, cache_dir)
