"""The system under test for BERT training cells: ``models.get_bert_model``
through ``parallel.SPMDTrainer`` on a dp mesh — the program's own entry
points, handed the benchmark's weights, batches and dropout keys.

Everything that belongs to this family sits here, behind the name a traffic
file gives as ``system``: the weights, the batches, the call of the plain
reference, and the compiled step.  ``perf/drivers/train_steps.py`` holds the
window only and asks a system module for ``weights``, ``batches``,
``reference_numbers`` and ``build``; another family is another file.

This is also the ONE module that reaches into the program past its public
entry points (listed in PERF.md section 3): ``Parameter._load_init``,
``SPMDTrainer._state`` and ``._step_fn``, and the layout of
``mx.random.set_state``'s key pool.
"""
import numpy as np

from ..harness.projections import N_PROJECTIONS, project, projection_key
from ..harness.weights import host_rng, make_weights, seed_key
from ..reference import bert as reference

N_STEP_KEYS = 128

# benchmark name -> program parameter name (after the block's prefix)
_FIXED = {
    "word_embed": "word_embed_weight", "pos_embed": "pos_embed_weight",
    "type_embed": "type_embed_weight",
    "embed_ln.g": "layernorm0_gamma", "embed_ln.b": "layernorm0_beta",
    "pooler.w": "pooler_weight", "pooler.b": "pooler_bias",
    "mlm_transform.w": "dec_t_weight", "mlm_transform.b": "dec_t_bias",
    "mlm_ln.g": "layernorm1_gamma", "mlm_ln.b": "layernorm1_beta",
    "mlm_bias": "decoder_bias", "nsp.w": "nsp_weight", "nsp.b": "nsp_bias"}
_LAYER = {
    "qkv.w": "attn_qkv_weight", "qkv.b": "attn_qkv_bias",
    "attn_out.w": "attn_out_weight", "attn_out.b": "attn_out_bias",
    "attn_ln.g": "layernorm0_gamma", "attn_ln.b": "layernorm0_beta",
    "ffn1.w": "ffn_ffn1_weight", "ffn1.b": "ffn_ffn1_bias",
    "ffn2.w": "ffn_ffn2_weight", "ffn2.b": "ffn_ffn2_bias",
    "ffn_ln.g": "ffn_layernorm0_gamma", "ffn_ln.b": "ffn_layernorm0_beta"}


def weights(cfg, seed, device=None):
    """The run's seeded weights by the benchmark's names (float32)."""
    return make_weights(reference.shapes(cfg), cfg["initializer_range"],
                        seed, device)


def one_batch(seed, index, cfg, rows, seq_len):
    """Whole batch ``index`` of a run (host arrays; every row differs):
    tokens, segment ids (a sentence pair split at a seeded point), a valid
    mask of full length, the masked positions (distinct, sorted), their
    labels and the next-sentence label."""
    rng = host_rng(seed, stream=100 + index)
    vocab, n_masked = cfg["vocab_size"], cfg["max_predictions_per_seq"]
    tokens = rng.integers(0, vocab, (rows, seq_len), dtype=np.int32)
    split = rng.integers(seq_len // 4, 3 * seq_len // 4, (rows, 1))
    segments = (np.arange(seq_len)[None, :] >= split).astype(np.int32)
    valid = np.ones((rows, seq_len), np.float32)
    positions = np.sort(np.argsort(rng.random((rows, seq_len)), axis=1)
                        [:, :n_masked], axis=1).astype(np.int32)
    mlm = rng.integers(0, vocab, (rows, n_masked), dtype=np.int32)
    nsp = rng.integers(0, 2, (rows,), dtype=np.int32)
    return tokens, segments, valid, positions, mlm, nsp


def batches(cfg, traffic, seed, rows):
    """The pool of seeded batches a run stages and rotates."""
    return [one_batch(seed, i, cfg, rows, traffic["seq_len"])
            for i in range(traffic["batch_pool"])]


def step_keys(seed, n=N_STEP_KEYS):
    """The run's dropout keys: ``n`` host uint32 rows from the seed; step
    ``t`` of the program and of the reference takes row ``t``."""
    import jax
    return np.asarray(jax.random.split(seed_key(seed, stream=2), n))


def reference_numbers(cfg, traffic, seed, batches, precision="float32"):
    """The plain reference's losses, norms and projections over the steps
    that ``batches`` lists (its own weights from the seed, the same dropout
    keys; everything on the device is freed on return)."""
    import jax.numpy as jnp
    keys = step_keys(seed)[:len(batches)]
    dev = [tuple(jnp.asarray(a) for a in b) for b in batches]
    return reference.follow_steps(
        weights(cfg, seed), dev, [jnp.asarray(k) for k in keys], cfg,
        cfg["training"]["optimizer"], traffic["reference_block_rows"],
        precision=precision,
        projection=(projection_key(seed), N_PROJECTIONS))


def program_name(name):
    if name in _FIXED:
        return _FIXED[name]
    layer, rest = name.split(".", 1)
    return f"enc_layer{layer[1:]}_{_LAYER[rest]}"


class BertPretrain:
    """The compiled step with its state: ONE object, built in set-up, driven
    through its first steps for the check and handed to the window."""

    def __init__(self, cfg, seed, weights, devices):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu.models import get_bert_model
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.parallel import (FunctionalOptimizer, SPMDTrainer,
                                        device_mesh)

        if cfg["hidden_dropout_prob"] != cfg["attention_probs_dropout_prob"]:
            raise ValueError("models/bert.py has one dropout rate")
        net = get_bert_model(
            "bert_base", vocab_size=cfg["vocab_size"],
            max_length=cfg["max_position_embeddings"],
            dropout=cfg["hidden_dropout_prob"], units=cfg["hidden_size"],
            hidden_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            token_type_vocab_size=cfg["type_vocab_size"])
        opt = cfg["training"]["optimizer"]
        ctx = mx.context.context_from_jax_device(devices[0])
        params = net.collect_params()
        self.names = {}
        for name, arr in weights.items():
            pname = net.prefix + program_name(name)
            # the checkpoint-load path: no host initialiser runs
            params[pname]._load_init(NDArray(arr), ctx)
            self.names[pname] = name
        missing = set(params.keys()) - set(self.names)
        if missing:
            raise KeyError(f"no weights for {sorted(missing)}")
        vocab, n_masked = cfg["vocab_size"], cfg["max_predictions_per_seq"]
        ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

        def loss_fn(out, label):
            _seq, _pooled, mlm, nsp = out
            mlm_lab = mx.nd.slice_axis(label, axis=1, begin=0, end=n_masked)
            nsp_lab = mx.nd.slice_axis(label, axis=1, begin=n_masked,
                                       end=n_masked + 1)
            return ce(mlm.reshape((-1, vocab)),
                      mlm_lab.reshape((-1,))).mean() + \
                ce(nsp, nsp_lab.reshape((-1,))).mean()

        self.mesh = device_mesh({"pp": 1, "dp": len(devices), "sp": 1,
                                 "tp": 1}, devices=list(devices))
        self.trainer = SPMDTrainer(
            net, loss_fn,
            FunctionalOptimizer("adam", opt["learning_rate"],
                                beta1=opt["beta1"], beta2=opt["beta2"],
                                epsilon=opt["epsilon"]),
            self.mesh, n_in=4)
        self.beta1 = opt["beta1"]
        self.seed = seed
        self._jax = jax
        self._set_step_keys(0)

    def place(self, batch):
        """One whole batch (six host arrays) → the step's ``(data, label)``
        on the mesh, rows split over ``dp``; the label packs the masked-LM
        labels and the next-sentence label side by side."""
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P("dp"))
        tokens, segments, valid, positions, mlm, nsp = batch
        label = np.concatenate([mlm, nsp[:, None]], axis=1).astype("int32")
        put = self._jax.device_put
        return (tuple(put(a, sh) for a in (tokens, segments, valid,
                                           positions)), put(label, sh))

    def _set_step_keys(self, next_index):
        """Hand the program the dropout keys of its next steps:
        ``mx.random``'s pool is the program's own input for them."""
        import mxnet_tpu as mx
        keys = step_keys(self.seed)
        mx.random.set_state({"key": keys[-1], "pool_keys": keys,
                             "pool_i": int(next_index), "pool_last": None})

    def exhaust_step_keys(self):
        """Make the next step refill the program's key pool, as the window's
        steps will: driven once in set-up, the refill's small programs are
        compiled before the window."""
        self._set_step_keys(N_STEP_KEYS)

    def step(self, placed):
        """Dispatch one step; returns the loss (a device scalar, not
        waited for)."""
        return self.trainer.step(*placed)._data

    def state_norms(self):
        """Per-leaf norms of the parameters' first Adam slot, keyed by the
        benchmark's names — after one step that slot is (1 - beta1) x the
        gradient the optimizer was given."""
        import jax.numpy as jnp
        params, opt_state, _aux = self.trainer._state
        f = self._jax.jit(lambda s: {k: jnp.sqrt(jnp.sum(jnp.square(v[0])))
                                     for k, v in s.items()})
        return {self.names[k]: float(x) / (1.0 - self.beta1)
                for k, x in f(opt_state).items()}

    def grad_projections(self):
        """The first gradient's seeded random projections, from the same
        Adam slot, by the benchmark's names."""
        _params, opt_state, _aux = self.trainer._state
        scale = 1.0 / (1.0 - self.beta1)
        tree = {self.names[n]: v[0] for n, v in opt_state.items()}
        return [float(x) * scale for x in project(
            tree, projection_key(self.seed), N_PROJECTIONS)]

    def delta_norms(self, start):
        """Per-leaf norm of (parameters now - ``start``), ``start`` being
        the benchmark's own weights by the benchmark's names (consumed)."""
        import jax.numpy as jnp
        params, _opt, _aux = self.trainer._state
        sh = {k: v.sharding for k, v in params.items()}
        start = {k: self._jax.device_put(start[self.names[k]], sh[k])
                 for k in params}
        f = self._jax.jit(lambda a, b: {
            k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})
        return {self.names[k]: float(x)
                for k, x in f(params, start).items()}

    def step_memory_bytes(self, placed):
        """What the compiled step holds on a chip while it runs, from the
        compiler's own memory analysis: arguments plus temporaries plus the
        outputs that alias no argument.  (The TPU allocator's peak leaves a
        running program's temporaries out.)  One trace and a cache hit."""
        import jax.numpy as jnp
        m = self.trainer._step_fn.lower(
            self.trainer._state, placed[0], placed[1],
            jnp.zeros((2,), "uint32"), jnp.uint32(0)).compile(
            ).memory_analysis()
        parts = {"arguments": int(m.argument_size_in_bytes),
                 "temporaries": int(m.temp_size_in_bytes),
                 "outputs_not_aliased": int(m.output_size_in_bytes
                                            - m.alias_size_in_bytes)}
        return sum(parts.values()), parts


def build(cfg, traffic, seed, weights, devices):
    return BertPretrain(cfg, seed, weights, devices)
