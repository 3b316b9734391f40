"""BERT (BASELINE.json config 3: "BERT-base pretraining, Gluon hybridize —
exercises embedding + layernorm + matmul kernels").

The reference repo has no transformer (SURVEY.md §5.7: no attention op at
all) — this is a TPU-first design: every attention matmul is a single
``batch_dot`` on the MXU, shapes are static under ``hybridize()`` (one XLA
executable), and for long sequences the same (B, H, T, D) tensors drop into
``mxnet_tpu.parallel.ring_self_attention`` over an ``sp`` mesh axis.

Pretraining heads follow the standard recipe: tied-embedding masked-LM
decoder + next-sentence classifier.
"""
from __future__ import annotations

import math

from ..gluon import Block, HybridBlock, nn
from ..telemetry import bus as _tel

__all__ = ["MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "BERTEncoder", "BERTModel", "BERTClassifier", "get_bert_model"]


from ..symbol.symbol import Symbol as _Symbol


class MultiHeadAttention(HybridBlock):
    """Self-attention: fused QKV projection, (B,H,T,D) batch_dot scores."""

    def __init__(self, units, num_heads, dropout=0.0,
                 use_flash_attention=True, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._use_flash = use_flash_attention
        with self.name_scope():
            self.qkv = nn.Dense(units * 3, flatten=False, use_bias=True,
                                prefix="qkv_")
            self.proj = nn.Dense(units, flatten=False, use_bias=True,
                                 prefix="out_")
            self.dropout = nn.Dropout(dropout)

    def _split_heads(self, F, x):
        # (B, T, C) -> (B, H, T, C/H)
        x = F.reshape(x, shape=(0, 0, self._num_heads, -1))
        return F.transpose(x, axes=(0, 2, 1, 3))

    def hybrid_forward(self, F, x, mask=None):
        qkv = self.qkv(x)
        q, k, v = F.split(qkv, num_outputs=3, axis=-1)
        q = self._split_heads(F, q) * (1.0 / math.sqrt(self._units //
                                                       self._num_heads))
        k = self._split_heads(F, k)
        v = self._split_heads(F, v)
        from ..parallel.sp_context import current_sequence_parallel
        sp = current_sequence_parallel()
        rate = self.dropout._rate
        plain = mask is None and not rate
        if sp is not None and not plain:
            import warnings
            warnings.warn(
                "sequence-parallel scope active but attention falls back to "
                "the dense T×T path: the sharded attention impls (ring/"
                "ulysses) support neither a valid-length mask nor "
                "attention-prob dropout yet. Long sequences will "
                "materialize full score matrices.")
        # the path is chosen by what is there: a sequence sharded over sp
        # goes around the ring (or, with a mask or dropout, down the dense
        # tail, where XLA partitions the T x T tensors); every other call
        # takes the blockwise kernels, the mask and dropout's keep-mask
        # being operands that are present or absent
        kind = "dense"
        if sp is not None:
            kind = sp[3] if plain else "dense"
        elif self._use_flash:
            kind = "blockwise"
        _tel.count("attention.path", kind=kind, masked=mask is not None,
                   dropout=bool(rate))
        ctx = None
        if kind in ("ring", "ulysses"):
            # sequence-parallel path: T stays sharded over the sp axis;
            # K/V ring around it (parallel/ring_attention.py) or heads are
            # all_to_all-sharded (parallel/ulysses.py), per the scope's impl
            from ..ndarray import invoke_fn
            from ..parallel.ring_attention import ring_self_attention
            from ..parallel.ulysses import ulysses_self_attention
            mesh, sp_axis, dp_axis, impl = sp
            attn = ulysses_self_attention if impl == "ulysses" \
                else ring_self_attention
            ctx = invoke_fn(
                lambda qq, kk, vv: attn(
                    qq, kk, vv, mesh, sp_axis=sp_axis, dp_axis=dp_axis,
                    scale=1.0),
                [q, k, v])
        elif kind == "blockwise":
            # the dropout op draws its key where self.dropout would (one a
            # call, whatever the mode), so the step's key chain is the
            # dense tail's
            args = [q, k, v] if mask is None else [q, k, v, mask]
            ctx = F.contrib.flash_attention_dropout(*args, p=rate,
                                                    scale=1.0) \
                if rate else F.contrib.flash_attention(*args, scale=1.0)
        if ctx is not None:
            ctx = F.transpose(ctx, axes=(0, 2, 1, 3))
            ctx = F.reshape(ctx, shape=(0, 0, -3))
            return self.proj(ctx)
        # the dense tail: use_flash_attention=False, or a sequence-parallel
        # scope with a mask or dropout.  scores: (B, H, T, T)
        scores = F.batch_dot(F.reshape(q, shape=(-3, 0, 0)),
                             F.reshape(k, shape=(-3, 0, 0)),
                             transpose_b=True)
        if mask is not None:
            # mask: (B, T) 1=valid → additive -inf on padded keys
            neg = (1.0 - F.expand_dims(mask, axis=1)) * -1e30
            neg = F.expand_dims(neg, axis=1)  # (B, 1, 1, T)
            scores = F.reshape(scores, shape=(-4, -1, self._num_heads, 0, 0))
            scores = F.broadcast_add(scores, neg)
            scores = F.reshape(scores, shape=(-3, 0, 0))
        attn = F.softmax(scores, axis=-1)
        attn = self.dropout(attn)
        ctx = F.batch_dot(attn, F.reshape(v, shape=(-3, 0, 0)))
        # back to (B, T, C)
        ctx = F.reshape(ctx, shape=(-4, -1, self._num_heads, 0, 0))
        ctx = F.transpose(ctx, axes=(0, 2, 1, 3))
        ctx = F.reshape(ctx, shape=(0, 0, -3))
        return self.proj(ctx)


class PositionwiseFFN(HybridBlock):
    """Dense→GELU→Dense with residual+LayerNorm."""

    def __init__(self, units, hidden_size, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False, prefix="ffn1_")
            self.activation = nn.GELU()
            self.ffn_2 = nn.Dense(units, flatten=False, prefix="ffn2_")
            self.dropout = nn.Dropout(dropout)
            self.layer_norm = nn.LayerNorm()

    def hybrid_forward(self, F, x):
        out = self.ffn_2(self.activation(self.ffn_1(x)))
        out = self.dropout(out)
        return self.layer_norm(out + x)


class TransformerEncoderCell(HybridBlock):
    """Post-LN transformer layer (BERT convention)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads, dropout,
                                                prefix="attn_")
            self.attn_dropout = nn.Dropout(dropout)
            self.attn_norm = nn.LayerNorm()
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       prefix="ffn_")

    def hybrid_forward(self, F, x, mask=None):
        out = self.attention(x, mask)
        x = self.attn_norm(self.attn_dropout(out) + x)
        return self.ffn(x)


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._num_layers = num_layers
        with self.name_scope():
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                self.layers.add(TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout,
                    prefix=f"layer{i}_"))

    def hybrid_forward(self, F, x, mask=None):
        for cell in self.layers._children.values():
            x = cell(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT backbone + pretraining heads.

    ``forward(token_ids, segment_ids, valid_mask, masked_positions)`` →
    ``(sequence_output, pooled_output[, mlm_scores])``; the masked-LM decoder
    is weight-tied to the word embedding.
    """

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_type_vocab_size=2, dropout=0.1, use_pooler=True,
                 use_decoder=True, use_classifier=True, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.use_pooler = use_pooler
        self.use_decoder = use_decoder
        self.use_classifier = use_classifier
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(token_type_vocab_size, units,
                                                 prefix="type_embed_")
            self._max_length = max_length
            self.position_embed = nn.Embedding(max_length, units,
                                               prefix="pos_embed_")
            self.embed_norm = nn.LayerNorm()
            self.embed_dropout = nn.Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout, prefix="enc_")
            if use_pooler:
                self.pooler = nn.Dense(units, activation="tanh",
                                       flatten=False, prefix="pooler_")
            if use_decoder:
                # masked-LM head: transform + tied-embedding output
                self.decoder_transform = nn.Dense(units, flatten=False,
                                                  prefix="dec_t_")
                self.decoder_act = nn.GELU()
                self.decoder_norm = nn.LayerNorm()
                self.decoder_bias = self.params.get(
                    "decoder_bias", shape=(vocab_size,), init="zeros")
            if use_classifier:
                self.nsp_classifier = nn.Dense(2, flatten=False,
                                               prefix="nsp_")

    def hybrid_forward(self, F, inputs, token_types=None, valid_mask=None,
                       masked_positions=None, decoder_bias=None):
        # position embeddings over max_length, sliced to the input's length
        # with slice_like — shape-polymorphic, so the model traces in BOTH
        # frontends (symbol export has no concrete input shape)
        positions = F.arange(self._max_length).astype("int32")
        x = self.word_embed(inputs)
        pos_emb = F.expand_dims(self.position_embed(positions), axis=0)
        x = x + F.slice_like(pos_emb, x, axes=(1,))
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.embed_dropout(self.embed_norm(x))
        seq_out = self.encoder(x, valid_mask)
        outputs = [seq_out]
        if self.use_pooler:
            pooled = self.pooler(F.slice_axis(seq_out, axis=1, begin=0,
                                              end=1).reshape((0, -1)))
            outputs.append(pooled)
        if self.use_decoder and masked_positions is not None:
            # gather masked positions: (B, M, C)
            picked = F._batched_gather(seq_out, masked_positions)
            h = self.decoder_norm(self.decoder_act(
                self.decoder_transform(picked)))
            w = self.word_embed.weight.var() if isinstance(h, _Symbol) \
                else self.word_embed.weight.data(h.context)
            scores = F.dot(h, w, transpose_b=True) + decoder_bias
            outputs.append(scores)
        if self.use_classifier and self.use_pooler:
            outputs.append(self.nsp_classifier(outputs[1]))
        return tuple(outputs) if len(outputs) > 1 else outputs[0]


class BERTClassifier(HybridBlock):
    """Sentence-pair classification head over the pooled output."""

    def __init__(self, bert, num_classes=2, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.bert = bert
        with self.name_scope():
            self.classifier = nn.HybridSequential(prefix="cls_")
            self.classifier.add(nn.Dropout(dropout))
            self.classifier.add(nn.Dense(num_classes, flatten=False))

    def hybrid_forward(self, F, inputs, token_types=None, valid_mask=None):
        _, pooled = self.bert(inputs, token_types, valid_mask)[:2]
        return self.classifier(pooled)


_BERT_CONFIGS = {
    "bert_tiny":  dict(units=128, hidden_size=512, num_layers=2, num_heads=2),
    "bert_mini":  dict(units=256, hidden_size=1024, num_layers=4, num_heads=4),
    "bert_small": dict(units=512, hidden_size=2048, num_layers=4, num_heads=8),
    "bert_base":  dict(units=768, hidden_size=3072, num_layers=12,
                       num_heads=12),
    "bert_large": dict(units=1024, hidden_size=4096, num_layers=24,
                       num_heads=16),
}


def get_bert_model(model_name="bert_base", vocab_size=30522, max_length=512,
                   dropout=0.1, **kwargs):
    cfg = dict(_BERT_CONFIGS[model_name])
    cfg.update(kwargs)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **cfg)
