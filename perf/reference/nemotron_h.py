"""Plain reference of Nemotron-3-Nano's forward pass (the ``nemotron_h``
family's layer as ``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16`` configures it): every layer is ``h <- h + mixer(RMSNorm(h))`` with one
mixer by ``hybrid_override_pattern`` — ``M`` a Mamba-2 mixer, ``*``
grouped-query attention, ``E`` routed + shared ``relu^2`` experts — then a
final norm and an untied head.  ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no chunks, no kernel,
and nothing imported from ``mxnet_tpu``.

**The Mamba-2 layer is the sequential recurrence**, a ``lax.scan`` over the
tokens of each sequence, the definition:

    [z | xBC | dt] = u W_in                      4096 | 6144 | 64 wide
    xBC'_t = silu(b + sum_j w[:, j] xBC_{t-3+j}) depthwise, causal, zeros before
    x_t (64 heads x 64), B_t, C_t (8 groups x 128) = split(xBC'_t)
    dt_h = softplus(dt_h + dt_bias_h);  a_h = exp(dt_h A_h), A_h = -exp(A_log_h)
    S_h <- a_h S_h + dt_h x_h (outer) B_{h // 8};  y_h = S_h C_{h // 8} + D_h x_h
    v = y * silu(z), RMS-normalised in 8 groups of 512, times a 4096-wide gain
    out = v W_out

Attention: ``q`` 32 heads x 128, ``k``, ``v`` 2 heads x 128, causal softmax of
``q k^T / sqrt(128)``, query head ``j`` reads KV head ``j // 16``, no biases,
no rotary.  Experts: ``s = sigmoid(u W_r)`` over all published experts, the 6
largest, ``w_k = 2.5 s_k / sum_chosen s``, ``sum_k w_k relu(u W_up,k)^2
W_down,k`` over the chosen experts that are HELD, plus one shared expert of
the same form, unweighted.

It is given the same share of the deployment as the program
(``cfg["held_experts"]``, the sliced vocabulary): what the absent experts
would have added is left out, here as in the program.  Several sequences may
be followed at once (a batch axis, each row its own recurrence: nothing is
rearranged), so that a run's few hundred requests finish.

The weights are the reference's own, made from the seed one tensor at a time
(``shapes`` is the table the system file uses too) and kept as the
configuration stores them; a layer is widened to float32 when it is used.
Departures from the published model are under ``assumed`` in
``perf/configs/nemotron3_nano_ep8.json``.

``precision`` selects a lower precision.  ``"weights_fp8"``, the cell's
control of the correctness check, rounds every matrix through e4m3 with one
scale a tensor: what an fp8 weight store would hand the layer; it fails.
``"state_bf16"`` rounds each Mamba layer's recurrent state to bfloat16 after
every token, as a bfloat16 state pool would.  It is NOT a control of the
cell: it reads below the sound runs (``PERF.md`` section 2: a comparison of
logits cannot see the state's precision), and stays as the probe that shows
so, for the ``benchmark`` PR that finds a check which can.
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def pattern(cfg):
    """The layers this chip runs, a letter each: the published
    ``hybrid_override_pattern``, or its first ``n_layer`` where the
    configuration is cut in depth."""
    whole = cfg["hybrid_override_pattern"]
    return whole[:cfg.get("n_layer", len(whole))]


def sizes(cfg):
    """The widths the layers are built from."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"u": cfg["hidden_size"], "H": H, "P": P, "G": G, "N": N,
            "d_inner": H * P, "conv_dim": H * P + 2 * G * N,
            "K": cfg["conv_kernel"], "q_heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"]}


def shapes(cfg):
    """The tensors of the share, by the published module names: ``{name:
    (shape, kind, dtype)}``.  Matrices are stored ``(in, out)`` and applied
    as ``x W``.  ``kind``: ``normal`` (N(0, initializer_range), rounded to
    bfloat16), ``ones``, ``conv`` (uniform in +-1/sqrt(conv_kernel)),
    ``dt_bias`` (the inverse softplus of a step log-uniform in
    [time_step_min, time_step_max], floored), ``a_log`` (log of a uniform in
    [1, 16]).  Experts are stacked ``(held, in, out)`` in the order of
    ``cfg["held_experts"]``."""
    z = sizes(cfg)
    u, wt = z["u"], cfg["precision"]["weights"]
    G, f = len(cfg["held_experts"]), cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    s = {"embeddings": ((cfg["vocab_size"], u), "normal", wt),
         "lm_head": ((u, cfg["vocab_size"]), "normal", wt),
         "norm_f": ((u,), "ones", "float32")}
    for i, kind in enumerate(pattern(cfg)):
        p = f"layers.{i}."
        s[p + "norm"] = ((u,), "ones", "float32")
        if kind == "M":
            s.update({
                p + "mixer.in_proj": ((u, 2 * z["d_inner"]
                                       + 2 * z["G"] * z["N"] + z["H"]),
                                      "normal", wt),
                p + "mixer.conv1d.weight": ((z["conv_dim"], z["K"]), "conv",
                                            "float32"),
                p + "mixer.conv1d.bias": ((z["conv_dim"],), "conv",
                                          "float32"),
                p + "mixer.dt_bias": ((z["H"],), "dt_bias", "float32"),
                p + "mixer.A_log": ((z["H"],), "a_log", "float32"),
                p + "mixer.D": ((z["H"],), "ones", "float32"),
                p + "mixer.norm": ((z["d_inner"],), "ones", "float32"),
                p + "mixer.out_proj": ((z["d_inner"], u), "normal", wt)})
        elif kind == "*":
            s.update({
                p + "mixer.q_proj": ((u, z["q_heads"] * z["hd"]), "normal",
                                     wt),
                p + "mixer.k_proj": ((u, z["kv_heads"] * z["hd"]), "normal",
                                     wt),
                p + "mixer.v_proj": ((u, z["kv_heads"] * z["hd"]), "normal",
                                     wt),
                p + "mixer.o_proj": ((z["q_heads"] * z["hd"], u), "normal",
                                     wt)})
        else:
            s.update({
                # the router's matrix is float32 (its scores are computed in
                # float32); its values are bfloat16-rounded
                p + "mixer.gate": ((u, cfg["published"]["n_routed_experts"]),
                                   "normal", "float32"),
                p + "mixer.experts.up_proj": ((G, u, f), "normal", wt),
                p + "mixer.experts.down_proj": ((G, f, u), "normal", wt),
                p + "mixer.shared_experts.up_proj": ((u, fs), "normal", wt),
                p + "mixer.shared_experts.down_proj": ((fs, u), "normal",
                                                       wt)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype",
                                              "numbers"))
def _draw(key, shape, kind, dtype, numbers):
    std, k, lo, hi, floor = numbers
    if kind == "normal":
        x = (jax.random.normal(key, shape, jnp.float32) * std).astype(
            jnp.bfloat16)
    elif kind == "conv":
        bound = 1.0 / math.sqrt(k)
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) == dt
    else:
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    return x.astype(dtype)


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key`` (a PRNG key of
    the run's seed): one tensor at a time, tensor ``i`` of the sorted names
    from ``fold_in(seed_key, i)``."""
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    numbers = (float(cfg["initializer_range"]), int(cfg["conv_kernel"]),
               float(cfg["time_step_min"]), float(cfg["time_step_max"]),
               float(cfg["time_step_floor"]))
    out = {}
    for i, (name, (shape, kind, dtype)) in enumerate(sorted(
            shapes(cfg).items())):
        if kind == "ones":
            out[name] = jax.device_put(jnp.ones(shape, dtype), device)
        else:
            out[name] = _draw(jax.random.fold_in(seed_key, i), shape, kind,
                              dtype, numbers)
    return out


# ------------------------------------------------------------- the layers
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _fp8(x):
    """Round to what e4m3 holds (4 significant bits, subnormals below
    2**-6, largest 448) with one scale a tensor.  In arithmetic, not by
    converting to the 8-bit type and back: the chip's compiler drops such a
    pair of converts as excess precision."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / scale
    _m, e = jnp.frexp(y)                    # |y| in [2**(e-1), 2**e)
    step = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    return jnp.round(y / step) * step * scale


def _w(w, precision):
    """A stored matrix, widened to float32 (through e4m3 for the
    ``weights_fp8`` control)."""
    w = w.astype(jnp.float32)
    return _fp8(w) if precision == "weights_fp8" else w


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _expert(x, wu, wd):
    return _mm(jnp.square(jax.nn.relu(_mm(x, wu))), wd)


def _recurrence(x, dt, A, B, C, D, round_state):
    """One sequence: ``x (T, H, P)``, ``dt (T, H)``, ``B``, ``C (T, H, N)``
    (already by head).  The definition, a token at a time."""
    def one(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if round_state:
            # a bfloat16 state pool: 8 exponent bits, 7 of mantissa (an
            # explicit rounding the compiler keeps)
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, (S * C_t[:, None, :]).sum(-1) + D[:, None] * x_t

    S0 = jnp.zeros(x.shape[1:] + (B.shape[-1],), jnp.float32)
    return jax.lax.scan(one, S0, (x, dt, B, C))[1]


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _mamba(w, h, *, cfg_key, precision):
    """``h + Mamba2(RMSNorm(h))`` for sequences ``h (R, T, U)``."""
    z = dict(cfg_key)
    H, P, G, N, K = z["H"], z["P"], z["G"], z["N"], z["K"]
    di, cd = z["d_inner"], z["conv_dim"]
    R, T, _ = h.shape
    a = _rms(h, w["norm"], z["eps"])
    zxd = _mm(a, _w(w["mixer.in_proj"], precision))
    gate, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = w["mixer.conv1d.bias"] + sum(
        padded[:, j:j + T] * w["mixer.conv1d.weight"][:, j] for j in range(K))
    conv = jax.nn.silu(conv)
    x = conv[..., :di].reshape(R, T, H, P)
    # head h reads group h // (H // G)
    B = jnp.repeat(conv[..., di:di + G * N].reshape(R, T, G, N), H // G,
                   axis=2)
    C = jnp.repeat(conv[..., di + G * N:].reshape(R, T, G, N), H // G,
                   axis=2)
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])
    y = jax.vmap(functools.partial(
        _recurrence, A=-jnp.exp(w["mixer.A_log"]), D=w["mixer.D"],
        round_state=precision == "state_bf16"))(x, dt, B=B, C=C)
    v = y.reshape(R, T, di) * jax.nn.silu(gate)
    g = v.reshape(R, T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + z["eps"])
    v = g.reshape(R, T, di) * w["mixer.norm"]
    return h + _mm(v, _w(w["mixer.out_proj"], precision))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(w, h, *, cfg_key, precision):
    """``h + Attention(RMSNorm(h))`` for sequences ``h (R, T, U)``: dense,
    causal, every query head over its KV head."""
    z = dict(cfg_key)
    Hq, Hkv, hd = z["q_heads"], z["kv_heads"], z["hd"]
    R, T, _ = h.shape
    a = _rms(h, w["norm"], z["eps"])
    q = _mm(a, _w(w["mixer.q_proj"], precision)).reshape(R, T, Hq, hd)
    k = _mm(a, _w(w["mixer.k_proj"], precision)).reshape(R, T, Hkv, hd)
    v = _mm(a, _w(w["mixer.v_proj"], precision)).reshape(R, T, Hkv, hd)
    k = jnp.repeat(k, Hq // Hkv, axis=2)        # query head j: KV head j // r
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", pr, v, precision=HI).reshape(
        R, T, Hq * hd)
    return h + _mm(o, _w(w["mixer.o_proj"], precision))


def route(scores, cfg):
    """``(ids (T, k), weights (T, k))`` over ``scores (T, E)``: the ``k``
    largest (``n_group`` = ``topk_group`` = 1: no group is ever masked),
    ``weights = routed_scaling_factor * s_k / sum_chosen s``."""
    k = cfg["num_experts_per_tok"]
    ids = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, cfg["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _route_and_shared(w, h, *, cfg_key, precision):
    """``(m, ids, weights, h + E_shared(m))`` of one expert layer over flat
    tokens ``h (T, U)``."""
    cfg = dict(cfg_key)
    m = _rms(h, w["norm"], cfg["eps"])
    scores = jax.nn.sigmoid(_mm(m, _w(w["mixer.gate"], precision)))
    ids, wts = route(scores, cfg)
    y = _expert(m, _w(w["mixer.shared_experts.up_proj"], precision),
                _w(w["mixer.shared_experts.down_proj"], precision))
    return m, ids, wts, h + y


@functools.partial(jax.jit, static_argnames=("precision",))
def _add_expert(y, m, ids, wts, rows, live, e, wu, wd, *, precision):
    """``y[rows] += w_e * E_e(m[rows])`` for the token ``rows`` that chose
    expert ``e`` (padded to a fixed count; ``live`` marks the real ones)."""
    w_e = jnp.where(ids[rows] == e, wts[rows], 0.0).sum(-1) * live
    out = _expert(m[rows], _w(wu, precision), _w(wd, precision))
    return y.at[rows].add(w_e[:, None] * out)


def _experts(w, h, *, cfg_key, precision):
    """``h + sum over the chosen experts held here of w_k E_k(m) +
    E_shared(m)`` for sequences ``h (R, T, U)``: a plain loop over the held
    experts, each applied to the tokens that chose it (their count padded to
    a power of two so that the shapes are few; a padded row adds zero)."""
    import numpy as np
    R, T, U = h.shape
    m, ids, wts, y = _route_and_shared(w, h.reshape(R * T, U),
                                       cfg_key=cfg_key, precision=precision)
    chose = np.asarray(ids)
    for j, e in enumerate(dict(cfg_key)["held_experts"]):
        rows = np.nonzero((chose == e).any(-1))[0]
        if not rows.size:
            continue
        n = min(max(8, 1 << int(rows.size - 1).bit_length()), chose.shape[0])
        padded = np.zeros((n,), "int32")
        padded[:rows.size] = rows
        y = _add_expert(
            y, m, ids, wts, jnp.asarray(padded),
            jnp.asarray(np.arange(n) < rows.size, jnp.float32),
            jnp.int32(e), w["mixer.experts.up_proj"][j],
            w["mixer.experts.down_proj"][j], precision=precision)
    return y.reshape(R, T, U)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(norm, head, h, *, eps, precision):
    return _mm(_rms(h, norm, eps), _w(head, precision))


def _freeze(cfg):
    """The configuration's numbers as a hashable static argument."""
    out = dict(sizes(cfg), eps=cfg["norm_eps"],
               num_experts_per_tok=cfg["num_experts_per_tok"],
               routed_scaling_factor=cfg["routed_scaling_factor"],
               held_experts=tuple(cfg["held_experts"]))
    return tuple(sorted(out.items()))


_MIXERS = {"M": _mamba, "*": _attention, "E": _experts}


def forward(w, cfg, tokens, precision="float32"):
    """Logits ``(R, T, vocab)`` of sequences ``tokens (R, T)`` (or ``(T,
    vocab)`` of one, ``(T,)``): row ``t`` scores the token that follows
    position ``t``.  A layer at a time."""
    key = _freeze(cfg)
    one = tokens.ndim == 1
    tokens = tokens[None] if one else tokens
    with jax.default_matmul_precision("highest"):
        h = w["embeddings"][tokens].astype(jnp.float32)
        for i, kind in enumerate(pattern(cfg)):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            h = _MIXERS[kind](lw, h, cfg_key=key, precision=precision)
        logits = _head(w["norm_f"], w["lm_head"], h, eps=cfg["norm_eps"],
                       precision=precision)
    return logits[0] if one else logits


@jax.jit
def _gaps(ref, chosen):
    return ref.max(axis=-1) - jnp.take_along_axis(
        ref, chosen[..., None], axis=-1)[..., 0]


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32",
                      tokens_together=6144):
    """For finished requests: at each served position, how far the
    reference's logit of the served token lies below the reference's best
    (0 where the served token IS the reference's choice).

    With ``precision`` set to a lower one, the token read is not the served
    one but the token that the lower precision puts first at that position
    (teacher-forced on the same prompt and served tokens): the control.
    Sequences of like length are followed together, each padded to the next
    multiple of ``pad_to``'s quarter (every layer is causal, so padding
    touches nothing before it), as many a call as ``tokens_together`` holds
    at that length (16 of 384, 4 of 1,536: a few shapes, and a run's two
    hundred requests in a minute).  Returns one float32 array of gaps over
    all served tokens, request after request."""
    import numpy as np
    step = max(pad_to // 4, 1)
    seqs = [list(p) + list(t) for p, t in zip(prompts, served)]
    length = [-(-len(q) // step) * step for q in seqs]
    order = sorted(range(len(seqs)), key=lambda r: length[r])
    out = [None] * len(seqs)
    g = 0
    while g < len(order):
        n = length[order[g]]
        rows = max(tokens_together // n, 1)
        group = [r for r in order[g:g + rows] if length[r] == n]
        g += len(group)
        padded = np.zeros((rows, n), "int32")
        chosen = np.zeros((rows, n), "int32")
        for j, r in enumerate(group):
            padded[j, :len(seqs[r])] = seqs[r]
            # row t scores the token that follows position t
            chosen[j, len(prompts[r]) - 1:len(seqs[r]) - 1] = served[r]
        ref = forward(w, cfg, jnp.asarray(padded))
        if precision != "float32":
            chosen = jnp.argmax(forward(w, cfg, jnp.asarray(padded),
                                        precision), axis=-1)
        gaps = np.asarray(_gaps(ref, jnp.asarray(chosen)), "float32")
        for j, r in enumerate(group):
            out[r] = gaps[j, len(prompts[r]) - 1:len(seqs[r]) - 1]
    return np.concatenate(out) if out else np.zeros((0,), "float32")
