"""Operations and bytes of one decode step of the latent-attention,
routed-expert block on a residual path of hyper-connected streams
(``perf/configs/xing4_29b_ep8.json``), computed from shapes.  The
yardstick's: ``step_roofline.serve_hyper_moe`` divides the least time
:func:`decode_step_cost` implies by the time the device took, ``hc_roofline``
does the same for the hyper-connections alone (:func:`hc_step_cost`) over
the device time under their named scopes."""
from . import flops_latent_moe


def hc_step_cost(cfg, rows, param_bytes=4, stream_bytes=4):
    """The hyper-connections of one decode step of ``rows`` LIVE sequences:
    2 sublayers a layer, each reading its ``phi (n C, n (n + 2))`` (with its
    ``n (n + 2)`` biases and 3 scalars) once in float32, and each live row's
    stream ``(n, C)`` three times (the coefficients' norm and product, the
    read ``Hpre X``, the write-back's ``Hres X``) and writing it once.
    FLOPs a row and sublayer: the product ``2 n C n (n + 2)``, the norm ``2
    n C``, the read ``2 n C``, the write-back ``2 n C (n + 1)``, and the
    Sinkhorn rounds' ``4 n^2`` a round."""
    n, C = cfg["hc_mult"], cfg["hidden_size"]
    cols = n * (n + 2)
    sublayers = 2 * cfg["n_layer"]
    params = sublayers * (n * C * cols + cols + 3) * param_bytes
    streams = sublayers * rows * 4 * n * C * stream_bytes
    flops = sublayers * rows * (2 * n * C * cols + 4 * n * C
                                + 2 * n * C * (n + 1)
                                + 4 * n * n * cfg["hc_sinkhorn_iters"])
    return {"flops": float(flops), "bytes": float(params + streams),
            "param_bytes": float(params), "stream_bytes": float(streams)}


def decode_step_cost(cfg, rows, context_tokens, experts_hit_per_layer,
                     held_assignments_per_step):
    """One decode step of ``rows`` live sequences holding ``context_tokens``
    tokens each: ``flops_latent_moe.decode_step_cost`` at this configuration
    (its always-read weights, the held experts hit, the live latent rows;
    the selection bias's 256 bytes a layer are not counted) plus the
    hyper-connections' :func:`hc_step_cost`."""
    base = flops_latent_moe.decode_step_cost(
        cfg, rows, context_tokens, experts_hit_per_layer,
        held_assignments_per_step)
    hc = hc_step_cost(cfg, rows)
    return {"flops": base["flops"] + hc["flops"],
            "bytes": base["bytes"] + hc["bytes"],
            "always_read_bytes": base["always_read_bytes"]
            + hc["param_bytes"],
            "hc_bytes": hc["bytes"]}
