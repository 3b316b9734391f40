"""Subprocess worker for the AOT cold-start drill (ci gateway stage).

Each invocation is one "process restart": build + warm a DecodeSession
against an on-disk AOT program cache (or none), generate a fixed prompt,
and print one JSON line with the warm time, the token ids, and the cache
hit/miss/fallback counts.  The drill runs it twice against the same
directory — the second run must load every program (misses == 0), be
several times faster to warm, and produce bitwise-identical tokens.

Usage::

    python tests/aot_cache_worker.py            # no cache: pure cold
    python tests/aot_cache_worker.py /some/dir  # cache-backed
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    cache_dir = sys.argv[1] if len(sys.argv) > 1 and sys.argv[1] else None
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import DecodeSession, get_decode_model

    mx.random.seed(0)
    net = get_decode_model("decode_tiny", vocab_size=96, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    t0 = time.perf_counter()
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8,),
                         page_size=8, aot_cache=cache_dir)
    warm_s = time.perf_counter() - t0
    try:
        res = sess.generate([5, 9, 2], max_new_tokens=8, temperature=0.8,
                            seed=11, timeout=120)
        pc = sess.runtime.aot_cache
        print(json.dumps({
            "warm_s": round(warm_s, 4),
            "token_ids": res.token_ids,
            "finish_reason": res.finish_reason,
            "cache": pc.stats() if pc is not None else None,
            "platform": sess.stats()["platform"],
        }))
    finally:
        sess.close(drain=False)


if __name__ == "__main__":
    main()
