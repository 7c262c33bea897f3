"""Perf attribution for the ERNIE train step (not the driver bench).

Times variants with the same differenced scan-N method as bench.py to
locate where step time goes: full step (default dispatch — the Pallas
flash kernel at seq >= 128), dropout off, and forced pallas/jnp paths
for kernel-vs-XLA comparisons.

The `attrib` variant skips the timing sweep and instead captures an
xplane trace of the running step, printing the device-time bucket split
(observe.attribute) plus the collective-overlap pairing
(observe.overlap_report).  A capture whose device plane holds no
classifiable op rows is a broken capture, not a zero measurement — the
variant exits nonzero with a message instead of printing a JSON line
full of silent zeros.
"""

import json
import os
import sys
import time

import numpy as np


def _timed_scan_ms(eng, ids, labels, *, n1, reps):
    """Differenced-scan ms/step shared by every variant: scan n1 and
    3*n1 steps inside one jit each (true step-to-step data dependency),
    difference paired timings so the fixed dispatch + transfer cost
    cancels, min over `reps` pairs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu import amp
    from paddle_tpu.framework import random as _random

    raw = eng._step_fn._raw_step_fn
    xj, yj = jnp.asarray(ids), jnp.asarray(labels)
    lr = jnp.asarray(1e-4, jnp.float32)
    key = _random.default_generator.next_key()
    st = eng.state

    def make(n):
        @jax.jit
        def run(params, buffers, opt_state):
            def body(carry, i):
                p, b, o = carry
                with amp.auto_cast(enable=True, dtype="bfloat16"):
                    loss, p2, b2, o2 = raw(
                        p, b, o, {"inputs": (xj,), "labels": (yj,)},
                        lr, jax.random.fold_in(key, i))
                return (p2, b2, o2), loss
            (p, b, o), losses = lax.scan(
                body, (params, buffers, opt_state), jnp.arange(n))
            return losses[-1]
        return run

    r1, r2 = make(n1), make(3 * n1)
    for r in (r1, r2):
        float(np.asarray(r(st.params, st.buffers, st.opt_state)))
    diffs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(r1(st.params, st.buffers, st.opt_state)))
        t1 = time.perf_counter()
        float(np.asarray(r2(st.params, st.buffers, st.opt_state)))
        t2 = time.perf_counter()
        diffs.append((t2 - t1) - (t1 - t0))
    return min(diffs) / (2 * n1) * 1e3


def main():
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    import jax.numpy as jnp
    from jax import lax

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.engine import Engine
    from paddle_tpu.framework import random as _random
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq = 512
    iters = 16

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 18000, (batch, seq)).astype(np.int32)
    labels = ids.copy()
    labels[rng.rand(batch, seq) > 0.15] = -100

    def build(dropout, force_attn=None, mesh=None):
        if force_attn:
            os.environ["PADDLE_TPU_FLASH_FORCE"] = force_attn
        else:
            os.environ.pop("PADDLE_TPU_FLASH_FORCE", None)
        paddle.seed(0)
        cfg = ErnieConfig(vocab_size=18000, hidden_size=768, num_layers=12,
                          num_heads=12, ffn_hidden_size=3072,
                          max_seq_len=seq, dropout=dropout,
                          attn_dropout=dropout, use_parallel=False)
        model = ErnieForPretraining(cfg)
        criterion = ErniePretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     weight_decay=0.01)

        def loss_fn(outputs, mlm_labels):
            logits, nsp = outputs
            return criterion(logits, nsp, mlm_labels)

        kwargs = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            kwargs = dict(mesh=mesh,
                          batch_spec=NamedSharding(mesh, P("dp")))
        eng = Engine(model, opt, loss_fn, **kwargs)
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            eng.train_batch(ids, labels)  # build + warm
        return eng

    def timed_step(eng):
        return _timed_scan_ms(eng, ids, labels, n1=iters, reps=1)

    variant = sys.argv[1] if len(sys.argv) > 1 else "full"
    if variant == "longctx":
        print(json.dumps(longctx()))
        return 0
    if variant == "attrib":
        return attrib()
    if variant == "full":
        eng = build(dropout=0.1)
    elif variant == "nodrop":
        eng = build(dropout=0.0)
    elif variant == "pallas_attn":
        eng = build(dropout=0.1, force_attn="pallas")
    elif variant == "pallas_nodrop":
        eng = build(dropout=0.0, force_attn="pallas")
    elif variant == "mesh1":
        # GSPMD-partitioned step over a 1-device mesh: must match the
        # un-meshed step time — the Pallas kernel stays in the meshed
        # program under a shard_map (VERDICT r4 item 1)
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        eng = build(dropout=0.1, mesh=mesh)
    else:
        raise SystemExit(f"unknown variant {variant}")
    ms = timed_step(eng)
    print(json.dumps({"variant": variant, "step_ms": round(ms, 2)}))


def attrib():
    """Device-time attribution + overlap pairing of the live train step.

    Exits 2 (with a stderr message) when the xplane capture comes back
    with an empty device plane — zero classified rows means the
    profiler produced nothing to attribute, and a silent all-zero JSON
    line would read as "no collective time" rather than "no data".
    """
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.engine import Engine
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        batch, seq = int(os.environ.get("BENCH_BATCH", "32")), 512
        cfg = ErnieConfig(vocab_size=18000, hidden_size=768, num_layers=12,
                          num_heads=12, ffn_hidden_size=3072,
                          max_seq_len=seq, dropout=0.1, attn_dropout=0.1,
                          use_parallel=False)
    else:
        batch, seq = 4, 64
        cfg = ErnieConfig(vocab_size=512, hidden_size=64, num_layers=2,
                          num_heads=4, ffn_hidden_size=128,
                          max_seq_len=seq, dropout=0.0,
                          use_parallel=False)

    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    criterion = ErniePretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)

    def loss_fn(outputs, mlm_labels):
        logits, nsp = outputs
        return criterion(logits, nsp, mlm_labels)

    eng = Engine(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = ids.copy()
    labels[rng.rand(batch, seq) > 0.15] = -100
    with amp.auto_cast(enable=True, dtype="bfloat16"):
        eng.train_batch(ids, labels)
        eng.train_batch(ids, labels)  # warm: attribute a steady step

    try:
        report = eng.attribute_step(steps=3)
        overlap = eng.overlap_report(steps=3)
    except FileNotFoundError as e:
        print(f"bench_attrib attrib: xplane capture missing ({e}); the "
              "profiler wrote no device trace — nothing to attribute",
              file=sys.stderr)
        return 2
    if report["total_us"] <= 0.0 or overlap["total_us"] <= 0.0:
        print("bench_attrib attrib: xplane capture yielded an EMPTY "
              "device plane (zero classified op rows); the profiler "
              "backend produced no device events — refusing to print "
              "an all-zero attribution", file=sys.stderr)
        return 2
    print(json.dumps({
        "variant": "attrib",
        "batch": batch, "seq": seq,
        "buckets_us": {k: round(v, 1)
                       for k, v in report["buckets"].items()},
        "fractions": {k: round(v, 4)
                      for k, v in report["fractions"].items()},
        "exposed_collective_frac":
            round(overlap["exposed_collective_frac"], 4),
        "collective_share": round(overlap["collective_share"], 4),
        "hidden_collective_us": round(overlap["hidden_collective_us"], 1),
        "total_us": round(report["total_us"], 1),
    }))
    return 0


def longctx():
    """Long-context evidence: GPT-base causal train step at seq 8192 on
    ONE chip — possible because the flash backward's VMEM is bounded by
    block sizes (the XLA attention path OOMs at seq 4096).  Returns
    the result record (bench_ops.py --macro calls this in-process)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.engine import Engine
    from paddle_tpu.framework import random as _random
    from paddle_tpu.nlp.transformers import (
        GPTConfig, GPTForPretraining, GPTPretrainingCriterion,
    )

    batch, seq = int(os.environ.get("BENCH_LC_BATCH", "1")), 8192
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=seq, dropout=0.1,
                    attn_dropout=0.1, use_parallel=False)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    eng = Engine(model, opt,
                 lambda logits, labels: crit(logits, labels))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size,
                       (batch, seq + 1)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    with amp.auto_cast(enable=True, dtype="bfloat16"):
        eng.train_batch(x, y)
    ms = _timed_scan_ms(eng, x, y, n1=4, reps=3)
    tokens_per_sec = batch * seq / (ms / 1e3)
    return {"variant": "longctx", "seq": seq, "batch": batch,
            "step_ms": round(ms, 2),
            "tokens_per_sec": round(tokens_per_sec, 1)}


if __name__ == "__main__":
    sys.exit(main())
