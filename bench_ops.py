"""Per-op micro-benchmark + cross-round regression gate.

Ref parity: paddle/fluid/operators/benchmark/op_tester.cc +
tools/test_op_benchmark.sh + tools/check_op_benchmark_result.py — the
reference times each op kernel and fails CI when a PR regresses one.
Here the hot ops run under the same differenced-scan method as bench.py
(one dispatch, data-dependent chain, paired differencing to cancel the
fixed dispatch + transfer cost).  A failed op, or a --check against a
baseline from another device, is a non-zero exit.

Usage:
    python bench_ops.py                   # run, print one JSON line/op
    python bench_ops.py --save            # also update the baseline
    python bench_ops.py --check           # fail (exit 1) on >35% regress
    python bench_ops.py --macro [--save|--check]
        # model-level gates instead of the micro set: flash-attention
        # fwd+bwd at seq 512/1024/2048 (the quoted flash-vs-XLA wins)
        # and the seq-8192 longctx GPT train step — so those numbers
        # cannot silently rot (VERDICT r3 item 8)
Baseline: bench_ops_baseline.json (checked in; --save merges the keys it
ran, so micro and macro runs maintain disjoint halves of one file).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_ops_baseline.json")
REGRESS_TOLERANCE = 1.35  # >35% slower than baseline fails the gate
ABS_NOISE_MS = 0.05       # timing noise floor for tiny ops


def _specs():
    """op name -> (fn(x) -> array, example input). Shapes mirror the
    ERNIE-base ladder (batch 32, seq 512, hidden 768)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.op_registry import lookup

    f = lambda name: lookup(name).fn  # noqa: E731
    rng = np.random.RandomState(0)
    h = 768
    x_bsh = jnp.asarray(rng.randn(32, 512, h), jnp.bfloat16)
    w_hh = jnp.asarray(rng.randn(h, h), jnp.bfloat16)
    w_ffn = jnp.asarray(rng.randn(h, 4 * h), jnp.bfloat16)
    img = jnp.asarray(rng.randn(32, 64, 56, 56), jnp.bfloat16)
    kconv = jnp.asarray(rng.randn(64, 64, 3, 3), jnp.bfloat16)
    qkv = jnp.asarray(rng.randn(32, 12, 512, 64), jnp.bfloat16)
    ids = jnp.asarray(rng.randint(0, 18000, (32, 512)), jnp.int32)
    emb = jnp.asarray(rng.randn(18000, h), jnp.bfloat16)
    gamma = jnp.ones((h,), jnp.float32)
    key = jax.random.PRNGKey(0)

    return {
        "matmul_qkv": (lambda x: jnp.matmul(x, w_hh), x_bsh),
        "matmul_ffn": (lambda x: jnp.matmul(x, w_ffn), x_bsh),
        "flash_attention": (
            lambda x: f("flash_attention")(x, qkv, qkv, is_causal=False),
            qkv),
        "softmax": (lambda x: f("softmax")(x, axis=-1), x_bsh),
        "layer_norm": (
            lambda x: f("layer_norm")(x, gamma, jnp.zeros_like(gamma),
                                      begin_norm_axis=2), x_bsh),
        "gelu": (lambda x: f("gelu")(x), x_bsh),
        "dropout": (
            lambda x: f("dropout")(x, key, p=0.1, training=True), x_bsh),
        # carry the TABLE (float) so the scan chain stays data-dependent
        "lookup_table_v2": (lambda e: f("lookup_table_v2")(ids, e), emb),
        # the fused LM-head loss at bench shape: hidden states against
        # the full 18000-vocab tied table, no [N, V] logits materialised
        "fused_linear_cross_entropy": (
            lambda e: f("fused_linear_cross_entropy")(
                x_bsh.reshape(-1, h), e, ids.reshape(-1)), emb),
        "conv2d": (lambda x: f("conv2d")(x, kconv, stride=1, padding=1),
                   img),
        "pool2d": (lambda x: f("pool2d")(x, ksize=2, stride=2,
                                         pooling_type="max"), img),
        "reduce_sum": (lambda x: f("reduce_sum")(x, axis=-1), x_bsh),
        "transpose": (lambda x: f("transpose")(x, perm=(0, 2, 1)), x_bsh),
        "elementwise_add": (lambda x: f("elementwise_add")(x, x), x_bsh),
        "cumsum": (lambda x: f("cumsum")(x, axis=-1), x_bsh),
        "softmax_with_cross_entropy": (
            lambda x: f("softmax_with_cross_entropy")(
                x.reshape(-1, h).astype(jnp.float32),
                ids.reshape(-1) % h)[0], x_bsh),
    }


def _time_op(fn, x, iters=40):
    """Differenced-scan ms/op: chain iterations through a data
    dependency, time N and 3N inside one jit each, min of paired
    diffs. Ops faster than ~50us re-run with 8x the iterations so the
    marginal cost clears the timing noise."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def once(v):
        out = fn(v)
        if isinstance(out, tuple):
            out = out[0]
        return out._value if hasattr(out, "_value") else out

    def make(n):
        @jax.jit
        def run(v):
            def body(carry, _):
                out = once(carry)
                # fold output magnitude back into the carry to chain
                delta = jnp.real(out).astype(jnp.float32).mean() * 1e-6
                return (carry + delta.astype(carry.dtype)
                        if jnp.issubdtype(carry.dtype, jnp.floating)
                        else carry), delta
            carry, deltas = lax.scan(body, v, None, length=n)
            return deltas[-1]
        return run

    def measure(n):
        r1, r2 = make(n), make(3 * n)
        for r in (r1, r2):
            float(np.asarray(r(x)))
        diffs = []
        for _ in range(4):
            t0 = time.perf_counter()
            float(np.asarray(r1(x)))
            t1 = time.perf_counter()
            float(np.asarray(r2(x)))
            t2 = time.perf_counter()
            diffs.append((t2 - t1) - (t1 - t0))
        return max(min(diffs) / (2 * n) * 1e3, 0.0)

    ms = measure(iters)
    if ms < 0.05:
        ms = measure(8 * iters)
    return ms


def _macro_specs():
    """Model-level gates timed like the micro ops: flash attention
    fwd+bwd (default dispatch — the Pallas kernel on TPU at these seq
    lengths) at the quoted ladder sizes, b=32 h=12 d=64 causal."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.op_registry import lookup

    flash = lookup("flash_attention").fn
    rng = np.random.RandomState(0)
    specs = {}
    for s, iters in ((512, 16), (1024, 8), (2048, 4)):
        k = jnp.asarray(rng.randn(32, 12, s, 64), jnp.bfloat16)
        v = jnp.asarray(rng.randn(32, 12, s, 64), jnp.bfloat16)
        q = jnp.asarray(rng.randn(32, 12, s, 64), jnp.bfloat16)

        def fwd_bwd(x, k=k, v=v):
            return jax.grad(lambda a: jnp.sum(
                flash(a, k, v, is_causal=True).astype(jnp.float32)))(x)

        specs[f"flash_fwd_bwd_s{s}"] = (fwd_bwd, q, iters)
    return specs


def main(argv):
    save = "--save" in argv
    check = "--check" in argv
    macro = "--macro" in argv
    import jax

    if macro:
        # as bench_attrib.py configures it: the longctx step's dropout
        # masks come from the on-chip PRNG
        jax.config.update("jax_default_prng_impl", "rbg")
    dev = jax.devices()[0]
    results = {}

    def record(name, ms):
        results[name] = ms
        print(json.dumps({"op": name, "ms": ms,
                          "device": dev.device_kind}))

    if macro:
        for name, (fn, x, iters) in _macro_specs().items():
            record(name, round(_time_op(fn, x, iters=iters), 4))
        # the seq-8192 one-chip GPT train step, in THIS process (a
        # child could not have the chip this process holds)
        import bench_attrib

        record("longctx_gpt_s8192_step",
               round(bench_attrib.longctx()["step_ms"], 2))
    else:
        for name, (fn, x) in _specs().items():
            record(name, round(_time_op(fn, x), 4))
    return _finish(results, dev, save, check)


def _finish(results, dev, save, check):
    kind = dev.device_kind
    if save:
        base = {"device": kind, "ops": {}}
        if os.path.exists(BASELINE_PATH):
            base = json.load(open(BASELINE_PATH))
        if base.get("device") != kind:
            # numbers from another device are meaningless to merge with
            base = {"device": kind, "ops": {}}
        # merge: micro and macro runs each maintain their own keys
        base.setdefault("ops", {}).update(results)
        with open(BASELINE_PATH, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
        print(json.dumps({"saved": BASELINE_PATH}))
        return 0
    if check:
        if not os.path.exists(BASELINE_PATH):
            print(json.dumps({"check": "no baseline"}))
            return 1
        base = json.load(open(BASELINE_PATH))
        if base.get("device") != kind:
            print(json.dumps({"check": "fail",
                              "reason": f"baseline is from "
                                        f"{base.get('device')!r}, this "
                                        f"is {kind!r}"}))
            return 1
        bad = []
        for op, ms in results.items():
            ref = base["ops"].get(op)
            if ref and ms > ref * REGRESS_TOLERANCE \
                    and ms - ref > ABS_NOISE_MS:
                bad.append({"op": op, "ms": ms, "baseline_ms": ref})
        print(json.dumps({"check": "fail" if bad else "ok",
                          "regressions": bad}))
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
