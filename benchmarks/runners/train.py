"""Runner `train`: a pre-training job through the path its owner uses.

A seeded MLM set goes through `paddle.io.DataLoader` (fork workers)
into `Engine.train_batch` under bf16 autocast: `chip_smoke.py`'s trainer
leg, at the batch that fills the chip and with a clock round it.  The
loss is read every few steps, not every step, so the host runs ahead of
the device as it does in a real job; the window closes on a
`block_until_ready` of the last step's loss.

The labels are the input's own tokens at 15% of the positions, a task
the model can learn in tens of steps, so "the loss falls" is a check
with teeth on random tokens.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import flops
from benchmarks.harness import say

STEP_SPAN = "bench.step"
LOADER_SPAN = "bench.loader_next"


def _mlm_dataset(paddle, n, seq, vocab, share, seed):
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    ids = rng.randint(0, vocab, (n, seq)).astype(np.int32)
    labels = ids.copy()
    labels[rng.rand(n, seq) > share] = -100

    class MLMSet(paddle.io.Dataset):
        def __len__(self):
            return n

        def __getitem__(self, i):
            return ids[i], labels[i]

    return MLMSet()


def _build(cell):
    import paddle_tpu as paddle
    from paddle_tpu.engine import Engine
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )

    if cell.config["family"] != "ernie":
        raise SystemExit(f"runner train has no builder for family "
                         f"{cell.config['family']!r}")
    cfg = ErnieConfig(use_parallel=False, **cell.config["model"])
    train = cell.config["training"]
    paddle.seed(cell.seed % (2 ** 31 - 1))
    model = ErnieForPretraining(cfg)
    criterion = ErniePretrainingCriterion(cfg)
    optimizer = paddle.optimizer.AdamW(
        learning_rate=train["learning_rate"],
        parameters=model.parameters(),
        weight_decay=train["weight_decay"])
    engine = Engine(model, optimizer,
                    lambda out, mlm: criterion(out[0], out[1], mlm))
    return cfg, engine


def run(cell, tracer):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import amp, observe

    mix = cell.mix
    batch, seq = int(mix["batch"]), int(mix["seq"])
    cfg, engine = _build(cell)
    loader = paddle.io.DataLoader(
        _mlm_dataset(paddle, batch * int(mix["dataset_batches"]), seq,
                     cfg.vocab_size, float(mix["label_share"]), cell.seed),
        batch_size=batch, shuffle=False, drop_last=True,
        num_workers=int(mix["workers"]))

    def compiles():
        return sum(1 for e in observe.compile_events()
                   if e["name"] == "train_step")

    compiles_at_start = compiles()
    it = iter(loader)
    losses, waits = [], []
    steps = 0
    every = int(mix["loss_every"])
    with amp.auto_cast(enable=True, dtype=cell.config["training"]["autocast"]):
        # set-up: the one compile, then a few warm steps
        t0 = time.perf_counter()
        for _ in range(int(mix["warm_steps"])):
            ids, labels = next(it)
            loss = engine.train_batch(ids, labels)
        first_loss = float(np.asarray(loss._value))
        say(f"train: compile + {mix['warm_steps']} warm steps "
            f"{time.perf_counter() - t0:.1f} s, loss {first_loss:.4f}")
        compiles_warm = compiles()

        window_start = time.perf_counter()
        tracer.open()
        deadline = window_start + cell.seconds
        while time.perf_counter() < deadline:
            t_wait = time.perf_counter()
            with jax.profiler.TraceAnnotation(LOADER_SPAN):
                batch_in = next(it, None)
                if batch_in is None:            # next epoch
                    it = iter(loader)
                    batch_in = next(it)
            waits.append(time.perf_counter() - t_wait)
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                loss = engine.train_batch(*batch_in)
                steps += 1
                if steps % every == 0:
                    losses.append(float(np.asarray(loss._value)))
        jax.block_until_ready(loss._value)
        window_end = time.perf_counter()
        losses.append(float(np.asarray(loss._value)))
        compiles_end = compiles()
        capture = tracer.close()

        # finish the epoch, so that the fork workers find their queues
        # read and leave by themselves (an iterator dropped mid-epoch
        # has them terminated, stack dumps and all)
        for _ in it:
            pass
        on_chip = jax.devices()[0].platform == "tpu"
        text = engine.compiled_text() if on_chip else ""
        memory = engine.memory_analysis()

    elapsed = window_end - window_start
    tokens = steps * batch * seq
    say(f"train: {steps} steps of {batch * seq} tokens in {elapsed:.3f} s; "
        f"loss {first_loss:.4f} -> {losses[-1]:.4f}; waited "
        f"{1e3 * sum(waits) / max(steps, 1):.3f} ms/step on the loader")

    allocator = paddle.device.memory_stats().get("peak_bytes_in_use", -1)
    say(f"train: compiled step peak {memory['peak']} B, allocator peak "
        f"{allocator} B")
    checks = [
        ("compiled_once", compiles_warm - compiles_at_start == 1,
         f"{compiles_warm - compiles_at_start} compile(s) before the "
         "window"),
        ("no_compile_in_window", compiles_end == compiles_warm,
         f"{compiles_end - compiles_warm} compile(s) inside the window"),
        ("loss_finite_and_falling",
         bool(np.isfinite(losses).all()) and losses[-1] < first_loss,
         f"{first_loss:.4f} -> {losses[-1]:.4f} over {len(losses)} reads"),
    ]
    if on_chip:
        # flash forward, dq and dk/dv in every layer; LM-head loss
        # forward, dx and dw
        want = 3 * cfg.num_layers + 3
        n_calls = text.count("tpu_custom_call")
        checks.append(("mosaic_calls", n_calls >= want,
                       f"{n_calls} tpu_custom_call in the compiled step, "
                       f"floor {want}"))
    shape = dict(hidden=cfg.hidden_size, layers=cfg.num_layers,
                 ffn=cfg.ffn_hidden_size, vocab=cfg.vocab_size, seq=seq,
                 head_dense=int(cell.config["training"]["head_dense"]))
    return {
        "attempted": steps, "failed": 0, "checks": checks,
        "window_start": window_start,
        "end_to_end": {
            "train_tokens_per_s_chip": tokens / elapsed / cell.chips},
        "facts": {
            "steps": steps,
            "loader_wait_s": sum(waits),
            "flops_per_step": batch * seq
            * flops.transformer_train_flops_per_token(**shape),
        },
        # the allocator sees the resident state only on this backend
        # (1.4 GB against 14.3 GB compiled, PR 24's chip run); the
        # step's working set is in XLA's buffer assignment
        "memory_peak_bytes": max(int(memory["peak"]), int(allocator)),
        "driver_span": STEP_SPAN,
        "capture": capture,
    }
