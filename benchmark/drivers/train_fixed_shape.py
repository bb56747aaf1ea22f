"""Driver `train_fixed_shape`: one compiled train step (jit.TrainStep over
AdamW) on batches of one fixed shape taken from a device-resident ring.

Copied from bench.py:run (the path PR 21 ran on the chip), with the batch
taken from a ring drawn from the seed and a window bounded by time instead
of a step count. The host keeps at most `run_ahead` steps in flight by
waiting on the loss of an older step, so the device queue is never empty
and the window ends within a step or two of --seconds; the loss is read
(float) once, when the window closes."""
from __future__ import annotations

import collections
import math
import os
import time

import families
import flops


def setup(run):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    tr, say = run["traffic"], run["say"]
    fam = families.of(run["cfg"])
    forbidden = sorted(k for k in os.environ if k.startswith("PADDLE_TPU_"))
    if forbidden:
        raise SystemExit(f"train_fixed_shape: unset {forbidden}: the cell "
                         "runs the package's defaults")
    B, T = int(tr["batch"]), int(tr["seq_len"])
    t0 = time.perf_counter()
    cfg, model = fam.build(run["cfg"], run["seed"])
    vocab = fam.vocab_size(run["cfg"])
    if T != cfg.seq_len:
        raise SystemExit(f"traffic seq_len {T} != model seq_len "
                         f"{cfg.seq_len}")
    opt_cfg = run["cfg"]["train"]
    crit = fam.criterion()
    opt = paddle.optimizer.AdamW(
        learning_rate=float(opt_cfg["lr"]),
        multi_precision=bool(opt_cfg["multi_precision"]),
        parameters=model.parameters())

    def step_fn(tokens, labels):
        loss = crit(model(tokens), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train = paddle.jit.TrainStep(step_fn, model, opt)

    # the ring: every batch of the run, drawn on the device in one call
    R = int(tr["ring_batches"])

    @jax.jit
    def draw(key):
        toks = jax.random.randint(key, (R, B, T), 0, vocab,
                                  dtype=jnp.int64)
        return toks, jnp.roll(toks, -1, axis=-1)

    toks, labels = draw(jax.random.PRNGKey(run["seed"]))
    ring = [(paddle.to_tensor(toks[i]), paddle.to_tensor(labels[i]))
            for i in range(R)]
    jax.block_until_ready([t._data for pair in ring for t in pair])
    t1 = time.perf_counter()

    first = None
    for i in range(int(tr["warmup_steps"])):
        loss = train(*ring[i % R])
        if first is None:
            first = float(loss)  # sync: the first step has compiled and run
    float(loss)
    t2 = time.perf_counter()
    say(f"set-up parts: import+start {t0 - run['t_start']:.2f} s, model + "
        f"optimizer + ring {t1 - t0:.2f} s, {tr['warmup_steps']} warm-up "
        f"steps (compile or cache load) {t2 - t1:.2f} s; first loss "
        f"{first:.4f}")
    return {"train": train, "ring": ring, "first_loss": first, "cfg": cfg,
            "step": int(tr["warmup_steps"]), "B": B, "T": T}


def window(run, state, seconds):
    tracer = run["tracer"]
    train, ring = state["train"], state["ring"]
    R, ahead = len(ring), int(run["traffic"]["run_ahead"])
    pending = collections.deque()
    dispatch = []
    i = state["step"]
    steps = 0
    loss = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tracer.due(now):
            tracer.start()
        toks, labels = ring[i % R]
        with tracer.annotate("bench.step_call"):
            a = time.perf_counter()
            loss = train(toks, labels)
            dispatch.append(time.perf_counter() - a)
        pending.append(loss)
        i += 1
        steps += 1
        if len(pending) > ahead:
            with tracer.annotate("bench.sync"):
                pending.popleft()._data.block_until_ready()
    with tracer.annotate("bench.sync"):
        final = float(loss)
    window_s = time.perf_counter() - t0
    state["step"] = i
    return {"window_s": window_s, "steps": steps,
            "tokens": steps * state["B"] * state["T"],
            "dispatch_s": dispatch, "final_loss": final,
            "attempted": steps, "failed": 0}


def check(run, state, samples):
    from paddle_tpu.profiler import registry

    say = run["say"]
    first, final = state["first_loss"], samples["final_loss"]
    ok = math.isfinite(first) and math.isfinite(final) and final < first
    say(f"loss: first warm-up step {first:.4f}, at window close "
        f"{final:.4f} after {state['step']} steps "
        f"({'falls' if ok else 'DOES NOT FALL'})")
    k = registry.counters("kernel")
    facts = {n: k[n] for n in ("flash.pallas", "flash.xla",
                               "flash.fallbacks")}
    if run["on_tpu"]:
        kernel_ok = facts["flash.pallas"] >= 1 and facts["flash.xla"] == 0 \
            and facts["flash.fallbacks"] == 0
    else:  # rehearsal off the chip: XLA attention is the only route
        kernel_ok = facts["flash.pallas"] == 0 and facts["flash.xla"] >= 1
    say(f"flash attention resolved: {facts} "
        f"({'as expected' if kernel_ok else 'NOT as expected'})")
    compiled_ok = run["compiles_in_window"] == 0
    if not compiled_ok:
        say(f"{run['compiles_in_window']} compiles inside the window")
    tps = samples["tokens"] / samples["window_s"] / int(run["wl"]["chips"])
    fpt = families.of(run["cfg"]).train_flops_per_token(run)
    disp = sorted(samples["dispatch_s"])
    line = (f"{samples['steps']} steps in {samples['window_s']:.3f} s: "
            f"{tps:.1f} tokens/s/chip, step "
            f"{1e3 * samples['window_s'] / samples['steps']:.2f} ms, "
            f"dispatch median {1e3 * disp[len(disp) // 2]:.3f} ms")
    if fpt is not None:  # a family with no count says nothing of FLOPs
        line += f"; {fpt:.4e} FLOPs/token"
        if run["on_tpu"]:
            pk = flops.peak(run["peaks"], run["device_kind"],
                            "bf16_flops_per_s")
            line += f", MFU {100 * tps * fpt / pk:.2f} % of {pk:.3g}"
    say(line)
    run["compared"] = {
        "final_loss_below_first": [final, first],
        "flash_fallbacks": [facts["flash.fallbacks"], 0],
        "flash_calls_off_the_expected_route":
            [facts["flash.xla" if run["on_tpu"] else "flash.pallas"], 0],
        "compiles_in_window": [run["compiles_in_window"], 0]}
    return ok and kernel_ok and compiled_ok
