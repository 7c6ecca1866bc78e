"""chip_smoke.py — the standing proof that the main path starts on the chip.

    python chip_smoke.py             # one TPU chip: train the dense LM, serve it
    python chip_smoke.py --chips 4   # four chips: the data-parallel step only

One process, no children. Trains ``lm_large`` (``bench.LM_LARGE_KWARGS``,
random weights from a seed) for a few steps through ``pt.Trainer``, then
serves the trained weights through ``DecodeEngine`` and checks every phase
by the repo's own means. There is no CPU retry and no smaller config: any
failed check prints its reason, prints ``{"ok": false, ...}`` as the last
line and exits non-zero. Earlier lines are one JSON object per phase
(sizes, seconds, bytes); the seconds are not benchmark numbers. The last
line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 0
TRAIN_BATCH = 4
TRAIN_STEPS = 5
DP_STEPS = 3
# 16 slots x 2048 positions of f32 pages (2 x 1.7 GB as the chip lays them
# out) fit one chip's 15.75 GB: the decode step, its pages donated, needs
# 14.0 GB in all, 9.7 GB of it temp for converting K and V to the page
# write's layout and back; 32 slots do not (22.1 GB, refused by the compiler)
DECODE = dict(max_slots=16, page_size=16, max_context=2048)
N_REQUESTS = 8
PROMPT_LEN = (64, 1024)
NEW_TOKENS = (32, 64)
# stated tolerances (bf16 operands: 8-bit mantissa, ~4e-3 per rounding)
LOSS_ATOL = 2e-2        # flash on vs off at step 0, loss ~ ln(32000) = 10.4
GNORM_RTOL = 2e-2       # ... and the global gradient norm of that step
DP_LOSS_ATOL = 5e-2     # four-chip vs one-chip losses over three Adam steps
# teacher-forced serve check: the engine's token may sit below the plain
# reference's best logit by at most this many standard deviations of that
# position's logits (a wrong token sits ~4 sigma below at vocab 32000)
TIE_SIGMAS = 0.05


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def model_kwargs() -> dict:
    import bench

    return dict(bench.LM_LARGE_KWARGS)


def device_phase(want_chips: int):
    """Phase 0: the accelerator or nothing."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices() is {devs} (platform {devs[0].platform!r})")
    check(len(devs) >= want_chips,
          f"--chips {want_chips} needs {want_chips} chips, jax sees {len(devs)}")
    from paddle_tpu.core.config import apply_compile_cache
    from paddle_tpu.observability.mfu import peak_flops_for_kind

    cache_dir = apply_compile_cache(default_dir=os.path.join(_REPO, ".jax_cache"))
    peak = peak_flops_for_kind(devs[0].device_kind)
    check(peak is not None,
          f"no bf16 peak on record for device kind {devs[0].device_kind!r}")
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), compile_cache_dir=cache_dir, peak_bf16_flops=peak,
         jax=jax.__version__)
    return devs


def mem(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in stats}


def compiled_text(jitted, *args):
    """(text, memory_analysis) of the executable ``jitted`` runs for args."""
    compiled = jitted.lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis()


def flash_parity(spec, batch) -> None:
    """Step-0 loss and gradient norm at batch 1: flash kernel vs XLA
    attention on the same initial weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.config import set_flags
    from paddle_tpu.framework import Variables

    variables = spec.model.init(SEED, *batch)

    def loss_and_gnorm(params, state, *b):
        def loss_fn(p):
            out, _ = spec.model.apply(Variables(p, state), *b, is_train=True)
            return jnp.mean(out[0].astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree_util.tree_leaves(grads))
        return loss, jnp.sqrt(sq)

    got = {}
    for flash in (True, False):
        # the flag is read while tracing: one jit object per setting
        set_flags(use_flash_attention=flash)
        loss, gnorm = jax.jit(lambda p, s, *b: loss_and_gnorm(p, s, *b))(
            variables.params, variables.state, *batch)
        got[flash] = (float(loss), float(gnorm))
    set_flags(use_flash_attention=True)
    (l_on, g_on), (l_off, g_off) = got[True], got[False]
    emit("train.flash_parity", loss_flash=l_on, loss_xla=l_off, gnorm_flash=g_on,
         gnorm_xla=g_off, loss_atol=LOSS_ATOL, gnorm_rtol=GNORM_RTOL)
    check(abs(l_on - l_off) <= LOSS_ATOL,
          f"flash vs XLA attention step-0 loss: {l_on} vs {l_off}")
    check(abs(g_on - g_off) <= GNORM_RTOL * abs(g_off),
          f"flash vs XLA attention step-0 grad norm: {g_on} vs {g_off}")


def train_phase(dev):
    """Phase 1: ``pt.Trainer`` on lm_large, bf16 compute, flash on."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.core.config import set_flags

    set_flags(use_bf16_compute=True, use_flash_attention=True)
    spec = models.get_model("transformer_lm", **model_kwargs())
    rng = np.random.RandomState(SEED)
    batch = spec.synth_batch(TRAIN_BATCH, rng)
    flash_parity(spec, tuple(b[:1] for b in batch))

    trainer = pt.Trainer(lambda: spec.model, spec.optimizer, place=pt.TPUPlace(0))
    losses, walls, t_begin = [], [], [0.0]

    def on_event(ev):
        if isinstance(ev, pt.BeginStepEvent):
            t_begin[0] = time.perf_counter()
        elif isinstance(ev, pt.EndStepEvent):
            jax.block_until_ready((trainer.variables, trainer.opt_state))
            walls.append(time.perf_counter() - t_begin[0])
            losses.append(float(ev.metrics))

    trainer.train(num_epochs=1, event_handler=on_event,
                  reader=lambda: iter([batch] * TRAIN_STEPS))
    steady = sorted(walls[1:])[len(walls[1:]) // 2]
    text, ma = compiled_text(trainer._compiled_step(), trainer.variables,
                             trainer.opt_state, *batch)
    n_params = sum(int(np.prod(p.shape)) for p in trainer.variables.params.values())
    emit("train", batch=TRAIN_BATCH, steps=TRAIN_STEPS, n_params=n_params,
         losses=losses, first_step_s=walls[0], steady_step_s=steady,
         compile_s=walls[0] - steady, tpu_custom_calls=text.count("tpu_custom_call"),
         program_bytes=dict(arguments=ma.argument_size_in_bytes,
                            output=ma.output_size_in_bytes,
                            temp=ma.temp_size_in_bytes,
                            alias=ma.alias_size_in_bytes),
         **mem(dev))
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} of {TRAIN_STEPS} steps ran")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # neither interpret mode nor the fall-through to XLA attention happened
    check("tpu_custom_call" in text,
          "the compiled train step holds no tpu_custom_call: the flash "
          "kernel did not lower through Mosaic")
    check(next(iter(trainer.variables.params.values())).devices() == {dev},
          "trained parameters are not on the chip")
    variables = trainer.variables
    # phase 2's 11.8 GB decode program will not share the chip with the
    # optimizer state and the compiled step
    trainer.opt_state = None
    trainer.stop()  # closes the executor and with it the compiled step
    return spec, variables


def serve_phase(dev, spec, variables):
    """Phase 2: ``DecodeEngine`` on the trained weights, then a
    teacher-forced comparison with one plain forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.config import set_flags
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = spec.extra["cfg"]
    before = mem(dev)
    emit("serve.before_engine", **before)
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(1, cfg["vocab"], size=rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
               .astype(np.int32) for _ in range(N_REQUESTS)]
    budgets = [int(rng.randint(NEW_TOKENS[0], NEW_TOKENS[1] + 1)) for _ in range(N_REQUESTS)]

    t0 = time.perf_counter()
    with DecodeEngine(variables, dict(cfg, scan_layers=False),
                      decode=DecodeConfig(**DECODE)) as engine:
        t_built = time.perf_counter()
        handles = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [h.result(timeout=600) for h in handles]
        t_done = time.perf_counter()
        snap = engine.metrics.snapshot()
        step_cache = engine.decode_step_cache_size()
    engine.kv.assert_no_leaks()
    emit("serve", requests=N_REQUESTS, **DECODE,
         prompt_lens=[int(p.size) for p in prompts], new_tokens=budgets,
         engine_build_s=t_built - t0, serve_s=t_done - t_built,
         finish_reasons=[o.finish_reason for o in outs],
         steps_total=snap["steps_total"],
         prefill_chunks_total=snap["prefill_chunks_total"],
         step_faults_total=snap["step_faults_total"],
         recovered_total=snap["recovered_total"],
         decode_step_cache_size=step_cache, **mem(dev))
    check(all(o.finish_reason == "length" and o.tokens.size == n
              for o, n in zip(outs, budgets)),
          f"not every request ran to its budget: "
          f"{[(o.finish_reason, int(o.tokens.size)) for o in outs]} vs {budgets}")
    # a kernel the chip refuses would otherwise pass as "recovered"
    check(snap["step_faults_total"] == 0 and snap["recovered_total"] == 0
          and snap["errors_total"] == 0,
          f"the engine faulted and recovered: {snap}")
    check(step_cache == 1, f"decode step compiled {step_cache} times, not once")
    del engine

    # teacher-forced: one plain forward (eval mode, XLA attention, f32
    # operands) over prompt + output, all padded to one causal length
    set_flags(use_flash_attention=False, use_bf16_compute=False)
    n_max = max(budgets)
    t_pad = -(-max(p.size + n for p, n in zip(prompts, budgets)) // 128) * 128
    ids = np.zeros((N_REQUESTS, t_pad), np.int32)
    at = np.zeros((N_REQUESTS, n_max), np.int32)
    for r, (p, o) in enumerate(zip(prompts, outs)):
        ids[r, :p.size + o.tokens.size] = np.concatenate([p, o.tokens])
        # logits at position len(prompt)-1+j predict output token j
        at[r, :o.tokens.size] = p.size - 1 + np.arange(o.tokens.size)

    def ref_logits(v, ids_, at_):
        (_, _, logits), _ = spec.model.apply(v, ids_, ids_, is_train=False)
        return jnp.take_along_axis(logits, at_[:, :, None], axis=1)

    ref = np.asarray(jax.jit(ref_logits)(variables, ids, at), np.float32)
    set_flags(use_flash_attention=True, use_bf16_compute=True)
    check(np.isfinite(ref).all(), "non-finite reference logits")
    exact = total = 0
    worst = 0.0
    for r, o in enumerate(outs):
        rows = ref[r, :o.tokens.size]
        gap = rows.max(-1) - rows[np.arange(o.tokens.size), o.tokens]
        worst = max(worst, float((gap / rows.std(-1)).max()))
        exact += int((rows.argmax(-1) == o.tokens).sum())
        total += int(o.tokens.size)
    emit("serve.teacher_forced", positions=total, exact=exact,
         worst_gap_sigmas=worst, tie_sigmas=TIE_SIGMAS, padded_len=int(t_pad))
    check(worst <= TIE_SIGMAS,
          f"an engine token sits {worst:.3f} sigma below the reference's "
          f"best logit (allowed {TIE_SIGMAS}); {exact}/{total} exact")


def four_chip_phase(devs):
    """``--chips 4``: the data-parallel step on a ``data`` mesh of every
    chip against the same steps on chip 0 alone."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.core.config import set_flags
    from paddle_tpu.parallel import DataParallel
    from paddle_tpu.parallel.mesh import make_mesh

    set_flags(use_bf16_compute=True, use_flash_attention=True)
    spec = models.get_model("transformer_lm", **model_kwargs())
    batch = spec.synth_batch(TRAIN_BATCH, np.random.RandomState(SEED))

    # chip 0 alone first: its state must be gone before the mesh run
    opt = spec.optimizer()
    exe = pt.Executor(pt.TPUPlace(0))
    step = exe.prepare(opt.minimize(spec.model), donate_argnums=(0, 1))
    v = exe.put(spec.model.init(SEED, *batch))
    o = exe.put(opt.create_state(v.params))
    single = []
    for _ in range(DP_STEPS):
        out = step(v, o, *batch)
        v, o = out.variables, out.opt_state
        single.append(float(out.loss))
    del out, v, o, step
    exe.close()

    dp = DataParallel(spec.model, spec.optimizer(), mesh=make_mesh(data=-1))
    v, o = dp.init(SEED, *batch)
    dev_batch = dp.put_batch(*batch)
    losses, walls = [], []
    for _ in range(DP_STEPS):
        t0 = time.perf_counter()
        out = dp.step(v, o, *dev_batch)
        v, o = out.variables, out.opt_state
        jax.block_until_ready((v, o))
        walls.append(time.perf_counter() - t0)
        losses.append(float(out.loss))
    del out
    with jax.set_mesh(dp.mesh):
        text, ma = compiled_text(dp._step_fn, v, o, None, *dev_batch)
    in_use = [mem(d).get("bytes_in_use", 0) for d in devs]
    emit("data_parallel", chips=len(devs), global_batch=TRAIN_BATCH,
         steps=DP_STEPS, losses=losses, single_chip_losses=single,
         loss_atol=DP_LOSS_ATOL, first_step_s=walls[0], steady_step_s=min(walls[1:]),
         tpu_custom_calls=text.count("tpu_custom_call"),
         all_reduces=text.count("all-reduce"), all_gathers=text.count("all-gather"),
         program_bytes_per_chip=dict(arguments=ma.argument_size_in_bytes,
                                     output=ma.output_size_in_bytes,
                                     temp=ma.temp_size_in_bytes,
                                     alias=ma.alias_size_in_bytes),
         bytes_in_use=in_use)
    check(all(np.isfinite(losses)) and all(np.isfinite(single)),
          f"non-finite loss: mesh {losses}, one chip {single}")
    check(all(abs(a - b) <= DP_LOSS_ATOL for a, b in zip(losses, single)),
          f"mesh losses {losses} disagree with one chip's {single}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    n = len(devs)
    check(all(len(p.sharding.device_set) == n for p in v.params.values()),
          "a parameter leaf does not live on every chip")
    check(all(len({s.device for s in b.addressable_shards}) == n
              and b.addressable_shards[0].data.shape[0] * n == b.shape[0]
              for b in dev_batch),
          "the batch is not split over the data axis")
    check(all(x > 0 for x in in_use), f"a chip holds nothing: {in_use}")
    check("tpu_custom_call" in text and "all-reduce" in text,
          "the data-parallel step lacks the flash kernel or the gradient "
          f"all-reduce ({text.count('tpu_custom_call')} tpu_custom_call, "
          f"{text.count('all-reduce')} all-reduce)")


def run(chips: int) -> dict:
    t0 = time.perf_counter()
    devs = device_phase(chips)
    if chips == 4:
        four_chip_phase(devs)
    else:
        spec, variables = train_phase(devs[0])
        serve_phase(devs[0], spec, variables)
    emit("done", wall_s=time.perf_counter() - t0)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the data-parallel phase and its one-chip "
                         "comparison, and no other phase")
    args = ap.parse_args(argv)
    try:
        device = run(args.chips)
    except Exception as e:  # the script's one boundary: report, then fail
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
