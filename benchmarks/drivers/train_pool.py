"""Driver ``train_pool``: one ``pt.Trainer`` fed a pool of seeded batches
through its normal reader. Set-up builds the trainer, walks it through the
checked first steps and the warm-up, and the same object runs the window.
The plain reference follows the checked steps after the window, once the
program's state is freed."""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, weights
from benchmarks.families import _common
from benchmarks.references import common as refc


def _first_gradient(opt_state, beta1: float) -> dict:
    """The first gradient as the optimizer got it, as host arrays: after one
    step from zero state Adam's first moment is (1 - beta1) times it."""
    moment = jax.device_get(dict(opt_state.slots["moment1"]))
    return {k: np.asarray(v, np.float32) / np.float32(1.0 - beta1) for k, v in moment.items()}


def prepare(ctx):
    """(family module, pool of batches, model, parameter shapes) of the cell."""
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    pool = family.training_pool(ctx.mix, ctx.config, ctx.seed)
    model, _ = family.build_model(ctx.config, family.row_length(ctx.mix), "train")
    return family, pool, model, _common.param_shapes(model, pool[0])


def program_walk(ctx, family, pool, model, shapes):
    """Build the trainer, run check + warm steps and the window. Returns what
    the checked steps and the window showed, and the memory peak."""
    import paddle_tpu as pt

    config, mix = ctx.config, ctx.mix
    w0 = weights.make_weights(shapes, ctx.seed)
    trainer = _common.make_trainer(model, config, w0, ctx.devices, pool[0])
    del w0
    n_check, n_warm = mix["check_steps"], mix["warm_steps"]
    beta1 = config["optimizer"]["beta1"]
    st = dict(step=0, losses=[], win_losses=[], t_open=None, t_close=None, steps=0, tokens=0,
              first_grad=None, delta_norms=None, stop=False)
    tokens_of = [family.real_target_tokens(b) for b in pool]

    def reader():
        i = 0
        while not st["stop"]:
            with jax.profiler.TraceAnnotation("bench.reader"):
                batch = pool[i % len(pool)]
            yield batch
            i += 1

    def on_event(ev):
        if not isinstance(ev, pt.EndStepEvent):
            return
        with jax.profiler.TraceAnnotation("bench.step_end"):
            # every output of the step's one program is ready when one is
            jax.block_until_ready(trainer.opt_state.step)
            now = time.perf_counter()
            k = st["step"]
            st["step"] += 1
            loss = float(ev.metrics)
            if k < n_check:
                st["losses"].append(loss)
                if k == 0:
                    st["first_grad"] = _first_gradient(trainer.opt_state, beta1)
                if k == n_check - 1:
                    p0 = weights.make_weights(shapes, ctx.seed)
                    d = refc.leaf_delta_norms(dict(trainer.variables.params), p0)
                    st["delta_norms"] = {n: float(v) for n, v in d.items()}
                    del p0, d
            if st["t_open"] is None:
                if k == n_check + n_warm - 1:
                    ctx.open_window()
                    st["t_open"] = time.perf_counter()
                return
            st["steps"] += 1
            st["tokens"] += tokens_of[k % len(pool)]
            st["win_losses"].append(loss)
            if now - st["t_open"] >= ctx.seconds:
                st["t_close"] = now
                st["stop"] = True

    trainer.train(num_epochs=1, event_handler=on_event, reader=reader)
    ctx.close_window()
    peak = ctx.memory_peak()
    trainer.variables = trainer.opt_state = None
    trainer.stop()
    del trainer
    gc.collect()
    return st, peak


def program_readings(st: dict) -> dict:
    norms = {k: float(np.sqrt(np.sum(np.square(g, dtype=np.float64))))
             for k, g in st["first_grad"].items()}
    return {"losses": st["losses"], "grad_norms": norms, "delta_norms": st["delta_norms"]}


def reference_walk(ctx, family, pool, shapes, mm_name: str = "f32", **walk) -> dict:
    ref = importlib.import_module(f"benchmarks.references.{family.REFERENCE}")
    mix = ctx.mix
    loss_sum = family.reference_loss(ctx.config, refc.MATMULS[mm_name])
    f32 = weights.as_float32(shapes)
    batches = [tuple(jnp.asarray(x) for x in b) for b in pool[:mix["check_steps"]]]
    return refc.walk_steps(
        loss_sum, lambda: weights.make_weights(f32, ctx.seed), batches,
        lambda b: ref.blocks(b, mix["ref_block_rows"]), ref.n_tokens, ctx.config["optimizer"],
        **walk)


def control_walk(ctx, family, pool, shapes, ref_first_grad: dict):
    """The control: the reference one precision lower (fp8 operands) put in
    the program's place. Returns its readings and the norm per leaf of its
    first gradient's difference from the reference's. Run by the calibration
    tool and the self-tests, never by a benchmark run."""
    low = reference_walk(ctx, family, pool, shapes, "fp8", keep_first_grad=True)
    diff = refc.leaf_delta_norms({k: jnp.asarray(v) for k, v in low.pop("first_grad").items()},
                                 {k: jnp.asarray(v) for k, v in ref_first_grad.items()})
    return low, {k: float(v) for k, v in diff.items()}


def run(ctx) -> dict:
    family, pool, model, shapes = prepare(ctx)
    st, peak = program_walk(ctx, family, pool, model, shapes)
    del model
    seconds = st["t_close"] - st["t_open"]
    prog = program_readings(st)
    t_ref = time.perf_counter()
    ref = reference_walk(ctx, family, pool, shapes, compare_first_grad=st.pop("first_grad"))
    checks = check.train_checks(prog, ref, ctx.limits, st["win_losses"])
    print(f"reference walk took {time.perf_counter() - t_ref:.1f} s; window losses "
          f"{st['win_losses'][0]:.4f} -> {st['win_losses'][-1]:.4f} over {st['steps']} steps",
          flush=True)
    chips = len(ctx.devices)
    return {
        "end_to_end": {"train_tok_s": st["tokens"] / seconds / chips},
        "counters": {
            "window_s": seconds, "steps": st["steps"], "tokens": st["tokens"],
            "step_ms": 1e3 * seconds / st["steps"],
            "train_flops_per_token": family.train_flops_per_step(ctx.config, ctx.mix, pool[0])
                                     / family.real_target_tokens(pool[0]),
            "flash_calls": family.flash_calls(ctx.config, ctx.mix),
            "chips": chips,
        },
        "checks": checks, "attempted": st["steps"], "failed": 0, "memory_peak_bytes": peak,
    }
