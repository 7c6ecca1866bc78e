"""What the family builders share: flags, the optimizer from a
configuration's ``optimizer`` group, a Trainer that starts from the
benchmark's weights. This is the only side of the benchmark that imports
the program."""

from __future__ import annotations


def apply_flags(config: dict) -> None:
    from paddle_tpu.core.config import set_flags

    set_flags(**config["flags"])


def make_optimizer(opt: dict):
    import paddle_tpu as pt

    if opt["schedule"] == "constant":
        rate = opt["learning_rate"]
    elif opt["schedule"] == "noam":
        rate = pt.lr_scheduler.NoamDecay(opt["d_model"], opt["warmup_steps"], opt["learning_rate"])
    else:
        raise ValueError(f"unknown schedule {opt['schedule']!r}")
    return pt.optimizer.Adam(learning_rate=rate, beta1=opt["beta1"], beta2=opt["beta2"],
                             epsilon=opt["epsilon"])


def param_shapes(model, batch) -> dict:
    """{name: ShapeDtypeStruct} of the program's parameters, nothing computed."""
    import jax

    shapes = jax.eval_shape(lambda: model.init(0, *batch))
    if shapes.state:
        raise ValueError(f"the benchmark seeds parameters only; model state: {list(shapes.state)}")
    return dict(shapes.params)


def variables_from(weights: dict):
    from paddle_tpu.framework import Variables

    return Variables(dict(weights), {})


def make_trainer(model, config: dict, weights: dict, devices, first_batch):
    """``pt.Trainer`` (its data-parallel form over several ``devices``) whose
    first step starts from ``weights`` and fresh optimizer state."""
    import paddle_tpu as pt

    opt_fn = lambda: make_optimizer(config["optimizer"])
    if len(devices) == 1:
        place = pt.TPUPlace(0) if devices[0].platform == "tpu" else None
        trainer = pt.Trainer(lambda: model, opt_fn, place=place)
        trainer.variables = trainer.exe.put(variables_from(weights))
        trainer.opt_state = trainer.exe.put(
            trainer.optimizer.create_state(trainer.variables.params))
        return trainer
    from paddle_tpu.parallel import DataParallel
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=-1, devices=devices)
    trainer = pt.Trainer(lambda: model, opt_fn, parallel=True, parallel_kwargs={"mesh": mesh})
    # what Trainer._ensure_initialized does, from the benchmark's weights
    trainer._dp = DataParallel(trainer.model, trainer.optimizer, mesh=mesh)
    trainer.variables, trainer.opt_state = trainer._dp.init(
        0, *first_batch, variables=variables_from(weights))
    return trainer
