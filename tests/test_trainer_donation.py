"""The single-device Trainer's step takes its state donated.

``Trainer._compiled_step`` prepares ``optimizer.minimize(model)`` with
``donate_argnums=(0, 1)``: the step consumes the ``variables`` and
``opt_state`` it is handed and every state output takes its input's buffer
(jax 0.9.0 donates on the CPU too). The rule that keeps a bad step's meaning
under donation: the state a step returns is always the state to carry.
Where the program computes ``finite`` it returns the old values on a
non-finite step; an injected ``"nan"`` fault does not run the step at all.
Everything that reads ``trainer.variables`` does so at a step boundary, on
live arrays; an array a caller took from the Trainer is valid until the next
step.
"""

import json
import os

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import checkpoint_sharded as cks
from paddle_tpu import models, tracing
from paddle_tpu.core import logging as ptlog
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.config import flags, set_flags
from paddle_tpu.resilience import ResilienceConfig, faults
from paddle_tpu.trainer import BeginStepEvent, CheckpointConfig, EndStepEvent, Trainer

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "benchmarks", "configs")
OPTIMIZERS = {
    "adam": lambda: pt.optimizer.Adam(learning_rate=0.05),
    "sgd": lambda: pt.optimizer.SGD(learning_rate=0.1),
}


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.clear()
    set_flags(check_nan_inf=False)


def _net(x, y):
    hidden = pt.layers.fc(x, size=8, act="relu")
    return pt.layers.mean((pt.layers.fc(hidden, size=1) - y) ** 2)


def _batches(n, bs=8, seed=0, bad=()):
    """``n`` regression batches; those whose index is in ``bad`` are not
    finite."""
    rng = np.random.RandomState(seed)
    w = np.array([[2.0], [-1.0], [0.5], [3.0]], np.float32)
    out = []
    for i in range(n):
        x = rng.randn(bs, 4).astype(np.float32)
        y = x @ w + 0.1
        out.append((np.full_like(x, np.inf), y) if i in bad else (x, y))
    return out


def _leaves(trainer):
    return jax.tree_util.tree_leaves((trainer.variables, trainer.opt_state))


def _host(trainer):
    """The Trainer's state copied to the host: what a caller who keeps an
    array across a step has to do."""
    return [np.array(leaf) for leaf in _leaves(trainer)]


def _same(a, b):
    return len(a) == len(b) and all(np.array_equal(x, y, equal_nan=True) for x, y in zip(a, b))


class Watch:
    """Event handler that keeps, per step, the leaves the step was handed
    (the arrays themselves), a host copy of the state before and after it,
    the loss and the Trainer's ``global_step`` after it."""

    def __init__(self, trainer):
        self.t = trainer
        self.handed, self.before, self.after, self.losses, self.global_steps = [], [], [], [], []

    def __call__(self, ev):
        if isinstance(ev, BeginStepEvent):
            self.handed.append(_leaves(self.t))
            self.before.append(_host(self.t))
        elif isinstance(ev, EndStepEvent):
            self.after.append(_host(self.t))
            self.losses.append(ev.metrics)
            self.global_steps.append(self.t.global_step)


# ---- the state is consumed, and the gauge says so --------------------------


@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_every_step_consumes_the_state_it_was_handed(opt):
    prof.reset_metrics()
    trainer = Trainer(lambda: _net, OPTIMIZERS[opt])
    watch = Watch(trainer)
    trainer.train(num_epochs=1, reader=lambda: iter(_batches(4)), event_handler=watch)
    # the first step's state came from model.init; every later one from a step
    assert len(watch.handed) == 4
    for handed in watch.handed[:3]:
        assert handed and all(leaf.is_deleted() for leaf in handed)
    assert prof.gauges()["trainer.state_donated"] == 1.0
    assert not any(leaf.is_deleted() for leaf in _leaves(trainer))
    assert not _same(watch.before[0], watch.after[-1])


def test_a_state_leaf_the_step_cannot_consume_reads_zero_and_is_named(monkeypatch):
    prof.reset_metrics()
    warned = []
    monkeypatch.setattr(ptlog, "warning", lambda msg, *a: warned.append(msg % a))
    batches = _batches(2)
    trainer = Trainer(lambda: _net, OPTIMIZERS["sgd"])
    trainer._ensure_initialized(batches[0])
    name = sorted(trainer.variables.params)[0]
    trainer.variables.params[name] = np.asarray(trainer.variables.params[name])  # a host array
    trainer.train(num_epochs=1, reader=lambda: iter(batches))
    assert prof.gauges()["trainer.state_donated"] == 0.0
    (line,) = [w for w in warned if "did not consume" in w]
    assert name in line and line.startswith("trainer step did not consume 1 of ")
    assert np.isfinite(trainer.test(lambda: iter(batches)))


def test_the_compile_span_carries_the_bytes_the_step_aliases():
    tracing.enable_tracing()
    tracing.reset_tracing()
    prev = flags().roofline_memory
    set_flags(roofline_memory="on")  # "auto" compiles ahead of time on a chip only
    try:
        trainer = Trainer(lambda: _net, OPTIMIZERS["adam"])
        trainer.train(num_epochs=1, reader=lambda: iter(_batches(2)))
    finally:
        set_flags(roofline_memory=prev)
    # (a second span, of a millisecond, where jit keyed model.init's arrays
    # apart from a step's: no backend compile, see the test below)
    span = [s for s in tracing.spans()
            if s.name == "executor.compile" and s.attrs.get("target") == "trainer_step"][0]
    state_bytes = sum(leaf.nbytes for leaf in _leaves(trainer))
    assert span.attrs["alias_bytes"] >= state_bytes > 0
    tracing.reset_tracing()


def test_ten_steps_compile_one_program():
    compiles = []
    counting = []

    def on_event(event, duration, **kw):
        if counting and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def handler(ev):
        # from the first step on: model.init's programs are not the step's
        if isinstance(ev, BeginStepEvent) and not counting:
            counting.append(True)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        trainer = Trainer(lambda: _net, OPTIMIZERS["adam"])
        trainer.train(num_epochs=1, reader=lambda: iter(_batches(10)), event_handler=handler)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert trainer.global_step == 10
    assert len(compiles) == 1


# ---- the train programs of the repo alias the state they are handed -------


def _cell_kwargs(config, seq_len):
    """A benchmark configuration's model as its train cells build it."""
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        config = json.load(f)
    return dict(config["model"], **config["train"], seq_len=seq_len)


@pytest.mark.parametrize("name, kwargs, bs", [
    ("resnet", lambda: dict(dataset="flowers", depth=50, class_dim=1000), 2),
    ("transformer_lm", lambda: _cell_kwargs("lm_big", 2048), 1),
    ("transformer", lambda: _cell_kwargs("nmt_big", 64), 2),
], ids=["resnet50", "lm_large", "nmt_big"])
def test_a_real_train_step_aliases_its_state_as_the_trainer_prepares_it(name, kwargs, bs):
    """The step as ``Trainer._compiled_step`` prepares it, compiled from
    shapes at the sizes the train cells run (and ResNet-50, which has no
    cell yet): the program must alias at least the parameters' bytes to its
    outputs, or a step holds parameters and optimizer state twice and the
    memory a cell reports from ``memory_analysis()`` is not what it needs."""
    spec = models.get_model(name, **kwargs())
    batch = spec.synth_batch(bs, np.random.RandomState(0))
    opt = spec.optimizer()
    variables = jax.eval_shape(lambda: spec.model.init(0, *batch))
    opt_state = jax.eval_shape(opt.create_state, variables.params)
    step = pt.Executor().prepare(
        opt.minimize(spec.model), donate_argnums=(0, 1), key=("trainer_step", name))
    compiled = step.lower(variables, opt_state, *batch).compile()
    param_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                      for p in jax.tree_util.tree_leaves(variables.params))
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes > 0
    assert mem.argument_size_in_bytes > param_bytes  # + optimizer state and batch
    assert mem.alias_size_in_bytes >= param_bytes
    # what the runtime enforces
    assert "input_output_alias" in compiled.as_text()


# ---- the arithmetic is the undonated step's --------------------------------


@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_five_steps_equal_the_undonated_step_walked_by_hand(opt):
    batches = _batches(5)
    trainer = Trainer(lambda: _net, OPTIMIZERS[opt])
    watch = Watch(trainer)
    trainer.train(num_epochs=1, reader=lambda: iter(batches), event_handler=watch)

    model = pt.build(_net)
    optimizer = OPTIMIZERS[opt]()
    variables = model.init(0, *batches[0])
    opt_state = optimizer.create_state(variables.params)
    step = jax.jit(optimizer.minimize(model))
    losses = []
    for batch in batches:
        handed = variables
        out = step(variables, opt_state, *[jnp.asarray(b) for b in batch])
        variables, opt_state = out.variables, out.opt_state
        losses.append(float(out.loss))
        assert not any(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(handed))
    assert watch.losses == losses
    by_hand = [np.array(leaf) for leaf in jax.tree_util.tree_leaves((variables, opt_state))]
    assert _same(_host(trainer), by_hand)


# ---- a bad step keeps its meaning ------------------------------------------


@pytest.mark.parametrize("parallel", [False, True], ids=["one_device", "data_parallel"])
@pytest.mark.parametrize("fault", ["non_finite_batch", "injected_nan"])
def test_skip_step_leaves_the_state_bit_identical_and_the_next_step_trains(fault, parallel):
    """``DataParallel`` donates by default (``donate=True``): before the
    step kept the old values itself, a skipped step left ``trainer.variables``
    pointing at deleted arrays there."""
    if fault == "non_finite_batch":
        set_flags(check_nan_inf=True)  # read when the step is traced
        batches = _batches(5, bad={2})
    else:
        batches = _batches(5)
        faults.install(faults.FaultSpec(faults.TRAINER_STEP, "nan", after=2, times=1))
    trainer = Trainer(lambda: _net, OPTIMIZERS["adam"], parallel=parallel,
                      resilience=ResilienceConfig(nan_policy="skip_step"))
    watch = Watch(trainer)
    trainer.train(num_epochs=1, reader=lambda: iter(batches), event_handler=watch)
    assert trainer.bad_steps == 1
    assert watch.global_steps == [1, 2, 2, 3, 4]
    assert np.isnan(watch.losses[2]) and all(np.isfinite(watch.losses[:2] + watch.losses[3:]))
    # parameters, Adam's moments and its step counter, bit for bit
    assert _same(watch.before[2], watch.after[2])
    assert not _same(watch.before[3], watch.after[3])
    assert not any(leaf.is_deleted() for leaf in _leaves(trainer))
    assert int(trainer.opt_state.step) == 4
    if fault == "non_finite_batch":
        # the bad step ran, and consumed what it was handed all the same
        assert all(leaf.is_deleted() for leaf in watch.handed[2])


def test_the_raise_policy_leaves_the_state_the_step_was_handed():
    set_flags(check_nan_inf=True)
    batches = _batches(3, bad={1})
    trainer = Trainer(lambda: _net, OPTIMIZERS["adam"])
    watch = Watch(trainer)
    with pytest.raises(pt.core.enforce.EnforceError, match="check_nan_inf"):
        trainer.train(num_epochs=1, reader=lambda: iter(batches), event_handler=watch)
    assert trainer.global_step == 1
    assert _same(watch.before[1], _host(trainer))
    trainer.train(num_epochs=1, reader=lambda: iter(batches[2:]), event_handler=watch)
    assert trainer.global_step == 2 and np.isfinite(watch.losses[-1])


def test_rollback_restores_the_checkpoint_and_trains_on(tmp_path):
    set_flags(check_nan_inf=True)
    trainer = Trainer(
        lambda: _net, OPTIMIZERS["adam"],
        checkpoint_config=CheckpointConfig(str(tmp_path / "ckpt"), step_interval=1,
                                           max_num_checkpoints=8),
        resilience=ResilienceConfig(nan_policy="rollback", rollback_after=2, max_rollbacks=2),
    )
    watch = Watch(trainer)
    trainer.train(num_epochs=1, reader=lambda: iter(_batches(6, bad={2, 3})),
                  event_handler=watch)
    assert (trainer.bad_steps, trainer.rollbacks, trainer.global_step) == (2, 1, 4)
    # the second bad step's end sees the restored state: step 2's checkpoint
    assert _same(watch.after[3], watch.after[1])
    assert not any(leaf.is_deleted() for leaf in _leaves(trainer))
    assert all(np.isfinite(watch.losses[4:])) and not _same(watch.after[3], watch.after[5])


# ---- whatever reads the state reads it live, at a step boundary ------------


def test_an_async_step_checkpoint_holds_the_state_of_its_step(tmp_path):
    """``save_sharded_async`` copies to the host before it returns, so the
    next step may consume the arrays while the writer thread is at work."""
    root = str(tmp_path / "ckpt")
    trainer = Trainer(
        lambda: _net, OPTIMIZERS["adam"],
        checkpoint_config=CheckpointConfig(root, step_interval=2, epoch_interval=100,
                                           sharded=True, async_save=True),
    )
    watch = Watch(trainer)
    trainer.train(num_epochs=1, reader=lambda: iter(_batches(3)), event_handler=watch)
    assert cks.wait_pending_save() is None
    like = (trainer.variables, trainer.opt_state)
    restored, meta = cks.load_sharded(root, like)
    assert int(meta["step"]) == 2
    at_step_two = [np.array(leaf) for leaf in jax.tree_util.tree_leaves(restored)]
    assert _same(at_step_two, watch.after[1]) and not _same(at_step_two, watch.after[2])


def test_preemption_saves_live_arrays_and_a_new_trainer_resumes(tmp_path):
    root = str(tmp_path / "ckpt")
    make = lambda: Trainer(lambda: _net, OPTIMIZERS["adam"],
                           checkpoint_config=CheckpointConfig(root, step_interval=100))
    trainer = make()
    faults.install(faults.FaultSpec(faults.TRAINER_STEP, "preempt", after=2, times=1))
    trainer.train(num_epochs=1, reader=lambda: iter(_batches(5)))
    assert trainer.preempted and trainer.global_step == 3
    saved = _host(trainer)
    faults.clear()
    resumed = make()
    resumed._ensure_initialized(_batches(1)[0])
    assert resumed.global_step == 3 and _same(_host(resumed), saved)


def test_test_evaluate_and_save_params_read_live_arrays(tmp_path):
    batches = _batches(3)
    trainer = Trainer(lambda: _net, OPTIMIZERS["adam"])
    trainer.train(num_epochs=1, reader=lambda: iter(batches))
    assert np.isfinite(trainer.test(lambda: iter(batches)))
    per_row = trainer.evaluate(lambda: iter(batches),
                               lambda out, x, y: np.full((x.shape[0],), float(out)))
    assert np.isfinite(per_row)
    trainer.save_params(str(tmp_path / "params"))
    loaded = pt.io.load_params(str(tmp_path / "params"))
    assert _same([np.array(v) for v in jax.tree_util.tree_leaves(loaded)],
                 [np.array(v) for v in jax.tree_util.tree_leaves(trainer.variables)])
    # and the Trainer trains on from them
    trainer.train(num_epochs=2, reader=lambda: iter(batches))
    assert trainer.global_step == 9


# ---- the benchmark's check still catches a step that changes nothing -------


def test_a_step_that_returns_a_copy_of_what_it_was_handed_is_not_correct(tmp_path, monkeypatch):
    """``tests/benchmarks/test_perfbench_runs.py`` freezes the state by
    returning ``self.variables`` as read after the step: arrays the donated
    step has consumed. The fault it stands for, a step that leaves the
    parameters as they were, is a copy taken before the step."""
    import time

    from benchmarks import harness, tiny

    real = pt.Trainer._run_step

    def frozen(self, batch):
        kept = jax.tree_util.tree_map(jnp.array, self.variables)
        return real(self, batch)._replace(variables=kept)

    monkeypatch.setattr(pt.Trainer, "_run_step", frozen)
    root = tiny.make_root(str(tmp_path / "bench_root"))
    loaded = harness.load_cell("lm_tiny.train_rows", root)
    line = harness.execute(loaded, jax.devices()[:1], 2**31 + 11, 0.5, False, time.perf_counter())
    assert line["correct"] is False
