"""Driver ``serve_closed_experts``: ``serve_closed``'s closed loop, window
and accounting as they are, with the reference walked by layer
(``serve_closed_layerwise.served_gaps``, found by ``serve_closed.run`` the way
``layerwise_reference`` makes it found: the float32 weights do not fit the
chip at once), for a model with an expert layer. Three things are its own:

* the weights are seeded in the form a published checkpoint holds them, a
  matrix an expert (``family.checkpoint_shapes``): the reference reads them
  so, the program's loader stacks them (``family.make_engine``);
* one more number decides ``correct``, ``served_far_share``
  (:func:`far_share`). ``served_gap_sigmas`` is a maximum, and with an
  expert layer its tail is made of single tokens whose eighth and ninth
  expert swapped on rounding; a fault that moves many tokens a little (a
  lower precision, an expert's term lost) shows in the share long before it
  shows in the maximum;
* the counters the expert readers take their shapes from."""

from __future__ import annotations

import contextlib
import importlib

from benchmarks import check
from benchmarks.drivers import serve_closed, serve_closed_layerwise


@contextlib.contextmanager
def swapped(**names):
    """``serve_closed.run`` looks its steps up by name at the call; while
    this is open it finds ``names`` ({name: function}) instead. No file that
    is there is edited, no accounting is copied."""
    whole = {n: getattr(serve_closed, n) for n in names}
    for n, f in names.items():
        setattr(serve_closed, n, f)
    try:
        yield
    finally:
        for n, f in whole.items():
            setattr(serve_closed, n, f)


def checkpoint_weights():
    """``serve_closed.prepare`` with the parameter shapes it returns, which
    every later step seeds its weights from, in the checkpoint's form."""
    prepare = serve_closed.prepare

    def prepared(ctx):
        family, per_client, shapes = prepare(ctx)
        return family, per_client, family.checkpoint_shapes(ctx.config, shapes)

    return swapped(prepare=prepared)


FAR_SIGMAS = 0.1


def far_share(gaps) -> float:
    """Share of served tokens that lie more than :data:`FAR_SIGMAS` standard
    deviations of their position's logits below the reference's best.
    Rounding alone swaps near ties: such a token lies a few hundredths below
    the best (a sound run's 90th percentile is 0.004-0.007, the dense cells'
    maxima 0.012-0.017), and sound runs, the control and planted faults have
    them alike, one token in twenty. A token a tenth off has had another
    term in its residual: a flipped expert, a lost one, a lower precision."""
    return sum(g > FAR_SIGMAS for g in gaps) / len(gaps) if len(gaps) else float("inf")


def run(ctx) -> dict:
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    seen = []

    def recorded(*args, **kwargs):
        seen.append(serve_closed_layerwise.served_gaps(*args, **kwargs))
        return seen[-1]

    with checkpoint_weights(), swapped(served_gaps=recorded):
        result = serve_closed.run(ctx)
    gaps = seen[-1]["f32"]
    result["checks"].append(check.compared(
        "served_far_share", far_share(gaps), ctx.limits["served_far_share"],
        f"of {len(gaps)} served tokens, over {FAR_SIGMAS} sigmas"))
    result["counters"].update(moe_calls=family.moe_calls(ctx.config),
                              max_slots=ctx.mix["engine"]["max_slots"])
    return result
