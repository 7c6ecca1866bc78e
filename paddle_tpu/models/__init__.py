"""Model zoo — the ``benchmark/fluid/models`` configs rebuilt TPU-first.

Reference: ``benchmark/fluid/models/{mnist,resnet,se_resnext,vgg,
machine_translation,stacked_dynamic_lstm}.py`` and
``benchmark/fluid/fluid_benchmark.py:310`` (model registry / get_model
protocol). Each module here exposes ``get_model(**cfg) -> ModelSpec`` where
the spec carries a built :class:`paddle_tpu.framework.Model` whose forward
returns ``(loss, metric_or_logits, ...)``, plus a synthetic-batch generator
mirroring the reference's fake-data path
(``fluid_benchmark.py:148-162`` fill-constant feeds).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from paddle_tpu.framework import Model

__all__ = ["ModelSpec", "ServingPrograms", "get_model", "serving_programs", "MODELS"]


@dataclasses.dataclass
class ModelSpec:
    """A runnable benchmark config (get_model protocol)."""

    name: str
    model: Model
    # synth_batch(batch_size, rng) -> tuple of numpy arrays fed to model.apply
    synth_batch: Callable[[int, np.random.RandomState], Tuple[np.ndarray, ...]]
    optimizer: Callable[[], Any]
    unit: str = "examples/sec"
    # elements counted per batch row for throughput (e.g. tokens per sentence)
    examples_per_row: int = 1
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ServingPrograms:
    """What a language model brings to ``serving.DecodeEngine``: the arrays
    its cache lives in and the two jittable programs that write them. The
    engine owns the arrays and hands them to every call donated; a program
    returns the new version of each after its first result.

    ``prefill_chunk(params, tokens [C], pos0, last_index, slot_ref, *cache,
    rng, cfg=, ...) -> (next_token, *cache)`` and ``decode_step(params,
    tokens [S], positions [S], slot_refs, *cache, rng, cfg=, ...) ->
    (next_tokens [S], *cache)``. With ``cache`` ``"pages"`` the arrays are
    pages ``[planes, num_pages, page_size, row]``, planes being the layers
    or, where a stack runs several passes, passes x layers (a K and a V
    array, or one array of latent rows), ``slot_ref`` is a slot's
    page-table row and the programs also take ``page_size=``; with ``"state"`` they are recurrent
    states indexed by slot, ``slot_ref`` is the slot's index, and
    ``slot_refs`` marks the slots that decode. A model with layers of both
    kinds (``models/hybrid_ssm_lm.py``: Mamba-2 layers beside attention
    layers; ``models/hybrid_moe_lm.py``: the same beside expert layers) brings ``"pages+state"``: ``cache_specs`` returns page arrays and
    state arrays, ``state_args`` names the states among ``cache_args``,
    ``slot_ref`` is the pair ``(page-table row, slot index)`` and
    ``slot_refs`` the pair ``(page tables, the mask of decoding slots)``.

    Both programs may return small arrays after the cache, one per name in
    ``extras`` (an expert layer's tokens per expert); the engine reads them
    in the turn in which it reads the tokens and puts
    ``span_attrs(cfg, *extras)`` on the call's span."""

    cache: str                      # "pages" | "state" | "pages+state"
    cache_args: Tuple[str, ...]     # the programs' names for the arrays
    # cache_specs(cfg, *, max_slots, num_pages, page_size, dtype)
    #   -> one jax.ShapeDtypeStruct per array
    cache_specs: Callable[..., Tuple[Any, ...]]
    prefill_chunk: Callable
    decode_step: Callable
    verify_step: Optional[Callable]  # scores a draft block; None = cannot
    mechanism: str                   # named when the engine refuses a feature
    # kv_heads(cfg) -> the heads a page's row holds: a replica group shards
    # pages by whole heads. None for a model without pages, or whose rows
    # are not heads side by side
    kv_heads: Optional[Callable[[dict], int]] = None
    # attends_in_kernel(cfg, pages spec, page_size) -> which of the programs
    # ("step", "chunk") attend over the pages a sequence holds, through a
    # kernel, and not over a gathered table: the family's own rule, asked
    # where the programs are traced. None: none has a kernel form
    attends_in_kernel: Optional[Callable[..., Tuple[str, ...]]] = None
    extras: Tuple[str, ...] = ()     # names of the small outputs after the cache
    # span_attrs(cfg, *extras as numpy) -> {attribute: number}
    span_attrs: Optional[Callable[..., Dict[str, Any]]] = None
    # gauges(cfg) -> {name: number}, published once as serving.decode.<name>
    gauges: Optional[Callable[[dict], Dict[str, float]]] = None
    # with "pages+state": which of cache_args are states indexed by slot
    # (the rest are page arrays). Not read for the two plain kinds
    state_args: Tuple[str, ...] = ()

    @property
    def has_pages(self) -> bool:
        return self.cache in ("pages", "pages+state")

    @property
    def has_state(self) -> bool:
        return self.cache in ("state", "pages+state")

    def is_state(self, arg: str) -> bool:
        """Whether the cache array the programs call ``arg`` is a state."""
        return self.cache == "state" or arg in self.state_args


def serving_programs(cfg: dict) -> ServingPrograms:
    """The serving programs of the model ``cfg`` describes: ``cfg["family"]``
    names the model module, ``transformer_lm`` where it is absent."""
    import importlib

    family = cfg.get("family", "transformer_lm")
    if family not in MODELS:
        raise KeyError(f"unknown model family {family!r} in a serving cfg")
    return importlib.import_module(f"paddle_tpu.models.{family}").serving_programs()


def get_model(name: str, **cfg) -> ModelSpec:
    """Look up and instantiate a benchmark model by reference name."""
    if name not in MODELS:
        raise KeyError(f"unknown model {name!r}; available: {sorted(MODELS)}")
    return MODELS[name](**cfg)


def _mnist(**cfg):
    from paddle_tpu.models import mnist

    return mnist.get_model(**cfg)


def _resnet(**cfg):
    from paddle_tpu.models import resnet

    return resnet.get_model(**cfg)


def _se_resnext(**cfg):
    from paddle_tpu.models import se_resnext

    return se_resnext.get_model(**cfg)


def _vgg(**cfg):
    from paddle_tpu.models import vgg

    return vgg.get_model(**cfg)


def _transformer(**cfg):
    from paddle_tpu.models import transformer

    return transformer.get_model(**cfg)


def _stacked_dynamic_lstm(**cfg):
    from paddle_tpu.models import stacked_lstm

    return stacked_lstm.get_model(**cfg)


def _machine_translation(**cfg):
    from paddle_tpu.models import machine_translation

    return machine_translation.get_model(**cfg)


def _retention_lm(**cfg):
    from paddle_tpu.models import retention_lm

    return retention_lm.get_model(**cfg)


def _latent_moe_lm(**cfg):
    from paddle_tpu.models import latent_moe_lm

    return latent_moe_lm.get_model(**cfg)


def _looped_lm(**cfg):
    from paddle_tpu.models import looped_lm

    return looped_lm.get_model(**cfg)


def _hybrid_ssm_lm(**cfg):
    from paddle_tpu.models import hybrid_ssm_lm

    return hybrid_ssm_lm.get_model(**cfg)


def _hybrid_moe_lm(**cfg):
    from paddle_tpu.models import hybrid_moe_lm

    return hybrid_moe_lm.get_model(**cfg)


def _transformer_lm(**cfg):
    from paddle_tpu.models import transformer_lm

    return transformer_lm.get_model(**cfg)


MODELS: Dict[str, Callable[..., ModelSpec]] = {
    "mnist": _mnist,
    "resnet": _resnet,
    "se_resnext": _se_resnext,
    "vgg": _vgg,
    "transformer": _transformer,
    "transformer_lm": _transformer_lm,
    "retention_lm": _retention_lm,
    "latent_moe_lm": _latent_moe_lm,
    "looped_lm": _looped_lm,
    "hybrid_ssm_lm": _hybrid_ssm_lm,
    "hybrid_moe_lm": _hybrid_moe_lm,
    "stacked_dynamic_lstm": _stacked_dynamic_lstm,
    "machine_translation": _machine_translation,
}
