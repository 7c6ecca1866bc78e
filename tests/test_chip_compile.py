"""Compiles of the main path at real size for a described TPU v5e.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached. These are the programs of the
benchmark's ``lm_big`` cells — the flash kernel and the train step of
``lm_big.train_2k``, the paged serving steps of ``lm_big.serve_long``, the
four-chip data-parallel step of ``lm_big.train_2k_dp4`` — and the two
serving programs of its ``brumby_14b``, ``sarvam_105b``, ``ouro_2_6b``,
``granite_4_0_h_micro`` and ``nemotron_3_super_120b_a12b`` cells, so what
the chip's compiler would refuse (a kernel that cannot be partitioned, a
program that does not fit HBM) fails here, at no chip time. Nothing runs:
a passing compile says nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this
file. The tests stay in this one file for the same reason.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu import models
from paddle_tpu.core.config import flags, set_flags

HBM_BYTES = 15.75 * 2**30  # what one v5e chip's allocator reports usable
# lm_big's widths (benchmarks/configs/lm_big.json) at the default vocabulary:
# the 512-wide default underfills the MXU; one scanned body is one Mosaic
# flash forward and backward to compile, not 12
LM_LARGE_KWARGS = dict(
    seq_len=2048, d_model=1024, d_inner=4096, num_heads=16, n_layers=12,
    max_len=2048, scan_layers=True,
)
# the engine PR 22 brought up: DecodeConfig(max_slots=16, page_size=16,
# max_context=2048), default prefill_chunk
SLOTS, PAGE, CONTEXT, CHUNK = 16, 16, 2048, 32


def _serve_long_engine() -> dict:
    """lm_big.serve_long's own engine, as the cell's traffic file has it
    today (48 slots, chunks of 512 on PR 48's tree): read, not pinned, so a
    PR that changes the cell's engine compiles the new one here."""
    import json

    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks", "traffic",
                           "serve_long.json")) as f:
        return json.load(f)["engine"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the code that asks ``jax.default_backend()`` (interpret-mode
    selection in ops/pallas/flash_attention.py) onto its TPU branch, with
    the lm_big cells' flags on and the persistent compile cache off: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = (flags().use_flash_attention, flags().use_bf16_compute)
    prev_cache = jax.config.jax_enable_compilation_cache
    set_flags(use_flash_attention=True, use_bf16_compute=True)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    set_flags(use_flash_attention=prev[0], use_bf16_compute=prev[1])


def _shapes(tree, sharding):
    """ShapeDtypeStructs placed by ``sharding`` (one, or a matching tree):
    ``jax.device_put`` to a described device fails."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _lm_large():
    return models.get_model("transformer_lm", **LM_LARGE_KWARGS)


def _abstract_state(spec, batch):
    v = jax.eval_shape(lambda: spec.model.init(0, *batch))
    opt = spec.optimizer()
    return opt, v, jax.eval_shape(opt.create_state, v.params)


# lm_big.train_2k's call, whose heads fit VMEM (resident forms, the table's
# blocks), and 8 MB of K/V a head, which do not (streamed forms, ruled blocks)
CELL_SHAPE, LONG_SHAPE = (4, 16, 2048, 64), (1, 4, 16384, 128)


def _mosaic_calls(text):
    """A compiled program's Mosaic calls, by instruction name."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [line.split(" = ", 1)[0].replace("ROOT", "").strip(" %").rstrip("_.0123456789")
            for line in calls]


def _results(lines):
    """``[(name, what follows " = ")]`` of the instructions among ``lines``
    of a compiled text: the second starts with the result's shape and layout."""
    return [tuple(l.strip().split(" = ", 1)) for l in lines if " = " in l]


def _copies_of(text, *shapes):
    """The ``copy`` instructions of a compiled text whose result is one of
    ``shapes`` (``"bf16[48,2048,2048]"``): a whole array moved, cut short."""
    return [f"{name} = {rest[:100]}" for name, rest in _results(text.splitlines())
            if rest.startswith(shapes) and " copy(" in rest]


def _flash_calls(shape, backward, one_chip):
    from paddle_tpu.ops.pallas import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    fn = jax.grad(fwd, argnums=(0, 1, 2)) if backward else fwd
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    return _mosaic_calls(jax.jit(fn).lower(x, x, x).compile().as_text())


@pytest.mark.parametrize("shape, form", [(CELL_SHAPE, "resident"), (LONG_SHAPE, "streamed")],
                         ids=["cell_2k", "long_16k"])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles(one_chip, as_tpu, backward, shape, form):
    """Forward, and forward with the fused backward, compile for the chip
    with the blocks the code resolves and inside the ``vmem_limit_bytes`` it
    sets: the cell's shape from the table in the resident forms, a head
    that does not fit VMEM by the rule in the streamed forms."""
    import importlib

    from paddle_tpu.core import profiler as prof

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fa.take_resolved()
    before = prof.counters()
    named = _flash_calls(shape, backward, one_chip)
    # forward only: one kernel; with the fused backward: fwd + dkv + dq
    assert len(named) >= (3 if backward else 1), named
    resolved = fa.take_resolved()
    assert len(resolved) == (3 if backward else 1), resolved
    source = "table" if shape == CELL_SHAPE else "rule"
    assert all(v.endswith(f" {source} {form}") for v in resolved.values()), resolved
    grew = prof.counters().get(f"flash.form.{form}", 0) - before.get(f"flash.form.{form}", 0)
    assert grew == len(resolved)


@pytest.mark.parametrize("kernel, shape, backward", [
    ("flash_fwd_resident", CELL_SHAPE, False),  # K/V of a head fit VMEM
    ("flash_bwd_dkv_resident", CELL_SHAPE, True),  # and so do Q and dO
    ("flash_bwd_dq_resident", CELL_SHAPE, True),
    ("flash_fwd", LONG_SHAPE, False),  # 8 MB of K/V a head: streamed
    ("flash_bwd_dkv", LONG_SHAPE, True),
    ("flash_bwd_dq", LONG_SHAPE, True),
])
def test_flash_kernels_carry_their_names(one_chip, as_tpu, kernel, shape, backward):
    """Each ``pallas_call`` has a ``name=``: the compiled instruction is
    called after it, so a device trace tells the kernels, and the resident
    forms from the streamed ones, apart."""
    named = _flash_calls(shape, backward, one_chip)
    assert any(n.endswith(kernel) for n in named), named


def test_flash_kv_len_fits_smem_at_128_pairs(one_chip, as_tpu):
    """``kv_len`` reaches the kernels as one 1-D scalar-prefetch array of B
    lengths: at 128 pairs x 16 heads the [B*H, 1] form it had overflowed
    SMEM in the backward (PERF.md, PR 24)."""
    from paddle_tpu.ops.pallas import flash_attention

    def loss(q, k, v, kv_len):
        return flash_attention(q, k, v, kv_len=kv_len, block_q=64, block_k=64
                               ).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((128, 16, 64, 64), jnp.bfloat16, sharding=one_chip)
    n = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x, n).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_lm_large_train_step_compiles(one_chip, as_tpu):
    """The Trainer's step (no donation) at batch 2: the flash kernel is in
    it — neither interpret mode nor the fall-through to XLA attention —
    and the program fits the chip."""
    spec = _lm_large()
    batch = spec.synth_batch(2, np.random.RandomState(0))
    opt, v, o = _abstract_state(spec, batch)
    compiled = jax.jit(opt.minimize(spec.model)).lower(
        _shapes(v, one_chip), _shapes(o, one_chip), *_shapes(batch, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("engine, program_limit", [
    (dict(max_slots=SLOTS, page_size=PAGE, max_context=CONTEXT, prefill_chunk=CHUNK), 4.5e9),
    (dict(max_slots=32, page_size=PAGE, max_context=CONTEXT, prefill_chunk=CHUNK), 8.0e9),
    (_serve_long_engine(), HBM_BYTES),
], ids=["16", "32", "serve_long"])
@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_paged_serving_steps_alias_their_pages(one_chip, as_tpu, which, engine, program_limit):
    """lm_big.serve_long's engine as its traffic file has it (on PR 48's
    tree 48 slots x 2048 positions of f32 pages, 4.83 GB each for K and V,
    filled in chunks of 512), and the two the
    path was brought up on in chunks of 32: 16 slots (1.6 GB each), and 32,
    which the chip refused (22.1 GB) while a program converted its pages.
    The engine donates them, so the compiled program
    must alias both to its outputs (undonated, PR 22 read alias 0), and it
    must take them as the model spells them,
    ``[L, num_pages, page_size, H_kv * dh]`` with the row of all heads
    minor-most: no whole-array copy on entry or exit (with heads an axis of
    their own and ``dh`` 64 the chip kept the page axis minor-most and each
    program converted K and V both ways: 9.7 GB of temp, 14.0 GB in all,
    PERF.md PR 26 and 30), no layer's slice of one before the gather (the
    gather indexes layer and page together), nor XLA's
    ``remat_(un)compressed`` re-lay-outs around the 24 page writes."""
    import re

    from paddle_tpu.models.transformer_lm import (
        paged_cache_shape, paged_decode_step, paged_prefill_chunk,
    )
    from paddle_tpu.serving import DecodeConfig

    dconf = DecodeConfig(**engine)  # the defaults of what a cell leaves out
    slots, page, chunk = dconf.max_slots, dconf.page_size, dconf.prefill_chunk
    spec = _lm_large()
    cfg = dict(spec.extra["cfg"], scan_layers=False)
    params = jax.eval_shape(
        lambda: spec.model.init(0, *spec.synth_batch(1, np.random.RandomState(0)))
    ).params
    per_slot = dconf.max_context // page
    num_pages = 1 + slots * per_slot if dconf.num_pages is None else dconf.num_pages
    pages = jax.ShapeDtypeStruct(
        paged_cache_shape(cfg, num_pages, page), jnp.float32, sharding=one_chip)
    assert pages.shape == (12, num_pages, page, 1024)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if which == "decode_step":
        fn, args = paged_decode_step, (i32(slots), i32(slots), i32(slots, per_slot))
    else:
        fn, args = paged_prefill_chunk, (i32(chunk), i32(), i32(), i32(per_slot))
    compiled = jax.jit(
        functools.partial(fn, cfg=cfg, page_size=page),
        donate_argnames=("k_pages", "v_pages"),  # as DecodeEngine.__init__
    ).lower(_shapes(params, one_chip), *args, pages, pages, None).compile()
    text = compiled.as_text()
    page_bytes = int(np.prod(pages.shape)) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * page_bytes
    assert "remat_" not in text
    whole, layer = ("f32[" + ",".join(str(d) for d in shape) + "]"
                    for shape in (pages.shape, pages.shape[1:]))
    page_array_copies = _copies_of(text, whole)
    assert not page_array_copies, page_array_copies[:2]
    layer_slices = [name for name, rest in _results(text.splitlines()) if rest.startswith(layer)]
    assert not layer_slices, layer_slices[:2]
    # both page parameters enter (and leave) in the order they are spelled
    entry = next(l for l in text.splitlines() if "entry_computation_layout" in l)
    layouts = re.findall(re.escape(whole) + r"\{([\d,]+)", entry)
    assert len(layouts) == 4 and set(layouts) == {"3,2,1,0"}, layouts
    # the step attends through the kernel, once a layer, and gathers nothing;
    # the chunk keeps the gather
    assert _mosaic_calls(text) == ["paged_attend_step"] * (12 if which == "decode_step" else 0)
    # 16 slots read 0.02 / 0.03 GB of temp in 4.11 / 4.12 GB; 32 slots
    # 0.02 / 0.03 GB in 7.33 / 7.34 GB; serve_long's 48 with its chunk of
    # 512 0.02 / 0.07 GB in 10.55 / 10.60 GB. The step's gathered context
    # was its temp until PR 36: 0.69 GB in 4.78 at 16 slots, 1.36 GB in 8.67
    # at 32, three sevenths of a page array at any size. The two shapes the
    # path was brought up on keep the limits they had; the cell's engine is
    # held to what follows from its shapes, whatever they become: a
    # sixteenth of a page array of temp (0.1 GB at 16 slots), the program
    # inside the chip
    temp_limit = 0.1e9 if program_limit < HBM_BYTES else page_bytes / 16
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
    assert _program_bytes(compiled) < program_limit <= HBM_BYTES


# sha256 of the StableHLO text ``lm_big``'s two serving programs lower to (16
# slots x 2048, chunk 32, float32 pages), taken on PR 30's tree: PR 31 gave
# ``_paged_attend`` a core and a row form as arguments for the latent cache,
# and the K and V path had to stay the program it was, byte for byte. A PR
# that means to change these programs replaces the digests and says so.
LM_BIG_TEXT = {
    "decode_step": "01706439647abc12d06cbccc558c445d323c31acd94e5d34704e7f7b341d2953",
    "prefill_chunk": "ca1ddf2ca818330a02eed971dcf8a0e582ef0363fb13f091f7996b57c2bfe9ef",
}


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_lm_big_serving_programs_lower_to_the_text_they_had(which):
    import hashlib

    from paddle_tpu.models.transformer_lm import (
        paged_cache_shape, paged_decode_step, paged_prefill_chunk,
    )

    spec = _lm_large()
    cfg = dict(spec.extra["cfg"], scan_layers=False)
    params = jax.eval_shape(
        lambda: spec.model.init(0, *spec.synth_batch(1, np.random.RandomState(0)))
    ).params
    per_slot = CONTEXT // PAGE
    pages = jax.ShapeDtypeStruct(paged_cache_shape(cfg, 1 + SLOTS * per_slot, PAGE), jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if which == "decode_step":
        fn, args = paged_decode_step, (i32(SLOTS), i32(SLOTS), i32(SLOTS, per_slot))
    else:
        fn, args = paged_prefill_chunk, (i32(CHUNK), i32(), i32(), i32(per_slot))
    text = jax.jit(functools.partial(fn, cfg=cfg, page_size=PAGE),
                   donate_argnames=("k_pages", "v_pages"),
                   ).lower(params, *args, pages, pages, None).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LM_BIG_TEXT[which]


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_sarvam_serving_steps_fit_the_chip_and_alias_their_latent_pages(one_chip, as_tpu, which):
    """The cell sarvam_105b.serve_docs32 at its own shapes: the dense layer
    and four expert layers of 32 held experts at the published widths in
    bfloat16 (9.07 GB) beside 32 slots x 16384 positions of latent rows
    (3.36 GB: a row of 576 in 640 lanes). The engine donates the one page
    array, so each program must alias it to its output, must take it as the
    model spells it (a row of 576 was kept page-minor by the chip and
    converted whole at every layer: 4.7 GB of temp), must hold the
    ``moe_gmm`` kernel three times an expert layer and, once a layer, the
    kernel that attends over the pages a sequence holds
    (``latent_attend_step`` / ``latent_attend_chunk``: no table is gathered
    and no score over all 16384 positions is kept), and must fit the chip."""
    import json
    import re

    from benchmarks.families import latent_moe_lm as family
    from paddle_tpu.models import latent_moe_lm

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(here, "configs", "sarvam_105b.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", "serve_docs32.json")) as f:
        engine = json.load(f)["engine"]
    cfg = dict(latent_moe_lm.BASE_CFG, **family.model_cfg(config))
    assert cfg["max_len"] == engine["max_context"] and cfg["experts_held"] == (0, 32)
    progs = models.serving_programs(cfg)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {k: bf16(shape) for k, shape in latent_moe_lm.param_shapes(cfg).items()}
    slots, page = engine["max_slots"], engine["page_size"]
    per_slot = engine["max_context"] // page
    (spec,) = progs.cache_specs(cfg, max_slots=slots, num_pages=1 + slots * per_slot,
                                page_size=page, dtype=jnp.dtype(engine["cache_dtype"]))
    assert spec.shape == (5, 1 + 32 * 1024, 16, 640) and spec.dtype == jnp.bfloat16
    pages = jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if which == "decode_step":
        fn, args = progs.decode_step, (i32(slots), i32(slots), i32(slots, per_slot))
    else:
        fn, args = progs.prefill_chunk, (i32(engine["prefill_chunk"]), i32(), i32(),
                                         i32(per_slot))
    compiled = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                       donate_argnames=progs.cache_args,
                       ).lower(params, *args, pages, None).compile()
    text = compiled.as_text()
    page_bytes = 2 * int(np.prod(pages.shape))
    weight_bytes = 2 * sum(int(np.prod(p.shape)) for p in params.values())
    assert 3.3e9 < page_bytes < 3.4e9 and 9.0e9 < weight_bytes < 9.1e9
    assert compiled.memory_analysis().alias_size_in_bytes >= page_bytes
    whole = "bf16[" + ",".join(str(d) for d in pages.shape) + "]"
    page_array_copies = _copies_of(text, whole)
    assert not page_array_copies and "remat_" not in text, page_array_copies[:2]
    entry = next(l for l in text.splitlines() if "entry_computation_layout" in l)
    layouts = re.findall(re.escape(whole) + r"\{([\d,]+)", entry)
    assert len(layouts) == 2 and set(layouts) == {"3,2,1,0"}, layouts
    attend = "latent_attend_step" if which == "decode_step" else "latent_attend_chunk"
    assert sorted(_mosaic_calls(text)) == [attend] * 5 + ["moe_gmm"] * 3 * 4
    # beside 12.43 GB of arguments the step holds 0.114 GB of temp and the
    # chunk 0.259 (its queries as rows, 42 MB, and their context, 67 MB, a
    # layer); with the gathered table and its scores they held 0.84 and 0.71
    assert _program_bytes(compiled) < HBM_BYTES - 2.0e9
    assert compiled.memory_analysis().temp_size_in_bytes < (
        0.2e9 if which == "decode_step" else 0.4e9)


def _ouro_program(one_chip, which):
    """``(compiled, pages, params)``: a serving program of the cell
    ouro_2_6b.serve_reason8 at its own shapes, nothing cut, compiled as the
    engine jits it (the page arrays donated)."""
    import json

    from benchmarks.families import looped_lm as family
    from paddle_tpu.models import looped_lm

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(here, "configs", "ouro_2_6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", "serve_reason8.json")) as f:
        engine = json.load(f)["engine"]
    cfg = dict(looped_lm.BASE_CFG, **family.model_cfg(config))
    assert cfg["max_len"] == engine["max_context"] and looped_lm.planes(cfg) == 192
    progs = models.serving_programs(cfg)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {k: bf16(shape) for k, shape in looped_lm.param_shapes(cfg).items()}
    assert params["layers/ffn/fc1/w"].shape == (48, 2048, 5632)
    slots, page = engine["max_slots"], engine["page_size"]
    per_slot = engine["max_context"] // page
    k_spec, v_spec = progs.cache_specs(cfg, max_slots=slots, num_pages=1 + slots * per_slot,
                                       page_size=page, dtype=jnp.dtype(engine["cache_dtype"]))
    assert k_spec.shape == v_spec.shape == (192, 321, 16, 2048) and k_spec.dtype == jnp.bfloat16
    pages = jax.ShapeDtypeStruct(k_spec.shape, k_spec.dtype, sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if which == "decode_step":
        fn, args = progs.decode_step, (i32(slots), i32(slots), i32(slots, per_slot))
    else:
        chunk = engine["prefill_chunk"]
        assert (chunk, -(-engine["max_context"] // chunk) * chunk // page) == (192, 48)
        fn, args = progs.prefill_chunk, (i32(chunk), i32(), i32(), i32(48))
    compiled = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                       donate_argnames=progs.cache_args,
                       ).lower(params, *args, pages, pages, None).compile()
    return compiled, pages, params


STACKED_PROJ = "bf16[48,2048,2048]"  # ouro_2_6b's layers/attn/{q,k,v,out}/w


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_ouro_serving_steps_fit_the_chip_and_keep_their_pages_in_place(one_chip, as_tpu, which):
    """The cell ouro_2_6b.serve_reason8 at its own shapes, nothing cut: 48
    layers at the published widths in bfloat16 (5.34 GB) run four passes,
    beside 8 slots x 640 positions of K and V pages in 192 planes (2 x 4.04
    GB). The page arrays ride the carry of the passes' loop and of the
    layers' loop inside it, and are both written and gathered in its body:
    they must stay in place (no whole-array copy, aliased to the outputs,
    held as the model spells them). So must the stacked projections: none is
    copied or held transposed, so the temporaries stay under 50 MB and
    arguments plus temporaries fit the chip. The chunk of 192 does not
    divide the context of 640: its table row is the engine's, lengthened to
    768 positions."""
    import re

    compiled, pages, params = _ouro_program(one_chip, which)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    page_bytes = 2 * int(np.prod(pages.shape))
    weight_bytes = 2 * sum(int(np.prod(p.shape)) for p in params.values())
    assert page_bytes == 4_039_114_752 and 5.33e9 < weight_bytes < 5.34e9
    assert mem.alias_size_in_bytes >= 2 * page_bytes
    whole = "bf16[" + ",".join(str(d) for d in pages.shape) + "]"
    copies = _copies_of(text, whole, STACKED_PROJ)
    assert not copies, copies[:2]
    assert STACKED_PROJ + "{1,2,0" not in text
    entry = next(l for l in text.splitlines() if "entry_computation_layout" in l)
    layouts = re.findall(re.escape(whole) + r"\{([\d,]+)", entry)
    assert len(layouts) == 4 and set(layouts) == {"3,2,1,0"}, layouts
    print(which, "temp", mem.temp_size_in_bytes, "arguments", mem.argument_size_in_bytes)
    # beside 13.41 GB of arguments the step holds 0.5 MB of temp and the chunk
    # 1.1 MB. Before PR 47 both held 0.81 GB: layers/attn/q/w and k/w, 403 MB
    # each, copied whole to {1,2,0} in the entry computation at every call,
    # because the split into heads had sunk from the projection onto its weight
    assert mem.temp_size_in_bytes < 0.05e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # the passes are one traced body and the layers another: two loops, whatever the passes
    assert text.count(" while(") == 2
    # the step attends through the kernel, once in the layers' body, the
    # plane a traced scalar; the chunk keeps the gather
    assert _mosaic_calls(text) == ["paged_attend_step"] * (which == "decode_step")


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_ouro_projections_read_their_stacked_weights_inside_the_matmul(one_chip, as_tpu, which):
    """In the layers' loop of ouro_2_6b.serve_reason8's programs the q, k, v
    and out weights are the loop's own tuple elements, whole stacks, and each
    is an operand of the fusion that multiplies it (a ``kOutput`` fusion: the
    layer's slice is taken inside, under the matmul). No instruction of the
    body itself yields one layer's weight for another to multiply: that was
    the two weights' one read from HBM done as a copy of its own, 384 times a
    step, before PR 47."""
    import re

    text = _ouro_program(one_chip, which)[0].as_text()
    computations, name = {}, None
    for line in text.splitlines():
        opens = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if opens:
            name = opens.group(1)
            computations[name] = []
        elif name is not None and not line.startswith("}"):
            computations[name].append(line)
    bodies = [computations[b] for b in re.findall(r" while\(.*?body=%([\w.\-]+)", text)]
    (layers,) = [b for b in bodies if not any(" while(" in l for l in b)]  # the inner loop
    layers = _results(layers)
    a_layer = [n for n, rest in layers if re.match(r"bf16\[(1,)?2048,2048\]", rest)]
    assert not a_layer, a_layer
    stacks = [n for n, rest in layers
              if rest.startswith(STACKED_PROJ + "{2,1,0") and " get-tuple-element(" in rest]
    assert len(stacks) == 4, stacks
    for stack in stacks:
        uses = [rest for n, rest in layers
                if re.search(re.escape(stack) + r"[,)]", rest) and not n.startswith("ROOT")]
        assert len(uses) == 1 and " fusion(" in uses[0] and "kind=kOutput" in uses[0], uses


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_granite_serving_steps_fit_the_chip_and_alias_pages_and_states(one_chip, as_tpu, which):
    """The cell granite_4_0_h_micro.serve_chat64 at its own shapes, nothing
    cut: 40 layers at the published widths in bfloat16 (6.38 GB) beside 64
    slots of SSM state and convolution tails (4.95 GB, float32) and 64 x 3072
    positions of bfloat16 K and V pages in 4 planes (1.61 GB): 12.95 GB of
    arguments. The engine donates all four cache arrays: each program must
    alias every one to its output, copy none of them whole, hold each as the
    model spells it, and fit the chip beside its temporaries (the step 0.04
    GB, the chunk 0.60 GB by this compile). The step holds the ``ssm_step``
    kernel once a Mamba-2 layer and ``paged_attend_step`` once an attention
    layer; the chunk holds neither (its scan and its gather are XLA's)."""
    import json
    import re

    from benchmarks.families import hybrid_ssm_lm as family
    from paddle_tpu.models import hybrid_ssm_lm as hm

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(here, "configs", "granite_4_0_h_micro.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", "serve_chat64.json")) as f:
        engine = json.load(f)["engine"]
    cfg = dict(hm.BASE_CFG, **family.model_cfg(config))
    assert cfg["max_len"] == engine["max_context"] == 3072
    progs = models.serving_programs(cfg)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {k: bf16(shape) for k, shape in hm.param_shapes(cfg).items()}
    slots, page = engine["max_slots"], engine["page_size"]
    per_slot = engine["max_context"] // page
    specs = progs.cache_specs(cfg, max_slots=slots, num_pages=1 + slots * per_slot,
                              page_size=page, dtype=jnp.dtype(engine["cache_dtype"]))
    assert [s.shape for s in specs] == [(4, 12289, 16, 512)] * 2 + [
        (36, 64, 128, 4096), (36, 64, 3 * 4352)]
    cache = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip) for s in specs]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if which == "decode_step":
        fn, args = progs.decode_step, (i32(slots), i32(slots), (i32(slots, per_slot), i32(slots)))
    else:
        fn, args = progs.prefill_chunk, (i32(engine["prefill_chunk"]), i32(), i32(),
                                         (i32(per_slot), i32()))
    compiled = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                       donate_argnames=progs.cache_args,
                       ).lower(params, *args, *cache, None).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    cache_bytes = sum(nbytes(c) for c in cache)
    weight_bytes = sum(nbytes(p) for p in params.values())
    assert weight_bytes == 2 * 3_191_396_096 and cache_bytes == 6_562_906_112
    assert 12.94e9 < mem.argument_size_in_bytes < 12.96e9
    assert mem.alias_size_in_bytes >= cache_bytes
    entry = next(l for l in text.splitlines() if "entry_computation_layout" in l)
    for c in cache:
        whole = ("f32[" if c.dtype == jnp.float32 else "bf16[") + ",".join(
            str(d) for d in c.shape) + "]"
        copies = _copies_of(text, whole)
        assert not copies, copies[:2]
        layouts = set(re.findall(re.escape(whole) + r"\{([\d,]+)", entry))
        assert layouts == {",".join(str(d) for d in reversed(range(len(c.shape))))}, (whole, layouts)
    print(which, "temp", mem.temp_size_in_bytes, "arguments", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < (0.2e9 if which == "decode_step" else 1.0e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    calls = _mosaic_calls(text)
    if which == "decode_step":
        assert sorted(set(calls)) == ["paged_attend_step", "ssm_step"], sorted(set(calls))
        assert calls.count("ssm_step") == 36 and calls.count("paged_attend_step") == 4
    else:
        assert calls == []


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_nemotron_serving_steps_fit_the_chip_and_alias_pages_and_states(one_chip, as_tpu, which):
    """The cell nemotron_3_super_120b_a12b.serve_chat64_moe at its own shapes:
    one period of 11 single-mixer layers at the published widths in bfloat16,
    128 of 512 experts held (9.30 GB), beside 64 slots of SSM state in 8
    groups and convolution tails (1.38 GB, float32) and 64 x 3072 positions of
    bfloat16 K and V pages in one plane (0.20 GB): 10.88 GB of arguments. Each
    program must alias all four cache arrays to its outputs, copy none of them
    whole, hold each as the model spells it and fit the chip beside its
    temporaries (reported). The step holds ``ssm_step`` once a Mamba-2 layer
    (a state tile of [128, 8192] in 8 groups), ``moe_gmm`` twice an expert
    layer and ``paged_attend_step`` once; the chunk holds ``moe_gmm`` alone."""
    import json
    import re

    from benchmarks.families import hybrid_moe_lm as family
    from paddle_tpu.models import hybrid_moe_lm as hmm
    from paddle_tpu.ops.pallas import moe as moe_kernel

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(here, "configs", "nemotron_3_super_120b_a12b.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", "serve_chat64_moe.json")) as f:
        engine = json.load(f)["engine"]
    cfg = family._program_cfg(config)
    assert cfg["max_len"] == engine["max_context"] == 3072 and cfg["experts_held"] == (0, 128)
    progs = models.serving_programs(cfg)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {k: bf16(shape) for k, shape in hmm.param_shapes(cfg).items()}
    slots, page = engine["max_slots"], engine["page_size"]
    per_slot = engine["max_context"] // page
    specs = progs.cache_specs(cfg, max_slots=slots, num_pages=1 + slots * per_slot,
                              page_size=page, dtype=jnp.dtype(engine["cache_dtype"]))
    assert [s.shape for s in specs] == [(1, 12289, 16, 256)] * 2 + [
        (5, 64, 128, 8192), (5, 64, 3 * 10240)]
    cache = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip) for s in specs]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if which == "decode_step":
        fn, args = progs.decode_step, (i32(slots), i32(slots), (i32(slots, per_slot), i32(slots)))
    else:
        fn, args = progs.prefill_chunk, (i32(engine["prefill_chunk"]), i32(), i32(),
                                         (i32(per_slot), i32()))
    moe_kernel.take_resolved()
    compiled = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                       donate_argnames=progs.cache_args,
                       ).lower(params, *args, *cache, None).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    cache_bytes = sum(nbytes(c) for c in cache)
    weight_bytes = sum(nbytes(p) for p in params.values())
    assert weight_bytes == 2 * 4_648_163_712 and cache_bytes == 1_582_841_856
    assert 10.87e9 < mem.argument_size_in_bytes < 10.89e9
    assert mem.alias_size_in_bytes >= cache_bytes
    entry = next(l for l in text.splitlines() if "entry_computation_layout" in l)
    for c in cache:
        whole = ("f32[" if c.dtype == jnp.float32 else "bf16[") + ",".join(
            str(d) for d in c.shape) + "]"
        copies = _copies_of(text, whole)
        assert not copies, copies[:2]
        layouts = set(re.findall(re.escape(whole) + r"\{([\d,]+)", entry))
        assert layouts == {",".join(str(d) for d in reversed(range(len(c.shape))))}, (whole, layouts)
    print(which, "temp", mem.temp_size_in_bytes, "arguments", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    calls = _mosaic_calls(text)
    # the two new shapes of the grouped matmul, in the row tile each program lays out
    tm = 16 if which == "decode_step" else 64
    assert moe_kernel.take_resolved() == {f"moe_gmm_{tm}x1024x2688": "2688 table",
                                          f"moe_gmm_{tm}x2688x1024": "1024 table"}
    if which == "decode_step":
        assert sorted(set(calls)) == ["moe_gmm", "paged_attend_step", "ssm_step"]
        assert (calls.count("ssm_step"), calls.count("moe_gmm"),
                calls.count("paged_attend_step")) == (5, 10, 1)
    else:
        assert calls == ["moe_gmm"] * 10


@pytest.mark.parametrize("which", ["decode_step", "prefill_chunk"])
def test_brumby_serving_steps_fit_the_chip_and_alias_their_state(one_chip, as_tpu, which):
    """The cell brumby_14b.serve_docs16 at its own shapes: 8 layers at the
    published widths in bfloat16 (8.4 GB) beside 16 slots of recurrent state
    (5.1 GB, float32). The engine donates the state, so each program must
    alias it to its output, must not copy it whole anywhere (an undonated or
    sliced state would), must hold the ``retention_step`` kernel once a layer
    in the step, and must fit the chip beside weights and state."""
    import json

    from benchmarks.families import retention_lm as family
    from paddle_tpu.models import retention_lm

    here = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(here, "configs", "brumby_14b.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "traffic", "serve_docs16.json")) as f:
        engine = json.load(f)["engine"]
    cfg = dict(retention_lm.BASE_CFG, **family.model_cfg(config))
    assert cfg["max_len"] == engine["max_context"]
    progs = models.serving_programs(cfg)
    bf16 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {k: bf16(shape) for k, shape in retention_lm.param_shapes(cfg).items()}
    (spec,) = progs.cache_specs(cfg, max_slots=engine["max_slots"])
    state = jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    slots = engine["max_slots"]
    if which == "decode_step":
        fn, args = progs.decode_step, (i32(slots), i32(slots), i32(slots))
    else:
        fn, args = progs.prefill_chunk, (i32(engine["prefill_chunk"]), i32(), i32(), i32())
    compiled = jax.jit(functools.partial(fn, cfg=cfg), donate_argnames=progs.cache_args,
                       ).lower(params, *args, state, None).compile()
    text = compiled.as_text()
    state_bytes = int(np.prod(state.shape)) * 4
    weight_bytes = 2 * sum(int(np.prod(p.shape)) for p in params.values())
    assert 5.0e9 < state_bytes < 5.2e9 and 8.3e9 < weight_bytes < 8.5e9
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    dims = ",".join(str(d) for d in state.shape)
    whole_state_copies = _copies_of(text, f"f32[{dims}]")
    assert not whole_state_copies, whole_state_copies[:2]
    assert text.count("tpu_custom_call") == (cfg["n_layers"] if which == "decode_step" else 0)
    assert ("retention_step" in text) == (which == "decode_step")
    assert _program_bytes(compiled) < HBM_BYTES
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_data_parallel_step_compiles_on_four_chips(topo, as_tpu):
    """A Mosaic kernel cannot be partitioned automatically: under the
    mesh, ops/attention.py runs the flash kernel per shard through
    shard_map. On the virtual CPU mesh the kernel is interpreted into
    plain XLA ops that partition freely, so only this compile sees it."""
    from paddle_tpu.parallel import DataParallel
    from paddle_tpu.parallel.mesh import make_mesh

    spec = _lm_large()
    batch = spec.synth_batch(4, np.random.RandomState(0))
    opt, v, o = _abstract_state(spec, batch)
    mesh = make_mesh(data=4, devices=topo.devices)
    dp = DataParallel(spec.model, opt, mesh=mesh)
    batch_sh = dp._batch_shardings(batch)
    var_sh, opt_sh = dp._state_shardings(v, o)
    step = dp._build_step_fn(v, o, batch_sh, donate=(0, 1))
    with jax.set_mesh(mesh):
        compiled = step.lower(
            _shapes(v, var_sh), _shapes(o, opt_sh), None,
            *[_shapes(b, s) for b, s in zip(batch, batch_sh)],
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    for kernel in ("flash_fwd_resident", "flash_bwd_dkv_resident", "flash_bwd_dq_resident"):
        assert kernel in text
    assert batch_sh[0].spec[0] == "data"  # one sequence per chip
    assert _program_bytes(compiled) < HBM_BYTES
    # the step moves the gradients across chips and nothing else: the
    # logits stay on the chip that computed them. Gathered, f32[2048,32000]
    # into f32[8192,32000], they are 786 MB into every chip every step, in
    # flight across the backward pass on the links the all-reduces need
    tokens, vocab = 4 * 2048, 32000
    assert f"f32[{tokens // 4},{vocab}]" in text  # a chip's logits
    assert "all-gather" not in text
    assert f"f32[{tokens},{vocab}]" not in text and f"f32[4,2048,{vocab}]" not in text
    state_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree_util.tree_leaves((v, o)))
    logits_bytes = tokens * vocab * 4
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    # the replicated state and one chip's quarter of the logits (with the
    # loss and what else the model returns: small)
    assert state_bytes + logits_bytes // 4 <= out_bytes < state_bytes + logits_bytes // 4 + 2**20
    out_shardings = compiled.output_shardings
    assert out_shardings.variables == var_sh and out_shardings.opt_state == opt_sh
    assert out_shardings.loss.is_fully_replicated


def test_trainer_step_aliases_its_state_at_lm_big_train_2k(topo, one_chip, as_tpu):
    """``lm_big.train_2k``'s step (4 x 2048 tokens, Adam) as
    ``Trainer._compiled_step`` builds it, on an Executor whose place is the
    described chip. Every state output must take its input's buffer:
    parameters and Adam's two slots, 12 bytes a parameter. Undonated the
    step allocated an output a leaf at every call (593 allocations of 43 us
    with the device idle, PERF.md PR 25) and held both copies of the state."""
    import paddle_tpu as pt
    from paddle_tpu.core.config import TPUPlace

    class DescribedChip(TPUPlace):
        def device(self):
            return topo.devices[0]

    spec = _lm_large()
    batch = spec.synth_batch(4, np.random.RandomState(0))
    opt, v, o = _abstract_state(spec, batch)
    n_params = sum(int(np.prod(p.shape)) for p in v.params.values())
    assert n_params == 216_692_736  # the cell's 216.7 M
    args = (_shapes(v, one_chip), _shapes(o, one_chip), *_shapes(batch, one_chip))
    trainer = pt.Trainer(lambda: spec.model, lambda: opt, place=DescribedChip())
    compiled = trainer._compiled_step().lower(*args).compile()
    undonated = jax.jit(opt.minimize(spec.model)).lower(*args).compile()
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased >= 12 * n_params
    assert undonated.memory_analysis().alias_size_in_bytes == 0
    saved = _program_bytes(undonated) - _program_bytes(compiled)
    # 1.99 of the 2.60 GB: donated, the outputs cannot serve as scratch while
    # their inputs live, and the program takes 0.61 GB more of temp
    assert 0.7 * aliased < saved <= aliased
    text = compiled.as_text()
    for kernel in ("flash_fwd_resident", "flash_bwd_dkv_resident", "flash_bwd_dq_resident"):
        assert kernel in text
    assert _program_bytes(compiled) < HBM_BYTES
