"""Consistency pins for the flash kernels' tuned-block table, the rule behind
it, and the counters that say which of them a call took.

VERDICT r4 #8: ``_TUNED_BLOCKS`` is populated from chip measurement
(ROADMAP A2) — but a bad checked-in tuple must fail HERE, on CPU, not
crash the next chip run. The constraints mirror what the kernels enforce
(divisibility through ``fit_block``) plus the VMEM each kernel's grid step
really needs: the kernels' own ``working_set_bytes`` — forward, dK/dV and
dQ, in the form (resident or streamed) the row's shape takes — under the
``vmem_limit_bytes`` the code sets for it. The reference's analogue is
cuDNN algo selection with a fallback guarantee
(``operators/conv_cudnn_op.cu.cc``).
"""
import json
import os

import importlib

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core import profiler as prof

# the module, not the same-named function the package re-exports (which
# shadows the submodule attribute `import ... as` resolves through)
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# a v5e core has 128 MiB of VMEM; the kernels never ask for more than
# fa._VMEM_LIMIT_MAX of it
_VMEM_PHYSICAL = 128 * 1024 * 1024


def _check_row(kernel: str, bq: int, bk: int, t: int, d: int, itemsize: int,
               where: str) -> None:
    for name, b in (("block_q", bq), ("block_k", bk)):
        assert isinstance(b, int) and b >= 128, f"{where}: {name}={b} < 128"
        assert b % 128 == 0, f"{where}: {name}={b} not MXU/lane aligned (128)"
        assert b <= 4096, f"{where}: {name}={b} implausibly large"
        assert t % b == 0, f"{where}: {name}={b} does not divide T={t}"
    held = t if fa._resident(kernel, t, t, d, itemsize, itemsize) else None
    need = fa.working_set_bytes(kernel, bq, bk, d, itemsize, held)
    limit = fa._vmem_limit(need) or fa._SCOPED_VMEM_DEFAULT
    assert need <= limit <= fa._VMEM_LIMIT_MAX < _VMEM_PHYSICAL, (
        f"{where}: {kernel} ({bq},{bk}) needs {need} bytes of VMEM, the limit "
        f"the code sets is {limit}")


def test_tuned_blocks_table_consistent():
    for key, row in fa._TUNED_BLOCKS.items():
        assert len(key) == 3, f"malformed key {key!r}: (T, head size, itemsize)"
        t, d, itemsize = key
        assert itemsize in (2, 4) and d % 8 == 0 and t % 128 == 0, key
        assert set(row) == set(fa.KERNELS), f"row {key}: one entry per kernel, got {sorted(row)}"
        for kernel, (bq, bk) in row.items():
            _check_row(kernel, bq, bk, t, d, itemsize, f"_TUNED_BLOCKS[{key}][{kernel}]")


def test_tuned_blocks_resolution_always_divides():
    """Whatever the table holds, tuned_blocks() must hand every kernel block
    sizes that pass its divisibility enforce, within the VMEM its limit
    grants, for every power-of-two T the bench/tune harnesses use — table
    row or rule."""
    for t_q in (128, 256, 512, 1024, 2048, 4096, 8192, 16384):
        for d, itemsize in ((64, 2), (128, 2), (256, 2), (64, 4)):
            for kernel in fa.KERNELS:
                bq, bk = fa.tuned_blocks(t_q, t_q, d, itemsize, kernel)
                _check_row(kernel, bq, bk, t_q, d, itemsize,
                           f"tuned_blocks({t_q},{t_q},{d},{itemsize},{kernel})")
                bq, bk = fa.tuned_blocks(t_q, 2 * t_q, d, itemsize, kernel)
                assert t_q % bq == 0 and (2 * t_q) % bk == 0


def test_the_rule_stays_inside_its_vmem_budget():
    """A shape the table lacks takes the largest blocks whose working set the
    budget holds, and falls to the fitted 128/128 only where nothing fits;
    whatever it picks, the limit the code sets covers it."""
    for t, d in ((192, 128), (1024, 64), (4096, 128), (16384, 128), (4096, 512)):
        for kernel in fa.KERNELS:
            bq, bk = fa.rule_blocks(t, t, d, 2, kernel)
            held = t if fa._resident(kernel, t, t, d, 2, 2) else None
            need = fa.working_set_bytes(kernel, bq, bk, d, 2, held)
            assert need <= fa._RULE_VMEM_BYTES or (bq, bk) == (
                fa.fit_block(128, t), fa.fit_block(128, t)), (t, kernel, bq, bk, need)
            assert need <= (fa._vmem_limit(need) or fa._SCOPED_VMEM_DEFAULT)
    assert fa.rule_blocks(4096, 4096, 128, 2, "fwd") == (1024, 1024)


def _trace_fwd_bwd(shape, dtype, **kw):
    """Trace (nothing runs) forward and fused backward; return the counters'
    growth and what the kernels resolved to."""
    fa.take_resolved()
    before = prof.counters()
    x = jax.ShapeDtypeStruct(shape, dtype)
    jax.eval_shape(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True, interpret=True, **kw)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
    after = prof.counters()
    grew = {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("flash.") and after[k] != before.get(k, 0)}
    return grew, fa.take_resolved()


@pytest.mark.parametrize("source, shape, dtype, kw", [
    # lm_big.train_2k's call: every kernel from the table, in its resident form
    ("table", (4, 16, 2048, 64), jnp.bfloat16, {}),
    # a shape nobody swept falls to the rule, visibly
    ("rule", (1, 2, 384, 32), jnp.float32, {}),
    # a caller's explicit blocks win over both
    ("caller", (4, 16, 2048, 64), jnp.bfloat16, {"block_q": 256, "block_k": 128}),
])
def test_block_sources_and_forms_are_counted(source, shape, dtype, kw):
    grew, resolved = _trace_fwd_bwd(shape, dtype, **kw)
    assert grew == {f"flash.blocks.{source}": 3, "flash.form.resident": 3}, grew
    assert sorted(resolved) == [
        "flash_bwd_dkv_resident", "flash_bwd_dq_resident", "flash_fwd_resident"]
    t, d = shape[2], shape[3]
    for kernel, name in (("fwd", "flash_fwd_resident"), ("dkv", "flash_bwd_dkv_resident"),
                         ("dq", "flash_bwd_dq_resident")):
        bq, bk = ((kw["block_q"], kw["block_k"]) if kw else
                  fa.tuned_blocks(t, t, d, jnp.dtype(dtype).itemsize, kernel))
        assert resolved[name] == f"{bq}x{bk} {source} resident"
    if source == "table":
        assert (t, d, 2) in fa._TUNED_BLOCKS


def test_a_head_that_does_not_fit_is_counted_streamed():
    grew, resolved = _trace_fwd_bwd((1, 2, 16384, 128), jnp.bfloat16)
    assert grew["flash.form.streamed"] == 3 and "flash.form.resident" not in grew
    assert sorted(resolved) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_the_compile_span_names_the_resolved_blocks():
    """``executor.compile`` carries what each flash kernel of the program it
    compiled resolved to."""
    import numpy as np

    from paddle_tpu import tracing
    from paddle_tpu.executor import Executor

    fa.take_resolved()
    q = jnp.asarray(np.random.RandomState(0).randn(1, 2, 32, 8), jnp.float32)
    Executor().run(lambda a: fa.flash_attention(a, a, a, causal=True, block_q=16, block_k=8), q)
    spans = [s for s in tracing.spans() if s.name == "executor.compile"
             and "flash_fwd_resident" in s.attrs]
    assert spans and spans[-1].attrs["flash_fwd_resident"] == "16x8 caller resident"


def test_flash_tune_artifact_rows_transplantable():
    """If a chip window already produced FLASH_TUNE_TPU.json, its 'best'
    rows must satisfy the same constraints — so they can be checked into
    _TUNED_BLOCKS verbatim."""
    path = os.path.join(os.path.dirname(__file__), "..", "FLASH_TUNE_TPU.json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        art = json.loads(f.readlines()[-1])
    for t_str, row in art.get("best", {}).items():
        if row.get("partial_sweep"):
            continue
        for kernel in fa.KERNELS:
            _check_row(kernel, row["block_q"], row["block_k"], int(t_str), 128, 2,
                       f"FLASH_TUNE_TPU.json best[{t_str}]")
