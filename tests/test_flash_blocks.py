"""Consistency pins for the flash kernel's tuned-block table.

VERDICT r4 #8: ``_TUNED_BLOCKS`` is to be populated from chip measurement
(ROADMAP A7) — but a bad checked-in tuple must fail HERE, on CPU, not
crash the next chip run. The constraints mirror what the kernel actually enforces
(divisibility at ``_flash_fwd``, ``flash_attention.py:228-231``) plus the
VMEM arithmetic a (block_q, block_k) tile implies. The reference's
analogue is cuDNN algo selection with a fallback guarantee
(``operators/conv_cudnn_op.cu.cc``).
"""
import json
import os

import importlib

# the module, not the same-named function the package re-exports (which
# shadows the submodule attribute `import ... as` resolves through)
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# v5e VMEM is 128 MiB/core but Mosaic needs headroom for double buffering
# and the backward's extra tiles — budget each fwd tile set at 16 MiB.
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_D_MAX = 256  # largest head_dim any in-tree model family uses


def _tile_bytes(bq: int, bk: int, d: int = _D_MAX) -> int:
    """Fwd working set per grid step: q/k/v tiles in bf16, scores bq x bk
    and the out/lse accumulators in f32."""
    return (
        bq * d * 2          # q tile (bf16)
        + 2 * bk * d * 2    # k + v tiles (bf16)
        + bq * bk * 4       # scores (f32)
        + bq * d * 4        # out accumulator (f32)
        + bq * 4            # lse (f32)
    )


def _check_row(bq: int, bk: int, where: str) -> None:
    for name, b in (("block_q", bq), ("block_k", bk)):
        assert isinstance(b, int) and b >= 128, f"{where}: {name}={b} < 128"
        assert b % 128 == 0, f"{where}: {name}={b} not MXU/lane aligned (128)"
        assert b <= 4096, f"{where}: {name}={b} implausibly large"
    assert _tile_bytes(bq, bk) <= _VMEM_BUDGET_BYTES, (
        f"{where}: ({bq},{bk}) tile set = {_tile_bytes(bq, bk)} bytes "
        f"exceeds the {_VMEM_BUDGET_BYTES}-byte VMEM budget at d={_D_MAX}"
    )


def test_tuned_blocks_table_consistent():
    prev_min_t = 0
    for row in fa._TUNED_BLOCKS:
        assert len(row) == 3, f"malformed row {row!r}"
        min_t, bq, bk = row
        assert min_t >= prev_min_t, (
            f"rows must be ascending by min_T (resolution takes the LAST "
            f"matching row): {fa._TUNED_BLOCKS}"
        )
        prev_min_t = min_t
        _check_row(bq, bk, f"_TUNED_BLOCKS row {row}")


def test_tuned_blocks_resolution_always_divides():
    """Whatever the table holds, tuned_blocks() must hand the kernel block
    sizes that pass its divisibility enforce for every power-of-two T the
    bench/tune harnesses use."""
    for t_q in (128, 256, 512, 1024, 2048, 4096, 8192, 16384):
        for t_kv in (t_q, 2 * t_q):
            bq, bk = fa.tuned_blocks(t_q, t_kv)
            assert min(bq, t_q) and t_q % min(bq, t_q) == 0
            assert t_kv % min(bk, t_kv) == 0
            _check_row(bq, bk, f"tuned_blocks({t_q},{t_kv})")


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
        bq, bk = row["block_q"], row["block_k"]
        _check_row(bq, bk, f"FLASH_TUNE_TPU.json best[{t_str}]")
        T = int(t_str)
        assert T % bq == 0 and T % bk == 0
