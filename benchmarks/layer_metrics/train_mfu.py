"""Model FLOP/s utilization: the operations forward and backward require per
loss-carrying token (``benchmarks/flops.py``) times tokens per second per
chip, over the chip's bf16 peak (``peaks.json``)."""


def read(view):
    per_token = view["counters"].get("train_flops_per_token")
    if per_token is None or not view["peaks"]:
        return None
    return 100.0 * per_token * view["end_to_end"]["train_tok_s"] / view["peaks"]["bf16_flops"]
