"""Hold the ``ssm_step`` kernel to its einsum twin on the attached chip and
time both at the serving cell's shape.

    python tools/ssm_step_sweep.py [--slots 64] [--layers 36] [--active 61]

First two planes of state with a ragged set of active slots: the kernel's
``y`` and state against the twin's at ``highest`` precision (largest
difference, and whether every idle slot's state is bit for bit what it was).
Then one decode step's worth of calls (every layer in turn, the state donated)
as one program, kernel and twin: milliseconds a step and the required bytes
(``benchmarks/ssm_bytes.py``) over that time. Results also go to
``chiprun_out/ssm_step_sweep.json``. On the chip only: there is no CPU
fallback."""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--active", type=int, default=61)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=1,
                    help="groups of B and C (8 with --heads 128 --layers 5: the tile of "
                         "nemotron_3_super_120b_a12b)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import ssm_bytes
    from paddle_tpu.ops.pallas.ssm import ssm_step, ssm_step_xla

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: {jax.devices()}", file=sys.stderr)
        return 3
    S, L, N, D = args.slots, args.layers, args.state, args.heads * args.head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    xdt = 0.01 * jax.random.normal(keys[1], (S, D))
    decay = jnp.exp(-0.01 * jax.random.uniform(keys[2], (S, D)))
    b, c = (jax.random.normal(k, (S, args.groups, N)) for k in keys[3:5])
    rng = np.random.default_rng(0)
    active = np.zeros((S,), np.int32)
    active[rng.permutation(S)[:args.active]] = 1
    active = jnp.asarray(active)
    out = {"shape": {"slots": S, "layers": L, "state": N, "channels": D,
                     "groups": args.groups, "active": args.active}}

    small = jax.random.normal(keys[0], (2, S, N, D))
    with jax.default_matmul_precision("highest"):
        y_k, s_k = ssm_step(small, xdt, decay, b, c, active, layer=1)
        y_x, s_x = ssm_step_xla(small, xdt, decay, b, c, active, layer=1)
    idle = np.asarray(active) == 0
    out["check"] = {
        "y_max_diff": float(jnp.abs(y_k - y_x).max()), "y_max": float(jnp.abs(y_x).max()),
        "state_max_diff": float(jnp.abs(s_k - s_x).max()),
        "idle_untouched": bool((np.asarray(s_k[1])[idle] == np.asarray(small[1])[idle]).all()),
        "other_plane_untouched": bool((s_k[0] == small[0]).all())}
    print(json.dumps(out["check"]), flush=True)
    del small, s_k, s_x

    calls = {"layers": L, "heads": args.heads, "head_dim": args.head_dim, "state": N}
    need = ssm_bytes.ssm_step_bytes(calls, args.active)

    def step(form, state):
        y = jnp.zeros((S, D))
        for i in range(L):  # unrolled, as the model's layers are
            y_i, state = form(state, xdt + y * 0.0, decay, b, c, active, layer=i)
            y = y + y_i
        return y, state

    for name, form in (("kernel", ssm_step), ("xla", ssm_step_xla)):
        fn = jax.jit(functools.partial(step, form), donate_argnums=(0,))
        state = jnp.zeros((L, S, N, D), jnp.float32)
        y, state = fn(state)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(5):
            y, state = fn(state)
        jax.block_until_ready(y)
        ms = (time.perf_counter() - t0) / 5 * 1e3
        out[name] = {"ms_a_step": ms, "required_gb_s": need / ms / 1e6}
        print(name, json.dumps(out[name]), flush=True)
        del state, y
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_step_sweep.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
