"""Time the ``paged_attend_step`` kernel on the attached chip at the two
serving cells' shapes, over the rows it holds a step, beside the gather it
replaces.

    python tools/paged_attend_sweep.py [--rows 64,128,256,512] [--cells ouro_2_6b,lm_big]

A decode step's attention is every plane's call, so each form is timed as
one program that scans the planes (the page arrays its arguments, the plane
index traced). Prints, per cell and form, milliseconds a plane, the bytes of
the live pages a plane over that time, and the largest difference from the
einsum form computed at ``highest`` precision. Results also go to
``chiprun_out/paged_attend_sweep.json``. On the chip only: there is no CPU
fallback."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# slots, heads, head size, planes held here, pages, page size, table width,
# pages' dtype, positions: the cells' engines, a slot's position spread over
# what the traffic leaves live (ouro_2_6b 48-640, lm_big 32-476)
CELLS = {
    "ouro_2_6b": dict(S=8, H=16, dh=128, planes=24, num_pages=321, page=16, P=40,
                      dtype="bfloat16", lo=100, hi=639),
    "lm_big": dict(S=16, H=16, dh=64, planes=12, num_pages=2049, page=16, P=128,
                   dtype="float32", lo=40, hi=470),
}


def timed(fn, *args, n=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def sweep(name, c, rows, out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.transformer_lm import _kv_core, _live_mask
    from paddle_tpu.ops.pallas import paged_attention as pa

    S, H, dh, ps, P = c["S"], c["H"], c["dh"], c["page"], c["P"]
    dtype = jnp.dtype(c["dtype"])
    shape = (c["planes"], c["num_pages"], ps, H * dh)
    k_pages, v_pages = (jax.random.normal(jax.random.PRNGKey(j), shape, dtype) for j in (1, 2))
    q = jax.random.normal(jax.random.PRNGKey(3), (S, H, dh), jnp.float32)
    pos = np.linspace(c["lo"], c["hi"], S).astype(np.int32)
    # every slot's pages out of order, as an engine that has run a while holds them
    tables = np.random.default_rng(0).permutation(np.arange(1, 1 + S * P)).reshape(S, P)
    tables, pos = jnp.asarray(tables, jnp.int32), jnp.asarray(pos)
    live_pages = int((np.asarray(pos) // ps + 1).sum())
    live_bytes = live_pages * 2 * ps * H * dh * dtype.itemsize
    live = _live_mask(pos, P * ps, None).reshape(S, 1, 1, -1, P * ps)

    def gathered(k_pages, v_pages, plane):
        def gather(j):
            pg = (k_pages, v_pages)[j]
            return jnp.take(pg.reshape((-1,) + pg.shape[2:]),
                            plane * pg.shape[1] + tables, axis=0, mode="clip")
        return _kv_core(q, gather, live)

    def every_plane(one):
        scanned = jax.jit(lambda k, v: jax.lax.scan(
            lambda acc, plane: (acc + one(k, v, plane), None),
            jnp.zeros((S, H, dh), jnp.float32), jnp.arange(c["planes"]))[0])
        return lambda: scanned(k_pages, v_pages)  # arguments: closed over, they are constants

    with jax.default_matmul_precision("highest"):
        exact = np.asarray(every_plane(gathered)())
    row = {"cell": name, "live_pages": live_pages, "table_pages": S * P,
           "live_mb_a_plane": live_bytes / 1e6,
           "least_ms_a_plane": live_bytes / 819e9 * 1e3, "forms": {}}

    def note(form, fn):
        got = np.asarray(fn())
        ms = timed(fn) / c["planes"]
        row["forms"][form] = {"ms_a_plane": ms, "live_gb_s": live_bytes / ms / 1e6,
                              "max_err": float(np.abs(got - exact).max())}
        print(json.dumps({"cell": name, "form": form, **row["forms"][form]}), flush=True)

    note("gather", every_plane(gathered))
    for r in rows:
        pa.STEP_ROWS = r
        note(f"kernel_{r}", every_plane(
            lambda k, v, plane: pa.paged_attend_step(q, k, v, plane, tables, pos)))
    out.append(row)


def main():
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="64,128,256,512")
    ap.add_argument("--cells", default="ouro_2_6b,lm_big")
    args = ap.parse_args()
    assert jax.default_backend() == "tpu", jax.devices()
    out = []
    for name in args.cells.split(","):
        sweep(name, CELLS[name], [int(r) for r in args.rows.split(",")], out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_attend_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
