"""Time the ``paged_attend_step`` kernel on the attached chip at the two
serving cells' shapes, over the rows it holds a step, beside the gather it
replaces; with ``--cells sarvam_105b`` the latent family's two kernels,
``latent_attend_step`` and ``latent_attend_chunk``, at that cell's shapes.

    python tools/paged_attend_sweep.py [--rows 64,128,256,512] [--cells ouro_2_6b,lm_big]
    python tools/paged_attend_sweep.py --cells sarvam_105b --rows 256,512,1024 [--tiles 8,16]

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


# sarvam_105b.serve_docs32: 32 slots x 16384 positions of one array of 640-wide
# rows, 64 heads that read the same row, the value its first 512 lanes; a
# step's slots at 1024-13000, a chunk of 512 queries from ``pos0``
LATENT = dict(S=32, H=64, row=640, rank=512, planes=5, page=16, P=1024, C=512, scale=0.13524,
              lo=1024, hi=13000, chunks_at=(0, 3072, 11776))


def sweep_latent(c, rows, tiles, out):
    """The step's and the chunk's kernel beside the gathered absorbed core
    (``latent_moe_lm._core_absorbed``'s products on rows made here)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import paged_attention as pa

    S, H, row, rank, ps, P, C = (c[k] for k in ("S", "H", "row", "rank", "page", "P", "C"))
    pages = (jax.random.normal(jax.random.PRNGKey(1), (c["planes"], 1 + S * P, ps, row),
                               jnp.float32) * 0.7).astype(jnp.bfloat16)
    tables = np.random.default_rng(0).permutation(np.arange(1, 1 + S * P)).reshape(S, P)
    tables = jnp.asarray(tables, jnp.int32)
    mm = lambda *a: jnp.einsum(*a, preferred_element_type=jnp.float32)

    def gathered(q, pages, plane, table, pos):  # q [B, H, Q, row]; table [B, P]; pos [B, Q]
        ctx = jnp.take(pages.reshape((-1,) + pages.shape[2:]), plane * pages.shape[1] + table,
                       axis=0, mode="clip").reshape(q.shape[0], P * ps, row)
        live = jnp.arange(P * ps)[None, None, None] <= pos[:, None, :, None]

        def heads(qh):  # 16 heads a block, as head_block_for cuts a chunk
            s = mm("bhqr,btr->bhqt", qh, ctx) * c["scale"]
            a = jax.nn.softmax(jnp.where(live, s, -1e9), -1)
            return mm("bhqt,btr->bhqr", a.astype(ctx.dtype), ctx)[..., :rank]

        g = min(16, H) if q.shape[2] > 1 else H
        blocks = jax.lax.map(heads, jnp.moveaxis(q.reshape(q.shape[0], H // g, g, -1, row), 1, 0))
        return jnp.moveaxis(blocks, 0, 1).reshape(q.shape[:3] + (rank,))

    def every_plane(one, shape):
        scanned = jax.jit(lambda pages: jax.lax.scan(
            lambda acc, plane: (acc + one(pages, plane), None),
            jnp.zeros(shape, jnp.float32), jnp.arange(c["planes"]))[0])
        return lambda: scanned(pages)

    def note(row_out, form, fn, exact, calls):
        got = np.asarray(fn())
        ms = timed(fn) / c["planes"]
        row_out["forms"][form] = {
            "ms_a_plane": ms, "live_gb_s": row_out["live_mb_a_plane"] * calls / ms / 1e3,
            "max_err": float(np.abs(got - exact).max()),
            "err_over_rms": float(np.abs(got - exact).max() / np.sqrt((exact ** 2).mean()))}
        print(json.dumps({"cell": row_out["cell"], "form": form, **row_out["forms"][form]}),
              flush=True)

    # the step: a query a slot
    pos = jnp.asarray(np.linspace(c["lo"], c["hi"], S).astype(np.int32))
    q = (jax.random.normal(jax.random.PRNGKey(3), (S, H, row), jnp.float32) * 0.5).astype(
        jnp.bfloat16).at[..., 576:].set(0)
    live_pages = int((np.asarray(pos) // ps + 1).sum())
    step = {"cell": "sarvam_105b.step", "live_pages": live_pages, "table_pages": S * P,
            "live_mb_a_plane": live_pages * ps * row * 2 / 1e6, "forms": {}}
    step["least_ms_a_plane"] = step["live_mb_a_plane"] / 819e3
    xla = every_plane(lambda pages, plane: gathered(
        q[:, :, None], pages, plane, tables, pos[:, None])[:, :, 0], (S, H, rank))
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(xla())
    note(step, "gather", xla, exact, 1)
    for r in rows:
        pa.LATENT_STEP_ROWS = r
        note(step, f"kernel_{r}", every_plane(lambda pages, plane: pa.latent_attend_step(
            q, pages, plane, tables, pos, scale=c["scale"], value_width=rank), (S, H, rank)),
            exact, 1)
    out.append(step)

    # the chunk: 512 queries of one sequence; a tile of tq queries reads the
    # sequence's live pages once more
    qc = (jax.random.normal(jax.random.PRNGKey(4), (H, C, row), jnp.float32) * 0.5).astype(
        jnp.bfloat16).at[..., 576:].set(0)
    for pos0 in c["chunks_at"]:
        at = pos0 + jnp.arange(C, dtype=jnp.int32)
        live_pages = (pos0 + C) // ps
        chunk = {"cell": f"sarvam_105b.chunk@{pos0}", "live_pages": live_pages, "table_pages": P,
                 "live_mb_a_plane": live_pages * ps * row * 2 / 1e6,
                 "tflop_a_plane": 2 * C * H * (pos0 + C / 2) * (row + rank) / 1e12, "forms": {}}
        xla = every_plane(lambda pages, plane: gathered(
            qc[None], pages, plane, tables[:1], at[None])[0], (H, C, rank))
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(xla())
        note(chunk, "gather_16_heads", xla, exact, 1)
        for r in rows:
            for tq in tiles:
                pa.LATENT_CHUNK_ROWS, pa.CHUNK_TILE_QUERIES = r, tq
                try:
                    note(chunk, f"kernel_{r}_x{tq}", every_plane(
                        lambda pages, plane: pa.latent_attend_chunk(
                            qc, pages, plane, tables[0], jnp.int32(pos0), scale=c["scale"],
                            value_width=rank), (H, C, rank)), exact, C // tq)
                except Exception as e:  # a tile that does not fit VMEM is a finding
                    print(json.dumps({"cell": chunk["cell"], "form": f"kernel_{r}_x{tq}",
                                      "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)
        out.append(chunk)


def main():
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="64,128,256,512")
    ap.add_argument("--tiles", default="8,16")
    ap.add_argument("--cells", default="ouro_2_6b,lm_big")
    args = ap.parse_args()
    assert jax.default_backend() == "tpu", jax.devices()
    out = []
    rows = [int(r) for r in args.rows.split(",")]
    for name in args.cells.split(","):
        if name == "sarvam_105b":
            sweep_latent(LATENT, rows, [int(t) for t in args.tiles.split(",")], out)
        else:
            sweep(name, CELLS[name], rows, out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_attend_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
