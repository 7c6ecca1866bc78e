"""Shared pieces of the plain references: straightforward ``jax.numpy`` in
float32, written from the papers. Nothing here imports the program.

Every matrix multiplication goes through one ``mm`` so that the same code is
the reference (float32 operands, ``highest`` precision, which a TPU does not
give a float32 matmul by default) and the lower-precision control (operands
rounded to fp8 e4m3 with a per-tensor scale, the step below the bfloat16 the
configurations state; forward and backward products alike)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@jax.custom_vjp
def mm_fp8(a, b):
    return mm_f32(_round_fp8(a), _round_fp8(b))


def _mm_fp8_fwd(a, b):
    return mm_fp8(a, b), (a, b)


def _unbroadcast(g, shape):
    extra = g.ndim - len(shape)
    if extra:
        g = jnp.sum(g, axis=tuple(range(extra)))
    return g.reshape(shape)


def _mm_fp8_bwd(res, g):
    a, b = res
    gq = _round_fp8(g)
    da = mm_f32(gq, _t(_round_fp8(b)))
    db = mm_f32(_t(_round_fp8(a)), gq)
    return _unbroadcast(da, a.shape), _unbroadcast(db, b.shape)


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)

MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def sinusoid(t: int, d: int):
    """Vaswani et al. 3.5, in the half-split layout the repo uses (sines in
    the first d/2 columns, cosines in the rest)."""
    pos = np.arange(t)[:, None].astype(np.float64)
    dim = np.arange(d // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(np.concatenate([np.sin(angle), np.cos(angle)], 1), jnp.float32)


def linear(mm, p, pfx, x, bias=True):
    y = mm(x, p[pfx + "/w"])
    return y + p[pfx + "/b"] if bias else y


def attention(mm, p, pfx, xq, xkv, heads: int, mask):
    """Multi-head attention over [B, T, D]; ``mask`` is additive, broadcast
    to [B, H, Tq, Tk]."""
    b, tq, d = xq.shape
    tk = xkv.shape[1]
    dh = d // heads

    def split(x, t):
        return x.reshape(b, t, heads, dh).transpose(0, 2, 1, 3)

    q = split(linear(mm, p, pfx + "/q", xq), tq)
    k = split(linear(mm, p, pfx + "/k", xkv), tk)
    v = split(linear(mm, p, pfx + "/v", xkv), tk)
    s = mm(q, _t(k)) / np.sqrt(dh)
    if mask is not None:
        s = s + mask
    w = jax.nn.softmax(s, axis=-1)
    ctx = mm(w, v).transpose(0, 2, 1, 3).reshape(b, tq, d)
    return linear(mm, p, pfx + "/out", ctx)


def ffn(mm, p, pfx, x):
    return linear(mm, p, pfx + "/fc2", jax.nn.relu(linear(mm, p, pfx + "/fc1", x)))


def causal_mask(t: int):
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -1e30)[None, None]


def key_mask(lens, t: int):
    """[B] lengths -> additive [B, 1, 1, T] that drops keys at or past len."""
    return jnp.where(jnp.arange(t)[None, :] < lens[:, None], 0.0, -1e30)[:, None, None, :]


def stack_layers(params, prefix_fmt: str, n: int):
    """{suffix: [n, ...]} of the leaves named ``prefix_fmt.format(i)/suffix``."""
    head = prefix_fmt.format(0) + "/"
    suffixes = [k[len(head):] for k in params if k.startswith(head)]
    return {s: jnp.stack([params[f"{prefix_fmt.format(i)}/{s}"] for i in range(n)])
            for s in suffixes}


def scan_layers(body, x, stacked):
    """Run ``body(x, layer_params) -> x`` over stacked layers, recomputing
    each layer in the backward pass so one layer's activations live at once."""
    step = jax.checkpoint(lambda h, lp: (body(h, lp), None))
    return jax.lax.scan(step, x, stacked)[0]


# -- optimizer and the three-step walk ------------------------------------

def noam_lr(step, d_model: int, warmup: int, scale: float):
    """Vaswani et al. eq. 3, with the 0-based step clipped to 1 as the repo
    evaluates its schedule before incrementing."""
    s = jnp.maximum(jnp.float32(step), 1.0)
    return scale * d_model ** -0.5 * jnp.minimum(s ** -0.5, s * warmup ** -1.5)


def learning_rate(opt: dict, step: int):
    if opt["schedule"] == "constant":
        return jnp.float32(opt["learning_rate"])
    if opt["schedule"] == "noam":
        return noam_lr(step, opt["d_model"], opt["warmup_steps"], opt["learning_rate"])
    raise ValueError(f"unknown schedule {opt['schedule']!r}")


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"), donate_argnums=(0, 2, 3))
def adam_update(params, grads, m, v, lr, t, *, b1, b2, eps):
    """Kingma & Ba with the bias correction folded into the rate."""
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    m = {k: b1 * m[k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(grads[k]) for k in params}
    new = {k: params[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps) for k in params}
    return new, m, v


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for k, x in tree.items()}


@jax.jit
def leaf_delta_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def walk_steps(loss_sum, make_params, batches, blocks, n_tokens, opt: dict,
               compare_first_grad=None, keep_first_grad: bool = False):
    """Follow the first ``len(batches)`` optimizer steps. ``loss_sum(params,
    *block)`` is the summed loss of a block of rows; ``blocks(batch)`` yields
    the blocks; ``n_tokens(batch)`` is the count the mean is taken over.
    Returns the losses, the first gradient's norm per leaf and the norm of
    each leaf's change after the last step, as host floats. With
    ``compare_first_grad`` ({leaf: host array}, another side's first
    gradient) also the norm per leaf of its difference from this walk's;
    with ``keep_first_grad`` this walk's first gradient as host arrays."""
    grad_block = jax.jit(jax.value_and_grad(loss_sum))
    params = make_params()
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for step, batch in enumerate(batches):
        n = float(n_tokens(batch))
        total, grads = 0.0, None
        for block in blocks(batch):
            ls, g = grad_block(params, *block)
            total += float(ls)
            grads = g if grads is None else _accumulate(grads, g)
        grads = jax.tree_util.tree_map(lambda x: x / n, grads)
        out["losses"].append(total / n)
        if step == 0:
            out["grad_norms"] = {k: float(x) for k, x in leaf_norms(grads).items()}
            if compare_first_grad is not None:
                other = {k: jnp.asarray(x) for k, x in compare_first_grad.items()}
                out["grad_diff_norms"] = {
                    k: float(x) for k, x in leaf_delta_norms(grads, other).items()}
                del other
            if keep_first_grad:
                out["first_grad"] = jax.device_get(grads)
        params, m, v = adam_update(
            params, grads, m, v, learning_rate(opt, step), jnp.float32(step + 1),
            b1=opt["beta1"], b2=opt["beta2"], eps=opt["epsilon"])
    out["delta_norms"] = {k: float(x) for k, x in
                          leaf_delta_norms(params, make_params()).items()}
    return out
