"""What both plain references share: the matrix product in a stated
precision, LayerNorm, multi-head attention, the loss, and AdamW.

Precisions: ``float32`` is the reference proper (``Precision.HIGHEST``: on a
TPU a float32 product otherwise runs in bfloat16 passes). ``bfloat16`` rounds
the operands of every product to bfloat16 and accumulates in float32: where a
sound program sits. ``fp8`` is the control: the dense layers' products take
operands rounded to float8_e4m3 with one scale per tensor, forward and
backward, the step below bfloat16 that a later PR could be tempted by."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
PRECISIONS = ("float32", "bfloat16", "fp8")
_E4M3_MAX = 448.0


def _fp8_round(x):
    scale = _E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return ((x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)) / scale


@jax.custom_vjp
def _fp8_mm(x, w):
    return jnp.dot(_fp8_round(x), _fp8_round(w), precision="highest")


def _fp8_mm_fwd(x, w):
    xq, wq = _fp8_round(x), _fp8_round(w)
    return jnp.dot(xq, wq, precision="highest"), (xq, wq)


def _fp8_mm_bwd(res, g):
    xq, wq = res
    gq = _fp8_round(g)
    dx = jnp.dot(gq, wq.T, precision="highest")
    dw = jnp.dot(xq.reshape(-1, xq.shape[-1]).T, gq.reshape(-1, gq.shape[-1]), precision="highest")
    return dx, dw


_fp8_mm.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


def mm(x, w, precision: str):
    """``x @ w`` for a dense layer: x (..., K), w (K, N)."""
    if precision == "float32":
        return jnp.dot(x, w, precision="highest")
    if precision == "bfloat16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    if precision == "fp8":
        return _fp8_mm(x, w)
    raise ValueError(f"unknown precision {precision!r}: one of {PRECISIONS}")


def einsum(spec: str, a, b, precision: str):
    """A product inside attention (scores, values): float32 at highest, else
    bfloat16 operands with float32 accumulation (fp8 leaves attention in
    bfloat16, as fp8 training recipes do)."""
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision="highest")
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, prefix: str):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * w[prefix + "/scale"] + w[prefix + "/bias"]


def dense(x, w, prefix: str, precision: str):
    y = mm(x, w[prefix + "/kernel"], precision)
    bias = w.get(prefix + "/bias")
    return y if bias is None else y + bias


def mlp(x, w, prefix: str, precision: str):
    h = layer_norm(x, w, prefix + "/LayerNorm_0")
    h = dense(h, w, prefix + "/dense_1", precision)
    h = jax.nn.gelu(h, approximate=False)
    return dense(h, w, prefix + "/dense_2", precision)


def rotary_features(pos, rotated: int):
    """(..., N) integer positions -> (..., N, rotated) angles, each
    frequency twice in adjacent channels."""
    inv_freq = 1.0 / (10000 ** (jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated))
    return jnp.repeat(pos.astype(jnp.float32)[..., None] * inv_freq, 2, axis=-1)


def rotate(t, angles):
    """Rotate the first ``angles.shape[-1]`` channels of ``t`` (B, H, N, D)
    by ``angles`` (B, N, R): adjacent channels pair up."""
    r = angles.shape[-1]
    t_rot, t_pass = t[..., :r], t[..., r:]
    x1, x2 = t_rot[..., 0::2], t_rot[..., 1::2]
    half = jnp.stack((-x2, x1), axis=-1).reshape(t_rot.shape)
    a = angles[:, None]
    return jnp.concatenate([t_rot * jnp.cos(a) + half * jnp.sin(a), t_pass], axis=-1)


def attention(x_q, x_kv, w, prefix: str, heads: int, precision: str, causal: bool = False,
              angles_q=None, angles_k=None):
    """Multi-head attention of x_q (B, N, Cq) over x_kv (B, M, Ckv) with the
    projections under ``prefix``. A causal mask is right-aligned: query i
    sees keys 0..M-N+i."""
    b, n, m = x_q.shape[0], x_q.shape[1], x_kv.shape[1]
    q = dense(x_q, w, prefix + "/q_proj", precision)
    k = dense(x_kv, w, prefix + "/k_proj", precision)
    v = dense(x_kv, w, prefix + "/v_proj", precision)
    d_qk, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    q = q.reshape(b, n, heads, d_qk).transpose(0, 2, 1, 3) * d_qk ** -0.5
    k = k.reshape(b, m, heads, d_qk).transpose(0, 2, 1, 3)
    v = v.reshape(b, m, heads, d_v).transpose(0, 2, 1, 3)
    if angles_q is not None:
        q = rotate(q, angles_q)
    if angles_k is not None:
        k = rotate(k, angles_k)
    scores = einsum("bhic,bhjc->bhij", q, k, precision)
    if causal:
        visible = jnp.arange(m)[None, :] <= (m - n + jnp.arange(n))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = einsum("bhij,bhjc->bhic", probs, v, precision)
    o = o.transpose(0, 2, 1, 3).reshape(b, n, heads * d_v)
    return dense(o, w, prefix + "/o_proj", precision)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0].mean()


# ------------------------------------------------------------------ optimizer


def adamw_init(weights: dict, moment_dtype) -> dict:
    zeros = {k: jnp.zeros(v.shape, moment_dtype) for k, v in weights.items()}
    return {"count": 0, "mu": zeros, "nu": dict(zeros)}


@functools.partial(jax.jit, static_argnames=("count", "lr", "clip", "weight_decay", "b1", "b2", "eps"))
def _adamw_update(weights, grads, mu, nu, *, count, lr, clip, weight_decay, b1, b2, eps):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    factor = clip / jnp.maximum(gnorm, clip)
    bc1, bc2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    new_w, new_mu, new_nu = {}, {}, {}
    for key, p in weights.items():
        g = grads[key] * factor
        m = b1 * mu[key].astype(jnp.float32) + (1.0 - b1) * g
        v = b2 * nu[key].astype(jnp.float32) + (1.0 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
        new_w[key] = p - lr * u
        new_mu[key], new_nu[key] = m.astype(mu[key].dtype), v.astype(nu[key].dtype)
    return new_w, new_mu, new_nu


def adamw_step(weights: dict, grads: dict, state: dict, *, lr: float, clip: float,
               weight_decay: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """Clip by global norm, Adam with bias correction, decoupled weight decay
    (``p -= lr * (adam + wd * p)``). The moments are stored in the dtype
    ``adamw_init`` gave them; the update uses them before that rounding."""
    count = state["count"] + 1
    new_w, mu, nu = _adamw_update(weights, grads, state["mu"], state["nu"], count=count, lr=lr,
                                  clip=clip, weight_decay=weight_decay, b1=b1, b2=b2, eps=eps)
    return new_w, {"count": count, "mu": mu, "nu": nu}


def leaf_norms(tree: dict, scale: float = 1.0) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) * scale) for k, v in tree.items()}


# ------------------------------------------------------------ training steps


def follow_train_steps(loss_fn, weights: dict, batches, *, rows: int, moment_dtype, lr: float,
                       clip: float, weight_decay: float) -> dict:
    """Drive ``loss_fn(weights, batch) -> loss`` through ``len(batches)``
    AdamW steps, each batch in blocks of ``rows`` rows whose gradients are
    averaged (every row weighs the same: equal token counts). Returns what a
    train cell compares: each step's loss, the norm per leaf of the first
    gradient as the optimizer got it (its first moment after one step over
    ``1 - b1``), that gradient itself (``first_grad``), and the norm per leaf
    of the weights' change after all steps."""
    b1 = 0.9
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    state = adamw_init(weights, moment_dtype)
    start, losses, first_grad = weights, [], None
    for batch in batches:
        n = len(next(iter(batch.values())))
        if n % rows:
            raise ValueError(f"reference rows {rows} do not divide the batch {n}")
        loss, grads = 0.0, None
        for lo in range(0, n, rows):
            block = {k: v[lo:lo + rows] for k, v in batch.items()}
            l, g = grad_fn(weights, block)
            loss += float(l) * rows / n
            g = {k: v * (rows / n) for k, v in g.items()}
            grads = g if grads is None else {k: grads[k] + g[k] for k in g}
        losses.append(loss)
        weights, state = adamw_step(weights, grads, state, lr=lr, clip=clip, weight_decay=weight_decay, b1=b1)
        if first_grad is None:
            first_grad = {k: v.astype(jnp.float32) / (1.0 - b1) for k, v in state["mu"].items()}
    change = leaf_norms({k: weights[k] - start[k] for k in weights})
    return {"losses": losses, "grad_norms": leaf_norms(first_grad), "first_grad": first_grad,
            "update_norms": change}
