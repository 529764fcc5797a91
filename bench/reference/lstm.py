"""Plain LSTM stack: weights from a seed, and the reference forward pass.

This module imports nothing of the system under test.  It is the yardstick
``correct`` is decided by, so it is written as the textbook equations and
nothing else:

    g_t = x_t W + h_{t-1} U + b          (gate order i, f, g, o on 4H)
    c_t = sigmoid(f) * c_{t-1} + sigmoid(i) * tanh(g)
    h_t = sigmoid(o) * tanh(c_t)

with zero initial state.  A bidirectional layer runs a second set of
weights over the time-reversed input and concatenates the two outputs,
forward first, on the feature axis; the next layer reads that concat.

``stack_forward`` takes the matmul precision and a ``mode``:

  "f32"   float32 storage and arithmetic (the reference itself);
  "bf16"  every stored value in bfloat16: weights, inputs, the gate
          pre-activations and h (c and the gate nonlinearities stay
          float32), the step below float32;
  "int8"  weights rounded to int8 with one absmax scale per output
          column (weight-only; activations float32), the step below
          bfloat16 weights.

The last two are the controls: the comparison that decides ``correct``
must fail them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "int8")
_PRECISION = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}


def seed_words(seed: int):
    """A seed of any size as two 32-bit words (a JAX key holds 32 bits)."""
    s = seed % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


def make_params(seed: int, *, hidden: int, input_size: int, layers: int,
                bidirectional: bool, dtype: str):
    """Seeded weights in the layout the system is served with: per layer
    ``{"W": (X, 4H), "U": (H, 4H), "b": (4H,)}`` (or ``{"fwd": .., "bwd":
    ..}`` pairs), drawn on the device in one jitted call, stored in
    ``dtype``.  W and U are truncated normals scaled by 1/sqrt(fan-in);
    b is N(0, 0.1^2), so the bias path is exercised."""
    lo, hi = seed_words(seed)
    return _make_params(jnp.uint32(lo), jnp.uint32(hi), hidden, input_size,
                        layers, bidirectional, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _make_params(lo, hi, hidden, input_size, layers, bidirectional, dtype):
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    dt = jnp.dtype(dtype)

    def one(k, x_dim):
        kw, ku, kb = jax.random.split(k, 3)
        w = jax.random.truncated_normal(kw, -2.0, 2.0, (x_dim, 4 * hidden))
        u = jax.random.truncated_normal(ku, -2.0, 2.0, (hidden, 4 * hidden))
        b = 0.1 * jax.random.normal(kb, (4 * hidden,))
        return {"W": (w / jnp.sqrt(x_dim)).astype(dt),
                "U": (u / jnp.sqrt(hidden)).astype(dt),
                "b": b.astype(dt)}

    out, x_dim = [], input_size
    for k in jax.random.split(key, layers):
        if bidirectional:
            kf, kb = jax.random.split(k)
            out.append({"fwd": one(kf, x_dim), "bwd": one(kb, x_dim)})
            x_dim = 2 * hidden
        else:
            out.append(one(k, x_dim))
            x_dim = hidden
    return {"layers": out}


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _layer(p, xs, mode: str, precision):
    W = p["W"].astype(jnp.float32)
    U = p["U"].astype(jnp.float32)
    b = p["b"].astype(jnp.float32)
    store = jnp.bfloat16 if mode == "bf16" else jnp.float32
    if mode == "int8":
        W, U = _int8(W), _int8(U)
    W, U, b, xs = (a.astype(store) for a in (W, U, b, xs))
    B, _, _ = xs.shape
    H = U.shape[0]
    xw = (jnp.einsum("btx,xg->btg", xs, W, precision=precision,
                     preferred_element_type=jnp.float32)
          + b.astype(jnp.float32)).astype(store)

    def step(carry, xw_t):
        h, c = carry
        g = xw_t.astype(jnp.float32) + jnp.dot(
            h, U, precision=precision, preferred_element_type=jnp.float32)
        g = g.astype(store).astype(jnp.float32)
        i, f, gg, o = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
        h = (jax.nn.sigmoid(o) * jnp.tanh(c)).astype(store)
        return (h, c), h

    init = (jnp.zeros((B, H), store), jnp.zeros((B, H), jnp.float32))
    _, hs = jax.lax.scan(step, init, jnp.swapaxes(xw, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


@functools.partial(jax.jit, static_argnames=("mode", "precision"))
def stack_forward(params, xs, *, mode: str = "f32",
                  precision: str = "highest"):
    """(B, T, X) -> (B, T, H * directions), float32."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r}; one of {MODES}")
    prec = _PRECISION[precision]
    y = xs.astype(jnp.float32)
    for layer in params["layers"]:
        if "fwd" in layer:
            f = _layer(layer["fwd"], y, mode, prec)
            b = _layer(layer["bwd"], jnp.flip(y, axis=1), mode, prec)
            y = jnp.concatenate([f, jnp.flip(b, axis=1)], axis=-1)
        else:
            y = _layer(layer, y, mode, prec)
    return y.astype(jnp.float32)
