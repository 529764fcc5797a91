"""Shared kernel utilities."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode everywhere but real TPUs."""
    return jax.default_backend() != "tpu"


def mxu_bf16_operands(interpret: bool) -> bool:
    """Whether a kernel's float32 dot runs as one bfloat16 MXU pass with
    float32 accumulation: compiled for the TPU (not interpreted) at the
    caller's default matmul precision.  Mosaic lowers such a dot in the
    MXU's f32 mode, which rounds both operands to bfloat16, so bfloat16
    operands give the same products at half the width in the MXU's
    latches.  The CPU's interpreted dot is exact float32, and a caller's
    ``jax.default_matmul_precision("highest")`` asks for float32
    contraction: neither runs one bfloat16 pass."""
    if interpret:
        return False
    level = jax.config.jax_default_matmul_precision
    if level is None:
        return True
    try:
        return jax.lax.Precision(level) == jax.lax.Precision.DEFAULT
    except ValueError:      # a dot algorithm preset, not a precision level
        return False


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def seq_vmem_limit_of(U, xw, h0, bt: int, *, quant: bool, sparse: bool,
                      masked: bool) -> int:
    """A sequence-kernel launch's scoped VMEM limit, from the one
    footprint model the planner and plancheck budget with
    (``core.tiling``), at the launch's own shapes and dtypes: U
    (G, Hr, gates·H), xw (G, B, T, gates, H), h0 (G, B, H)."""
    from repro.core.tiling import seq_block_footprint, seq_vmem_limit

    _, B, _, gates, H = xw.shape
    return seq_vmem_limit(seq_block_footprint(
        bt, B, H, gates=gates,
        act_bytes=max(xw.dtype.itemsize, h0.dtype.itemsize),
        weight_bytes=U.dtype.itemsize, precision="int8" if quant else "fp32",
        density=U.shape[1] / H if sparse else 1.0, masked=masked))


def ragged_b_mask(G: int, B: int, b_valid):
    """(G, B) int32 validity mask from per-cell valid row counts (ragged-B
    packing): mask[g, b] = 1 iff b < b_valid[g].  Shared by the sequence
    kernels' ``b_valid`` plumbing."""
    import jax
    import jax.numpy as jnp

    return (jax.lax.broadcasted_iota(jnp.int32, (G, B), 1)
            < jnp.asarray(b_valid, jnp.int32)[:, None]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# structural launch accounting
# ---------------------------------------------------------------------------


def _subjaxprs(value):
    """Yield any jaxprs hiding inside an eqn param value."""
    if hasattr(value, "jaxpr"):          # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):         # raw Jaxpr
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def _count_launches(jaxpr, mult: int) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += mult
        inner_mult = mult
        if eqn.primitive.name == "scan":
            inner_mult = mult * eqn.params.get("length", 1)
        for value in eqn.params.values():
            for sub in _subjaxprs(value):
                total += _count_launches(sub, inner_mult)
    return total


def pallas_launch_count(fn, *args, **kwargs) -> int:
    """Number of pallas_call launches ``fn(*args)`` issues at runtime.

    Traverses the jaxpr, multiplying launches under ``lax.scan`` by the trip
    count — the structural proof behind "1 launch vs T" claims (a scanned
    per-step kernel traces once but launches T times)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count_launches(closed.jaxpr, 1)
