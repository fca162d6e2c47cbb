"""Batched multi-tenant LoRA: a stacked adapter bank applied inside
the one fixed-shape decode program.

A fine-tuned variant served as its own engine costs a full parameter
copy, its own KV pool, and its own compiled closures — N tenants cost
N x HBM and N x compile caches. LoRA (Hu et al., 2021) collapses that:
a tenant is a low-rank delta ``y = base(x) + (x @ A) @ B * (alpha/r)``
over frozen base weights, a few percent of the parameter bytes. The
serving twist here is the BATCHED bank: all adapters of one engine
live stacked as

    A:     (n_adapters, d_in, rank)
    B:     (n_adapters, rank, d_out)
    scale: (n_adapters,)            # alpha / rank per adapter

and one decode step over B slots gathers each row's adapter INSIDE the
trace by a per-slot ``(B,)`` int32 index vector::

    y[b] = base(x[b]) + (x[b] @ A[idx[b]]) @ B[idx[b]] * scale[idx[b]]

so a batch mixing any number of tenants (base-model rows included)
runs ONE compiled program — the index vector is runtime data, exactly
like the int8 quant tables of ops/quantized.py. Adapter slot 0 is
RESERVED all-zeros: a base-model request rides the same program and
its delta is exactly ``+ 0.0``, bit-identical to a LoRA-free engine
(the engine maps "no adapter" to index 0 and never hands slot 0 to a
tenant).

The ``ops.lora.trace`` telemetry counter increments only when a
LoRA-bearing closure actually TRACES (this module's ``apply`` runs at
trace time only) — the bank analog of ``model.gpt.trace``, which
tests/test_lora.py holds flat across adapter load, refresh and
unload.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import telemetry, tracing

__all__ = ["init_bank", "set_slot", "clear_slot", "apply"]


def init_bank(n_adapters, d_in, d_out, rank):
    """Allocate an all-zeros stacked adapter bank for one projection:
    ``{"A": (n, d_in, r), "B": (n, r, d_out), "scale": (n,)}`` fp32.
    Slot 0 is the reserved base-model (all-zeros) adapter — ``n``
    must leave at least one loadable slot beside it."""
    n, r = int(n_adapters), int(rank)
    if r < 1:
        raise ValueError(f"lora rank must be >= 1, got {rank}")
    if n < 2:
        raise ValueError(
            f"n_adapters must be >= 2 (slot 0 is the reserved "
            f"all-zeros base adapter), got {n_adapters}")
    return {
        "A": jnp.zeros((n, int(d_in), r), jnp.float32),
        "B": jnp.zeros((n, r, int(d_out)), jnp.float32),
        "scale": jnp.zeros((n,), jnp.float32),
    }


def set_slot(bank, idx, a, b, alpha):
    """Install adapter ``(a, b, alpha)`` into bank slot ``idx``
    (host-side: returns a NEW bank pytree with the same structure —
    the closures take the bank as a runtime argument, so installing
    refreshed arrays retraces nothing). Slot 0 is immutable."""
    idx = int(idx)
    n, d_in, r = bank["A"].shape
    d_out = bank["B"].shape[2]
    if idx == 0:
        raise ValueError("adapter slot 0 is the reserved all-zeros "
                         "base adapter and cannot be written")
    if not 0 < idx < n:
        raise ValueError(f"adapter slot {idx} out of range (bank holds "
                         f"{n} slots)")
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if a.shape != (d_in, r):
        raise ValueError(f"adapter A shape {a.shape} != bank slot "
                         f"shape {(d_in, r)}")
    if b.shape != (r, d_out):
        raise ValueError(f"adapter B shape {b.shape} != bank slot "
                         f"shape {(r, d_out)}")
    return {
        "A": bank["A"].at[idx].set(a),
        "B": bank["B"].at[idx].set(b),
        "scale": bank["scale"].at[idx].set(float(alpha) / r),
    }


def clear_slot(bank, idx):
    """Zero bank slot ``idx`` back to the base (no-op) adapter —
    same runtime-argument/no-retrace contract as :func:`set_slot`."""
    idx = int(idx)
    if idx == 0:
        raise ValueError("adapter slot 0 is already the reserved "
                         "all-zeros base adapter")
    return {
        "A": bank["A"].at[idx].set(0.0),
        "B": bank["B"].at[idx].set(0.0),
        "scale": bank["scale"].at[idx].set(0.0),
    }


def apply(y, x, bank, idx):
    """``y + (x @ A[idx]) @ B[idx] * scale[idx]`` — the batched
    adapter delta over a projection's pre-activation output.

    ``y``/``x`` are ``(B, S, d_out)``/``(B, S, d_in)`` (decode steps
    run S=1), ``idx`` is the per-row ``(B,)`` int32 adapter index —
    gathered inside the trace, so tenant mix is runtime data. Rows
    with ``idx == 0`` add an exact ``0.0`` (slot 0 is all-zeros):
    base-model rows are bit-identical to the LoRA-free program's
    output. The low-rank factors contract in fp32 regardless of the
    base path (int8 engines keep the delta fp32 over the dequant
    base)."""
    telemetry.counter("ops.lora.trace")  # trace-time only
    tracing.flight.record("compile", what="ops.lora")
    idx = jnp.asarray(idx, jnp.int32)
    a = bank["A"][idx]                          # (B, d_in, r)
    b = bank["B"][idx]                          # (B, r, d_out)
    s = bank["scale"][idx]                      # (B,)
    lo = jnp.einsum("bsd,bdr->bsr", jnp.asarray(x, jnp.float32), a)
    delta = jnp.einsum("bsr,bro->bso", lo, b) * s[:, None, None]
    return y + delta
