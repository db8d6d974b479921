"""Pallas kernels of the hybrid stacks' two decode-bound layers (TPU).

`ssm_state_update` — one decode step of the Mamba-2 recurrence for every
lane: h ← dA · h + (Δ·x) ⊗ B, y = h · C, with the float32 state
[B, H, P, N] aliased in place. Memory-bound: the state is read once and
written once (64 lanes × 4 MiB a layer), everything else is kilobytes.
Δ·x reaches the kernel transposed, channels on sublanes and heads on
lanes ([B, G, P, Hg]), so that a head's factors are a [P, 1] column that
broadcasts along the state's lanes, and y leaves the same way (XLA does
those small transposes); a head's decay is a scalar read from SMEM, and
h · C runs on the otherwise idle MXU. Alone on the chip at the published
shape (my chip runs, PR 43): 0.906 ms a call against 0.656 of bytes; the
form with the decay broadcast from a column and the reduction over lanes
on the vector units read 1.167, XLA's own fusion 0.871.

`moe_held_experts` — the held experts of an expert layer as ONE pass over
their weights: for every held expert e, a(v, e) · w[:, e] · W_down[e],
summed over e into a float32 [rows, width of v]. Two instances, chosen by
static arguments: un-gated, a = relu(v · W_up[e])² (the experts of a
latent layer), and gated, a = silu(v · W_gate[e]) ⊙ (v · W_up[e]) (experts
on the full hidden: three matrices an expert). Every expert's weights are
read whatever the routing chose (the combine weight of an expert a row did
not choose is 0): a step's work is fixed by rows × experts held, not by
the seed. Rows tile outermost, so the output tile stays resident while
the experts stream past; a dispatch wider than one row tile re-reads the
weights once per tile.

Off-TPU both run the same mathematics in jax.numpy (`*_jnp`); the tests
run the kernels in interpret mode against them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import _activate

_VMEM_LIMIT = 64 * 1024 * 1024
MOE_ROW_TILE = 512


def use_kernels() -> bool:
    return jax.default_backend() == "tpu"


# -- Mamba-2 decode state update -------------------------------------------


def ssm_state_update_jnp(h, dA, xdt, Bm, Cm):
    """h [B, H, P, N] f32, dA [B, H], xdt [B, H, P], Bm / Cm [B, G, N]
    (all float32) → (h_new, y [B, H, P])."""
    B, H, P, N = h.shape
    G = Bm.shape[1]
    hg = h.reshape(B, G, H // G, P, N)
    new = (dA.reshape(B, G, H // G, 1, 1) * hg
           + xdt.reshape(B, G, H // G, P, 1) * Bm[:, :, None, None, :])
    y = jnp.sum(new * Cm[:, :, None, None, :], axis=-1)
    return new.reshape(B, H, P, N), y.reshape(B, H, P)


def _ssm_kernel(da_ref, x_ref, b_ref, c_ref, h_ref, out_ref, y_ref):
    # da [B, H] whole, in SMEM (a head's decay is a scalar); blocks of one
    # lane: x [G, P, Hg], b / c [G, N], h / out [G·Hg, P, N], y [G, P, Hg].
    G, N = b_ref.shape
    Hg = y_ref.shape[-1]
    lane = pl.program_id(0)

    def group(g, carry):
        xs = x_ref[g]                              # [P, Hg]
        bn = b_ref[pl.ds(g, 1), :]                 # [1, N]
        # h · C on the MXU, which is idle here: every column of the
        # product is the same y; float32 through the multi-pass form.
        cmat = jnp.broadcast_to(c_ref[pl.ds(g, 1), :], (N, N))
        for j in range(Hg):
            head = g * Hg + j
            new = da_ref[lane, head] * h_ref[head] + xs[:, j:j + 1] * bn
            out_ref[head] = new
            y = jax.lax.dot_general(
                new, cmat, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            y_ref[g, :, j:j + 1] = y[:, j:j + 1]
        return carry

    jax.lax.fori_loop(0, G, group, 0)


def ssm_state_update(h, dA, xdt, Bm, Cm, *, interpret: bool = False):
    """The kernel form of `ssm_state_update_jnp`; `h` is updated in place
    (aliased), so the caller's donated state buffer is the output."""
    B, H, P, N = h.shape
    G = Bm.shape[1]
    Hg = H // G
    # [B, G, P, Hg]: column j is head j's Δ·x over P.
    cols = xdt.reshape(B, G, Hg, P).transpose(0, 1, 3, 2)
    lane = lambda b: (b, 0, 0, 0)  # noqa: E731
    new, y = pl.pallas_call(
        _ssm_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, G, P, Hg), lane),
            pl.BlockSpec((None, G, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, G, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, H, P, N), lane),
        ],
        out_specs=[
            pl.BlockSpec((None, H, P, N), lane),
            pl.BlockSpec((None, G, P, Hg), lane),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(h.shape, h.dtype),
            jax.ShapeDtypeStruct((B, G, P, Hg), jnp.float32),
        ],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(dA, cols, Bm, Cm, h)
    return new, y.transpose(0, 1, 3, 2).reshape(B, H, P)


# -- held experts of an expert layer ---------------------------------------

def moe_held_experts_jnp(v, up, down, weights, *, gate=None,
                         activation: str = "relu2"):
    """v [R, L], up [E, L, I], down [E, I, L], weights [R, E] float32
    (0 where a row did not choose the expert) → float32 [R, L]. `gate`
    [E, L, I]: the gated form, activation(v · gate) ⊙ (v · up)."""
    h = jnp.einsum("rl,eli->eri", v, up, preferred_element_type=jnp.float32)
    if gate is None:
        a = _activate(h, activation)
    else:
        a = _activate(jnp.einsum("rl,eli->eri", v, gate,
                                 preferred_element_type=jnp.float32),
                      activation) * h
    a = a * weights.T[:, :, None]
    return jnp.einsum("eri,eil->rl", a.astype(v.dtype), down,
                      preferred_element_type=jnp.float32)


def _moe_kernel(*refs, activation: str, gated: bool):
    if gated:
        v_ref, w_ref, gate_ref, up_ref, down_ref, out_ref = refs
    else:
        v_ref, w_ref, up_ref, down_ref, out_ref = refs
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = v_ref[...]
    h = jnp.dot(v, up_ref[...], preferred_element_type=jnp.float32)
    if gated:
        a = _activate(jnp.dot(v, gate_ref[...],
                              preferred_element_type=jnp.float32),
                      activation) * h
    else:
        a = _activate(h, activation)
    a = a * w_ref[...]
    out_ref[...] += jnp.dot(a.astype(v.dtype), down_ref[...],
                            preferred_element_type=jnp.float32)


def _inner_tile(inner: int) -> int:
    """The widest 128-aligned divisor of the experts' width up to 1024
    (2688 → 896, 1536 → 768): a block of each weight is then a few MiB."""
    best = inner
    for t in range(128, min(inner, 1024) + 1, 128):
        if inner % t == 0:
            best = t
    return best


def moe_held_experts(v, up, down, weights, *, gate=None,
                     activation: str = "relu2", interpret: bool = False):
    """The kernel form of `moe_held_experts_jnp`."""
    R, L = v.shape
    E, _, inner = up.shape
    tile = R if R <= MOE_ROW_TILE else MOE_ROW_TILE
    pad = -R % tile
    if pad:
        v = jnp.pad(v, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    rows = R + pad
    it = _inner_tile(inner)
    # [E, rows, 1]: an expert's weights as a column that broadcasts along
    # the activation's lanes.
    wcol = weights.astype(jnp.float32).T[:, :, None]
    into = pl.BlockSpec((None, L, it), lambda r, e, i: (e, 0, i))
    ins = [up] if gate is None else [gate, up]
    out = pl.pallas_call(
        functools.partial(_moe_kernel, activation=activation,
                          gated=gate is not None),
        grid=(rows // tile, E, inner // it),
        in_specs=[
            pl.BlockSpec((tile, L), lambda r, e, i: (r, 0)),
            pl.BlockSpec((None, tile, 1), lambda r, e, i: (e, r, 0)),
            *[into] * len(ins),
            pl.BlockSpec((None, it, L), lambda r, e, i: (e, i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, L), lambda r, e, i: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_held_experts",
    )(v, wcol, *ins, down)
    return out[:R]
