"""The Mamba-2 recurrence for serving: a chunked scan for prefill and the
decode-step state update, a Pallas TPU kernel with a jnp twin.

Per head i (of group g = i // (heads / groups)) a sequence keeps a state
`S` of `head_dim x state` numbers and, with `a_t = exp(dt_t A_i)`:

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t[g]        y_t = S_t C_t[g]

(the skip `D_i x_t` is the caller's: one multiply outside). At rest the
state is laid **`[heads, state, head_dim]`**, the head's own width last:
`x`, `dt x` and `y` are then rows along the lanes and only `B` and `C`,
shared by a group's heads, stand as columns.

**Prefill** (`ssd_chunked_scan`, plain jnp): the sequence is cut into
chunks of `mamba_chunk_size`; within a chunk the outputs are matmuls
(`(C B^T * decay) (dt x)`), each chunk's contribution to the state is one
more, and a `lax.scan` over the chunks carries the state between them. A
position with `dt = 0` decays nothing and adds nothing, which is how
padding behind a prompt leaves the state as the last real token left it.
Everything here is float32 at `highest` precision: it is ~1 GFLOP a layer
beside matmuls a hundred times that.

**Decode** (`ssm_decode_step`): one token a lane. The whole cost is
moving the state: each live lane's `[heads, state, head_dim]` float32
block is read once and written once (4.19 MB a layer at 32 x 256 x 128),
six operations an element in between. The pool of every layer and lane,
`[L, slots, heads, state, head_dim]`, stays where it rests:

  - `ssm_state_reference` — the same arithmetic in jnp over one layer of
    the pool, idle lanes selected back to what they were;
  - `ssm_state_pallas` — grid `(slots, heads / head_block)`, the pool
    aliased onto the output (`input_output_aliases`) and blocked `(1, 1,
    head_block, state, head_dim)` by an index map over scalar-prefetched
    lane ids: the live lanes first, in order. A program multiplies its
    block by `a`, adds `B (x) dt x`, writes it back and returns `S C`
    from the tile it holds; `B` and `C` are turned into lane-replicated
    columns once a program (a sublane broadcast and one transpose).
    **An idle lane is neither read nor written**: the programs behind
    the last live lane's are mapped onto that lane's last block, which
    the pipeline neither fetches nor writes again, and they compute
    nothing. Their `y` is zeroed outside.

`impl` is resolved as attention's is (`serving.attention_impl`: `auto` is
the kernel on a TPU); off a TPU the kernel runs only interpreted, for
tests. `live_lanes` is the same count on the host for `engine.stats()`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from determined_tpu.ops._pallas_common import HAVE_PALLAS

if HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Prefill: the chunked scan.
# ---------------------------------------------------------------------------


def ssd_chunked_scan(
    x: jax.Array,    # [S, H, P]
    dt: jax.Array,   # [S, H] float32, softplus applied; 0 where padded
    a: jax.Array,    # [H] float32: A = -exp(A_log)
    b: jax.Array,    # [S, G, N]
    c: jax.Array,    # [S, G, N]
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """One sequence from a zero state → (y [S, H, P] float32 without the
    skip term, the state after the last position [H, N, P] float32)."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    per = heads // groups
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(nc, chunk, groups, per)
    xs = dt[..., None] * x.astype(f32).reshape(nc, chunk, groups, per, p)
    b = b.astype(f32).reshape(nc, chunk, groups, n)
    c = c.astype(f32).reshape(nc, chunk, groups, n)
    cum = jnp.cumsum(dt * a.reshape(groups, per), axis=1)   # [nc, Q, G, per]
    # within a chunk: position l reads every s <= l, decayed from s to l
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(
        causal, cum[:, :, None] - cum[:, None, :], -jnp.inf))
    cb = jnp.einsum("clgn,csgn->clsg", c, b, precision=HIGHEST)
    y = jnp.einsum("clsgr,csgrp->clgrp", cb[..., None] * decay, xs,
                   precision=HIGHEST)
    # what a chunk adds to the state by its end, and what it keeps of it
    to_end = jnp.exp(cum[:, -1:] - cum)                      # [nc, Q, G, per]
    added = jnp.einsum("csgn,csgr,csgrp->cgrnp", b, to_end, xs,
                       precision=HIGHEST)
    kept = jnp.exp(cum[:, -1])                               # [nc, G, per]

    def carry_state(state, at):
        c_k, from_start, added_k, kept_k = at
        # the state the chunk opened with, read at every position
        y_off = jnp.einsum("lgn,grnp->lgrp", c_k, state, precision=HIGHEST)
        state = state * kept_k[..., None, None] + added_k
        return state, y_off * from_start[..., None]

    state, y_off = jax.lax.scan(
        carry_state, jnp.zeros((groups, per, n, p), f32),
        (c, jnp.exp(cum), added, kept))
    y = (y + y_off).reshape(nc * chunk, heads, p)[:s]
    return y, state.reshape(heads, n, p)


# ---------------------------------------------------------------------------
# Decode: the state update.
# ---------------------------------------------------------------------------


def ssm_state_reference(x, dt, b, c, pool, layer, live, a):
    """The decode step's state update in jnp → (pool', y [slots, H, P]
    float32). x [slots, H, P]; dt [slots, H] float32; b, c [slots, G, N];
    pool [L, slots, H, N, P]; live [slots] bool; a [H] float32."""
    f32 = jnp.float32
    heads, groups = x.shape[1], b.shape[1]
    state = pool[layer].astype(f32)
    bh, ch = (jnp.repeat(t.astype(f32), heads // groups, axis=1)
              for t in (b, c))                               # [slots, H, N]
    new = state * jnp.exp(dt * a)[..., None, None] \
        + bh[..., :, None] * (dt[..., None] * x.astype(f32))[..., None, :]
    y = jnp.sum(new * ch[..., :, None], axis=2)
    keep = live[:, None, None, None]
    pool = pool.at[layer].set(
        jnp.where(keep, new, state).astype(pool.dtype))
    return pool, jnp.where(live[:, None, None], y, 0.0)


def kernel_refusal(heads: int, groups: int, head_dim: int,
                   state: int) -> Optional[str]:
    """Why the state kernel cannot take this geometry, or None."""
    if not HAVE_PALLAS:
        return "pallas is not in this jax build"
    if heads % groups:
        return f"{heads} mixer heads do not divide into {groups} groups"
    if head_dim % 128 or state % 8:
        return (f"a state tile of {state} x {head_dim} is not whole "
                "(8, 128) float32 tiles")
    return None


def head_block(heads: int, groups: int) -> int:
    """Heads a program of the kernel takes: a divisor of a group's heads
    (a block shares its B and C), at most 8 (1 MiB of float32 state at
    256 x 128, four such buffers in flight)."""
    per = heads // groups
    return next(hb for hb in (8, 4, 2, 1) if per % hb == 0)


def live_lanes(live) -> int:
    """Lanes whose state a decode call moves in one layer (host
    arithmetic for `engine.stats()`): the live ones."""
    return int(np.count_nonzero(np.asarray(live)))


def _state_kernel(ids_ref, n_ref, lay_ref, decay_ref, dtx_ref, b_ref, c_ref,
                  s_ref, y_ref, o_ref, *, hb):
    del ids_ref, lay_ref            # read by the index maps
    n_live = n_ref[0]
    width = s_ref.shape[-1]

    @pl.when(pl.program_id(0) < n_live)
    def _live():
        # B and C of the block's group as columns, replicated over the
        # lanes: rows first (a sublane broadcast), then one transpose.
        def column(ref):
            row = ref[0, 0].astype(jnp.float32)              # [1, N]
            return jnp.broadcast_to(row, (width, row.shape[-1])).T

        b_col, c_col = column(b_ref), column(c_ref)          # [N, P]
        for h in range(hb):
            new = s_ref[0, 0, h].astype(jnp.float32) \
                * decay_ref[0, 0, h:h + 1, :] \
                + b_col * dtx_ref[0, 0, h:h + 1, :]
            o_ref[0, 0, h] = new.astype(o_ref.dtype)
            y_ref[0, 0, h:h + 1, :] = jnp.sum(new * c_col, axis=0,
                                               keepdims=True)

    @pl.when(n_live == 0)
    def _nobody():
        # Every program stands on one block, which is written back once:
        # as it was.
        o_ref[...] = s_ref[...]


def ssm_state_pallas(x, dt, b, c, pool, layer, live, a,
                     interpret: bool = False):
    """`ssm_state_reference` as a Pallas TPU kernel; the pool is updated
    in place."""
    slots, heads, p = x.shape
    groups, n = b.shape[1:]
    why_not = None if interpret else kernel_refusal(heads, groups, p, n)
    if why_not:
        raise ValueError(f"the state kernel cannot run: {why_not}")
    per = heads // groups
    hb = head_block(heads, groups)
    nhb = heads // hb
    f32 = jnp.float32
    # Live lanes first, in order; behind them the last live lane again.
    n_live = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(slots) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])
    # Per head and lane of the head's width: the decay and dt x, as rows.
    decay = jnp.broadcast_to(jnp.exp(dt.astype(f32) * a)[..., None],
                             (slots, heads, p)).reshape(slots, nhb, hb, p)
    dtx = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(
        slots, nhb, hb, p)

    def at(i, j, ids, n, lay):
        """(lane, head block) of program (i, j): its own while i is live,
        else the last live program's."""
        return ids[i], jnp.where(i < n[0], j, nhb - 1)

    def rows(i, j, ids, n, lay):
        return (*at(i, j, ids, n, lay), 0, 0)

    def group(i, j, ids, n, lay):
        lane, block = at(i, j, ids, n, lay)
        return lane, block * hb // per, 0, 0

    def state(i, j, ids, n, lay):
        return (lay[0], *at(i, j, ids, n, lay), 0, 0)

    row_block = pl.BlockSpec((1, 1, hb, p), rows)
    group_block = pl.BlockSpec((1, 1, 1, n), group)
    state_block = pl.BlockSpec((1, 1, hb, n, p), state)
    y, pool = pl.pallas_call(
        functools.partial(_state_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,     # lane ids, live count, layer
            grid=(slots, nhb),
            in_specs=[row_block, row_block, group_block, group_block,
                      state_block],
            out_specs=[row_block, state_block]),
        out_shape=[jax.ShapeDtypeStruct((slots, nhb, hb, p), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},   # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(6 * slots * heads * n * p), transcendentals=0,
            bytes_accessed=int(2 * slots * heads * n * p
                               * pool.dtype.itemsize)),
        interpret=interpret,
    )(ids, jnp.reshape(n_live, (1,)),
      jnp.reshape(layer, (1,)).astype(jnp.int32), decay, dtx,
      b[:, :, None, :], c[:, :, None, :], pool)
    y = jnp.where(live[:, None, None], y.reshape(slots, heads, p), 0.0)
    return pool, y


@jax.jit
def ssm_state_update(x, dt, b, c, pool, layer, live, a):
    """The kernel under a name of its own: a device trace calls a custom
    call after the innermost function traced around it, and a scan's body
    is a `closed_call` like any other kernel's."""
    return ssm_state_pallas(x, dt, b, c, pool, layer, live, a)


def ssm_decode_step(x, dt, b, c, pool, layer, live, a,
                    impl: str = "reference"):
    """Dispatch by `serving.attention_impl` ("pallas" | "reference")."""
    if impl == "pallas":
        return ssm_state_update(x, dt, b, c, pool, layer, live, a)
    if impl == "reference":
        return ssm_state_reference(x, dt, b, c, pool, layer, live, a)
    raise ValueError(f"unknown state update impl {impl!r}")
