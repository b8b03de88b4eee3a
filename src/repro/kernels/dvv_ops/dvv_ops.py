"""Pallas TPU kernels: batched dotted-version-vector dominance.

Anti-entropy between replica nodes compares the clock sets of every
transferred key (paper §4.1); at production scale that is millions of
``leq`` evaluations per round.  The array encoding (core/batched.py) turns
one comparison into a handful of int32 vector ops over the replica
universe — ideal VPU work.

Layout (TPU adaptation, DESIGN.md §3.2): keys ride the 128-wide lane axis,
blocked by the grid; the replica universe rides the sublane axis, padded to
a multiple of 8.  Per-clock scalars (dot id, dot counter, valid, output)
are ``[K, keys]`` rows, so no operand wastes lanes on padding.

  * the per-clock dot lookup ``vy[ix]`` is a dynamic gather in the jnp
    reference; here it is a masked sublane-sum (`where(sub == ix, vy, 0)`),
    which maps to VPU selects + a sublane reduction instead of a gather;
  * every predicate is pure boolean algebra over comparisons — Mosaic
    refuses a boolean ``where`` against a Python literal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.batched import merge_context

NO_DOT = -1
LANES = 128
SUBLANES = 8
#: Most keys (lanes) per grid step.
MAX_BLOCK = 512
#: VMEM budget for one grid step's clock block ``int32[K, R_pad, block]``;
#: the key block shrinks (never below one lane tile) to stay inside it, so
#: K = 16 at R_pad = 128 still fits the default scoped VMEM double-buffered.
CLOCK_BLOCK_BYTES = 512 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def key_block(n: int, k: int, r_pad: int) -> int:
    """Keys per grid step: a multiple of 128, at most ``MAX_BLOCK``, and
    small enough that the ``[k, r_pad, block]`` clock block fits
    ``CLOCK_BLOCK_BYTES``."""
    fit = CLOCK_BLOCK_BYTES // (4 * k * r_pad) // LANES * LANES
    return max(LANES, min(MAX_BLOCK, fit, _round_up(n, LANES)))


def _covers(sub, vx, ix, nx, vy, iy, ny):
    """history(x) ⊆ history(y), batched → bool[..., 1, B].

    vx, vy: int32[..., R_pad, B] (replicas on sublanes, keys on lanes);
    ix/nx/iy/ny: int32[..., 1, B]; ``sub`` is the replica-axis iota.
    """
    # range coverage: 1..vx[r] ⊆ 1..vy[r] ∪ {ny at iy}
    dot_extends = (sub == iy) & (vx == ny) & (vx == vy + 1)
    range_bad = jnp.max(jnp.where((vx <= vy) | dot_extends, 0, 1),
                        axis=-2, keepdims=True)
    # dot coverage: no dot, or nx ≤ vy[ix], or (ix == iy ∧ nx == ny)
    vy_at_ix = jnp.sum(jnp.where(sub == ix, vy, 0), axis=-2, keepdims=True)
    dot_ok = (nx <= vy_at_ix) | ((iy == ix) & (nx == ny))
    return (range_bad == 0) & ((ix == NO_DOT) | dot_ok)


def _leq_kernel(vx_ref, ix_ref, nx_ref, vy_ref, iy_ref, ny_ref, out_ref):
    sub = jax.lax.broadcasted_iota(jnp.int32, vx_ref.shape, 0)
    ok = _covers(sub, vx_ref[...], ix_ref[...], nx_ref[...],
                 vy_ref[...], iy_ref[...], ny_ref[...])
    out_ref[...] = jnp.where(ok, 1, 0)


def _sync_mask_kernel(vv_ref, id_ref, n_ref, valid_ref, out_ref):
    """Fused pairwise dominance + survival for one block of keys.

    vv_ref    : int32[K, R_pad, B]
    id/n/valid: int32[K, B]
    out_ref   : int32[K, B]  — survival mask

    A loop over the partner slot j: each step reads slot j's clock and
    compares it with all K slots in both directions, so the live working
    set is a few ``[K, R_pad, B]`` blocks whatever K is (work is still the
    K² pairs).  Slot x dies to a valid partner j whose history covers its
    own, strictly or, of two equal clocks, when j is the lower slot (so a
    slot, equal to itself, never kills itself).
    """
    K, Rp, B = vv_ref.shape
    vv = vv_ref[...]
    ids, ns = (r[...].reshape(K, 1, B) for r in (id_ref, n_ref))
    sub = jax.lax.broadcasted_iota(jnp.int32, (K, Rp, B), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (K, 1, B), 0)

    def kill(j, dead):
        vj = vv_ref[pl.ds(j, 1)]                              # [1, Rp, B]
        ij, nj, ok_j = (r[pl.ds(j, 1), :].reshape(1, 1, B)
                        for r in (id_ref, n_ref, valid_ref))
        le = _covers(sub, vv, ids, ns, vj, ij, nj)            # x ⊆ j
        ge = _covers(sub, vj, ij, nj, vv, ids, ns)            # j ⊆ x
        hit = le & (~ge | (slot > j)) & (ok_j != 0)
        return dead | jnp.where(hit, 1, 0)

    dead = jax.lax.fori_loop(0, K, kill, jnp.zeros((K, 1, B), jnp.int32))
    alive = (valid_ref[...].reshape(K, 1, B) != 0) & (dead == 0)
    out_ref[...] = jnp.where(alive, 1, 0).reshape(K, B)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dvv_sync_mask_pallas(vvs, dot_ids, dot_ns, valid, *,
                         interpret: bool = True):
    """Which clocks of each key's combined set survive sync — one launch.

    vvs: int32[N, K, R]; dot_ids/dot_ns: int32[N, K]; valid: bool[N, K].
    Returns bool[N, K].  Semantics identical to ``core.batched.sync_mask``.
    """
    return _survival_mask(vvs, dot_ids, dot_ns, valid, interpret,
                          "dvv_sync_mask")


def _survival_mask(vvs, dot_ids, dot_ns, valid, interpret: bool,
                   name: str):
    """The survival kernel's launch, named ``name`` on the device trace
    (the write path's and the read sweep's masks run the same kernel)."""
    N, K, R = vvs.shape
    if N == 0 or K == 0:
        return jnp.zeros((N, K), bool)
    Rp = _round_up(max(R, 1), SUBLANES)
    block = key_block(N, K, Rp)
    Np = _round_up(N, block)

    vv_t = jnp.pad(vvs.astype(jnp.int32), ((0, Np - N), (0, 0), (0, Rp - R))
                   ).transpose(1, 2, 0)                        # [K, Rp, Np]

    def rows(a, fill=0):
        return jnp.pad(a.astype(jnp.int32), ((0, Np - N), (0, 0)),
                       constant_values=fill).T                 # [K, Np]

    narrow = pl.BlockSpec((K, block), lambda i: (0, i))
    out = pl.pallas_call(
        _sync_mask_kernel,
        grid=(Np // block,),
        in_specs=[pl.BlockSpec((K, Rp, block), lambda i: (0, 0, i)),
                  narrow, narrow, narrow],
        out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct((K, Np), jnp.int32),
        interpret=interpret,
        name=name,
    )(vv_t, rows(dot_ids, NO_DOT), rows(dot_ns), rows(valid))
    return out[:, :N].T != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def dvv_read_sweep_pallas(vvs, dot_ids, dot_ns, valid, *,
                          interpret: bool = True):
    """Survival mask and per-key §5.4 ceiling of the survivors, one
    program: ``(bool[N, K], int32[N, R])``."""
    mask = _survival_mask(vvs, dot_ids, dot_ns, valid, interpret,
                          "dvv_read_sweep_mask")
    return mask, merge_context(vvs, dot_ids, dot_ns, mask)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dvv_leq_pallas(vx, ix, nx, vy, iy, ny, *, interpret: bool = True):
    """history(x_k) ⊆ history(y_k) for k in [N].

    vx, vy: int32[N, R]; ix/nx/iy/ny: int32[N].  Returns bool[N].
    """
    N, R = vx.shape
    Rp = _round_up(max(R, 1), SUBLANES)
    block = key_block(N, 1, Rp)
    Np = _round_up(N, block)

    def clocks(a):
        return jnp.pad(a.astype(jnp.int32), ((0, Np - N), (0, Rp - R))).T

    def row(a, fill=0):
        return jnp.pad(a.astype(jnp.int32), (0, Np - N),
                       constant_values=fill)[None, :]

    wide = pl.BlockSpec((Rp, block), lambda i: (0, i))
    narrow = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        _leq_kernel,
        grid=(Np // block,),
        in_specs=[wide, narrow, narrow, wide, narrow, narrow],
        out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        interpret=interpret,
        name="dvv_leq",
    )(clocks(vx), row(ix, NO_DOT), row(nx), clocks(vy), row(iy, NO_DOT),
      row(ny))
    return out[0, :N] != 0
