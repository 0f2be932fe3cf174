"""Tiled Pallas kernel for the fused engine step.

One ``pl.pallas_call`` fuses the three bank-side stages of a simulated
cycle (see ``ref.py`` for the op-by-op oracle):

1. **arbitration** — per-bank FIFO lexicographic (arrival stamp, rotated
   priority) segment-min over the parked requests, computed as a running
   two-key min over ``(block_a, block_n)`` tiles of the dense ``(a, n)``
   request matrix;
2. **protocol update** — the protocol's :meth:`Protocol.fused_access`
   dense bank-state update, traced over this block's bank lanes;
3. **histogram** — the completion-latency histogram rows for this
   block's retiring grants.

Grid: ``(a // block_a,)`` bank tiles; the core dimension is swept by an
in-kernel ``fori_loop`` over ``n // block_n`` chunks, so no grid cell
ever depends on another (safe on parallel GPU grids, trivially correct
under ``interpret=True`` on CPU).  Bank-state arrays follow one of two
layout rules, which the protocol declares (``Protocol.bank_last``): by
default their leading dim is ``m * a`` for a per-protocol ``m`` (flat
Colibri queues: m=1), so every bank array blocks to ``(m * block_a,
...)`` at tile ``at``; with ``bank_last`` every bank array is ``(r, a)``
with the bank on the last (lane) axis (the hierarchical local queues:
``r`` = n_groups, or n_groups × the local queue's slots), so it blocks
to ``(r, block_a)`` at ``(0, at)``.  1-D bank arrays travel as ``(1,
m * a)`` rows and block on the last axis under either rule.

Per-tile partial outputs (histogram rows, [polls, msgs, lat_max] stat
rows) are reduced OUTSIDE the kernel — cross-tile accumulation through a
shared output block is exactly the pattern that breaks on parallel
grids.

The body lowers for the TPU (Mosaic): no gather or scatter by a computed
index (the winner's per-core values ride the arbitration merge as
one-hot selects; protocols use the dense helpers of
``core.protocols.base``), no bool in memory, and every 1-D operand as a
``(1, w)`` row.  ``tests/test_tpu_compile.py`` compiles it for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.metrics import LAT_BINS, LAT_SUB
from repro.core.protocols.base import (OUT_DONE, OUT_FAIL, OUT_GRANT,
                                       OUT_SLEEP, P_ACQ, P_REL, FusedCtx)
from repro.kernels.engine_step.ref import _BIG, _param_ns

#: number of reduced stat columns per tile: [polls, msgs, lat_max]
_N_STATS = 3


def _kernel(*refs, proto, p, n, block_a, block_n, q_cap, cycles,
            core_names, bank_names, xset_names, bool_banks, row_banks):
    n_core, n_bank, n_xset = len(core_names), len(bank_names), len(xset_names)
    nin = 6 + n_core + n_bank
    scal_ref, cand_ref, rot_ref, addr_ref, phase_ref, acq_ref = refs[:6]
    core_refs = dict(zip(core_names, refs[6:6 + n_core]))
    bank_refs = dict(zip(bank_names, refs[6 + n_core:nin]))
    outs = refs[nin:]
    valid_ref, win_ref, kind_ref, tmr_ref = outs[:4]
    bank_out = dict(zip(bank_names, outs[4:4 + n_bank]))
    xv_refs = dict(zip(xset_names, outs[4 + n_bank:4 + n_bank + n_xset]))
    xm_refs = dict(zip(xset_names,
                       outs[4 + n_bank + n_xset:4 + n_bank + 2 * n_xset]))
    stats_ref, hist_ref = outs[-2:]

    # 1-D operands travel as (1, w) rows (see fused_step_call): read row
    # 0, write through a leading unit axis
    scal = scal_ref[0]
    cyc, shift, lat = scal[0], scal[1], scal[2]
    # global bank ids of this tile's lanes
    bl = (pl.program_id(0) * block_a
          + jax.lax.broadcasted_iota(jnp.int32, (block_a,), 0))

    # ---- stage 1: chunked two-key segment-min over the core dimension.
    # Running (stamp, rot) pair per bank lane; merging a chunk keeps the
    # smaller stamp, and on stamp ties the smaller rot — associative, so
    # chunk order never matters and the result equals the global
    # lexicographic min (= ref.py's one-shot dense min).  The winner's
    # per-core values (phase, acquire stamp, the protocol's core fields)
    # ride along: each chunk picks its own winner's values by a one-hot
    # select (rot is a permutation, so at most one lane per bank), and the
    # merge keeps them with the pair.  Mosaic lowers no gather by a
    # computed index, and this never builds a (block_a, n) temporary.
    gat_refs = (phase_ref, acq_ref) + tuple(core_refs[f] for f in core_names)

    def merge(i, carry):
        run_cyc, run_rot, run_vals = carry
        sl = pl.ds(i * block_n, block_n)
        cand, rot, adr = cand_ref[0, sl], rot_ref[0, sl], addr_ref[0, sl]
        m = adr[None, :] == bl[:, None]                # (block_a, block_n)
        c2 = jnp.where(m, cand[None, :], _BIG)
        t_cyc = jnp.min(c2, axis=1)
        tie = (c2 == t_cyc[:, None]) & (c2 != _BIG)
        r2 = jnp.where(tie, rot[None, :], _BIG)
        t_rot = jnp.min(r2, axis=1)
        sel = tie & (r2 == t_rot[:, None])             # chunk winner lane
        better = (t_cyc < run_cyc) | ((t_cyc == run_cyc) & (t_rot < run_rot))
        run_vals = tuple(
            jnp.where(better,
                      jnp.sum(jnp.where(sel, r[0, sl][None, :], 0), axis=1),
                      v)
            for r, v in zip(gat_refs, run_vals))
        return (jnp.where(better, t_cyc, run_cyc),
                jnp.where(better, t_rot, run_rot), run_vals)

    zeros = jnp.zeros((block_a,), jnp.int32)
    init = (jnp.full((block_a,), _BIG, jnp.int32),
            jnp.full((block_a,), _BIG, jnp.int32),
            (zeros,) * len(gat_refs))
    best_cyc, best_rot, (phase_w, acq_w, *core_w) = jax.lax.fori_loop(
        0, n // block_n, merge, init)
    valid = best_cyc != _BIG
    win = jnp.where(valid, (best_rot - shift) % n, n).astype(jnp.int32)

    # ---- stage 2: protocol dense bank update over this tile
    acq_b = valid & (phase_w == P_ACQ)
    rel_b = valid & (phase_w == P_REL)
    fx = FusedCtx(p=_param_ns(p, lat), n=n, a=block_a, q_cap=q_cap,
                  win=win, acq_b=acq_b, rel_b=rel_b,
                  core=dict(zip(core_names, core_w)))
    # bool arrays cross the kernel boundary as int32 (Mosaic has no
    # bool memory layout); the protocol sees its own dtypes
    bank = {k: bank_refs[k][0] if k in row_banks else bank_refs[k][...]
            for k in bank_names}
    bank2, fo = proto.fused_access(
        fx, {k: (v != 0) if k in bool_banks else v for k, v in bank.items()})

    def put(ref, v, row=True):
        v = v.astype(jnp.int32)
        ref[...] = v[None, :] if row else v

    put(valid_ref, valid)
    put(win_ref, win)
    put(kind_ref, fo.kind)
    put(tmr_ref, fo.tmr)
    for k in bank_names:
        put(bank_out[k], bank2[k], k in row_banks)
    for f in xset_names:
        val, msk = fo.xset[f]
        put(xv_refs[f], val)
        put(xm_refs[f], msk)

    # ---- stage 3: completion-latency histogram row for this tile
    done_cyc = cyc + jnp.maximum(fo.tmr, 1)
    fut = (fo.kind == OUT_DONE) & (done_cyc < cycles)
    lat_b = done_cyc - acq_w
    lbkt = jnp.clip((LAT_SUB * jnp.log2(
        lat_b.astype(jnp.float32) + 1.0)).astype(jnp.int32),
        0, LAT_BINS - 1)
    lbins = jax.lax.broadcasted_iota(jnp.int32, (LAT_BINS, block_a), 0)
    hist_ref[...] = jnp.sum((lbkt[None, :] == lbins) & fut[None, :],
                            axis=1).astype(jnp.int32)[None, :]
    polls = (fo.kind == OUT_FAIL).sum()
    msgs = (fo.msgs.sum() if fo.msgs is not None
            else jnp.zeros((), jnp.int32))
    lat_max = jnp.max(jnp.where(fut, lat_b, 0))
    stats_ref[...] = jnp.stack([polls, msgs, lat_max]).astype(
        jnp.int32)[None, :]


def fused_step_call(proto, p, bank, *, cand_cyc, rot, addr, phase,
                    acq_start, core, cyc, shift, lat, n, a, q_cap, cycles,
                    interpret, block_a=None, block_n=None):
    """Launch the tiled kernel; same contract as ``ref.fused_step_ref``."""
    block_a = a if block_a is None else block_a
    block_n = n if block_n is None else block_n
    if a % block_a or n % block_n:
        raise ValueError(
            f"tile sizes must divide the extents: a={a} block_a={block_a}, "
            f"n={n} block_n={block_n}")
    ga = a // block_a
    core_names = tuple(proto.fused_core_fields)
    bank_names = tuple(sorted(bank))
    xset_names = tuple(proto.fused_xset_fields)
    # Mosaic constraints on what crosses the kernel boundary: bool arrays
    # travel as int32 (there is no bool memory layout), and every 1-D
    # array as a (1, w) row, so that a block's last two dims are either
    # the array's own or (8, 128)-aligned, also once vmap (the sweep
    # runner) prepends a batch axis, and so that a bank tile of a row
    # matches XLA's layout of the whole row
    bool_banks = frozenset(k for k in bank_names
                           if bank[k].dtype == jnp.bool_)
    row_banks = frozenset(k for k in bank_names if bank[k].ndim == 1)
    bank_in = [bank[k].astype(jnp.int32) if k in bool_banks else bank[k]
               for k in bank_names]
    bank_in = [v.reshape(1, -1) if v.ndim == 1 else v for v in bank_in]

    def _const(w):                           # same full (1, w) row everywhere
        return pl.BlockSpec((1, w), lambda at: (0, 0))

    def _banked(shape):                      # bank dim is m*a -> m*block_a
        if proto.bank_last or shape[0] == 1:  # (r, a), or a (1, m*a) row
            return pl.BlockSpec((shape[0], shape[1] // a * block_a),
                                lambda at: (0, at))
        rest = tuple(shape[1:])
        return pl.BlockSpec((shape[0] // a * block_a,) + rest,
                            lambda at: (at,) + (0,) * len(rest))

    scal = jnp.stack([jnp.asarray(cyc, jnp.int32),
                      jnp.asarray(shift, jnp.int32),
                      jnp.asarray(lat, jnp.int32)])[None, :]
    in_specs = ([_const(3)] + [_const(n)] * (5 + len(core_names))
                + [_banked(v.shape) for v in bank_in])
    lane = _banked((1, a))
    # per-tile partials: a (ga, 1, w) array whose squeezed block is the
    # array's own last two dims
    row = lambda w: pl.BlockSpec((None, 1, w),  # noqa: E731
                                 lambda at: (at, 0, 0))
    lanes = jax.ShapeDtypeStruct((1, a), jnp.int32)
    out_specs = ([lane] * 4
                 + [_banked(v.shape) for v in bank_in]
                 + [lane] * (2 * len(xset_names))
                 + [row(_N_STATS), row(LAT_BINS)])
    out_shape = ([lanes] * 4
                 + [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in bank_in]
                 + [lanes] * (2 * len(xset_names))
                 + [jax.ShapeDtypeStruct((ga, 1, _N_STATS), jnp.int32),
                    jax.ShapeDtypeStruct((ga, 1, LAT_BINS), jnp.int32)])
    outs = pl.pallas_call(
        functools.partial(_kernel, proto=proto, p=p, n=n, block_a=block_a,
                          block_n=block_n, q_cap=q_cap, cycles=cycles,
                          core_names=core_names, bank_names=bank_names,
                          xset_names=xset_names, bool_banks=bool_banks,
                          row_banks=row_banks),
        grid=(ga,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="engine_step",
    )(scal, *[x.reshape(1, n) for x in
              (cand_cyc, rot, addr, phase, acq_start,
               *[core[f] for f in core_names])],
      *bank_in)

    valid, win, kind, tmr = (o[0] for o in outs[:4])
    nb, nx = len(bank_names), len(xset_names)
    bank_new = {k: (v != 0 if k in bool_banks else v).reshape(bank[k].shape)
                for k, v in zip(bank_names, outs[4:4 + nb])}
    xv = [v[0] for v in outs[4 + nb:4 + nb + nx]]
    xm = [m[0] != 0 for m in outs[4 + nb + nx:4 + nb + 2 * nx]]
    stats, hist = outs[-2][:, 0], outs[-1][:, 0]
    return dict(valid=valid != 0, win=win, kind=kind, tmr=tmr, bank=bank_new,
                xset={f: (v, m) for f, v, m in zip(xset_names, xv, xm)},
                polls=stats[:, 0].sum(), msgs=stats[:, 1].sum(),
                hist=hist.sum(axis=0), lat_max=stats[:, 2].max())
