"""Public op: one fused bank-side engine step.

``fused_step`` is what ``core.sim`` calls per scan iteration on the
pallas backends; ``use_kernel=False`` routes to the pure-jnp oracle
(``ref.fused_step_ref``) — the **unfused** ablation baseline, identical
dataflow as separate XLA ops.  Both forms return the same dict, so the
engine's outcome-apply code is backend-agnostic.

Not jitted here: the call sits inside ``simulate``'s ``lax.scan`` body
and is traced (and on the interpret path, inlined as XLA ops) as part of
the engine's own jit.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.core.protocols.base import (OUT_DONE, OUT_FAIL, OUT_GRANT,
                                       OUT_SLEEP)
from repro.kernels.engine_step.kernel import fused_step_call
from repro.kernels.engine_step.ref import fused_step_ref

#: default tile sizes: tile only when the extent cleanly splits — typical
#: bank counts (a <= 256) stay single-tile, 4096-core runs sweep the core
#: dimension in 1024-lane chunks (EXPERIMENTS.md §Pallas-backend ablates)
PREF_BLOCK_A = 256
PREF_BLOCK_N = 1024


def outcome_counts(kind: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Per-cycle tallies of the fused step's ``OUT_*`` outcome codes.

    ``kind`` is the ``(a,)`` per-bank outcome array :func:`fused_step`
    returns; the four scalars feed the engine's windowed telemetry
    (``repro.obs``).  By the documented OUT_*->(st, nxt) apply mapping
    (``core.protocols.base``) these equal the scan path's gathered
    (st, nxt) tallies exactly, so telemetry stays backend-identical.
    """
    return dict(grants=(kind == OUT_GRANT).sum(),
                retires=(kind == OUT_DONE).sum(),
                fails=(kind == OUT_FAIL).sum(),
                enqueues=(kind == OUT_SLEEP).sum())


def pick_block(extent: int, pref: int) -> int:
    """Largest clean tile: ``pref`` when it divides ``extent``, else the
    whole extent (degenerate single tile — never a remainder tile)."""
    return pref if extent > pref and extent % pref == 0 else extent


def fused_step(proto, p, bank: Dict, *, cand_cyc, rot, addr, phase,
               acq_start, core: Dict, cyc, shift, lat,
               n: int, a: int, q_cap: int, cycles: int,
               interpret: bool, block_a=None, block_n=None,
               use_kernel: bool = True) -> Dict:
    """Arbitrate + protocol-update + histogram for one cycle's parked
    requests.  See ``ref.fused_step_ref`` for the argument contract.
    ``interpret`` has no default: the caller says whether the kernel runs
    in the Pallas interpreter (CPU) or compiles for the device."""
    if not use_kernel:
        return fused_step_ref(
            proto, p, bank, cand_cyc=cand_cyc, rot=rot, addr=addr,
            phase=phase, acq_start=acq_start, core=core, cyc=cyc,
            shift=shift, lat=lat, n=n, a=a, q_cap=q_cap, cycles=cycles)
    return fused_step_call(
        proto, p, bank, cand_cyc=cand_cyc, rot=rot, addr=addr, phase=phase,
        acq_start=acq_start, core=core, cyc=cyc, shift=shift, lat=lat,
        n=n, a=a, q_cap=q_cap, cycles=cycles,
        block_a=pick_block(a, PREF_BLOCK_A) if block_a is None else block_a,
        block_n=pick_block(n, PREF_BLOCK_N) if block_n is None else block_n,
        interpret=interpret)
