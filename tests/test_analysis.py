"""Static-analysis subsystem (``repro.analysis``): the model checker,
the trace-safety auditor and the integer-range analyzer.

Three layers of evidence that the gate means something:

* **the matrix** — every registered protocol passes every pass on the
  quick small-scope subset (the exact CI smoke invocation);
* **known-bad protocols** — toy plugins seeded with the classic bugs
  (a dropped wakeup, a poller wearing a retry-free contract, a watchdog
  that evicts live owners) each trip exactly the rule built to catch
  them;
* **mutation checks** — the two bugs this repo actually shipped and
  fixed (the PR 6 ``wake_grp`` cross-bank aliasing, the PR 8 class of
  stale-owner eviction) are re-seeded as protocol mutants and must be
  flagged, so the checker provably covers its origin stories.
"""
import dataclasses
import json

import jax.numpy as jnp
import pytest

from repro.analysis import int_range, model_check, trace_safety
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.model_check import Config, check_protocol
from repro.analysis.report import (Finding, PassReport, all_findings,
                                   fail_fast, summarize)
from repro.analysis.trace_safety import (audit_protocol, audit_static_fields,
                                         expected_scan_carries,
                                         reference_params, scan_carry_count,
                                         scatter_count)
from repro.core import sim
from repro.core.protocols.base import OUT_EVICT, OUT_NONE, Contract
from repro.core.protocols.colibri_hier import ColibriHier
from repro.core.protocols.lrscwait import LrscWait
from repro.core.protocols.registry import names as proto_names

TINY = [Config(n=2, a=1, ops=1)]


def _rules(rep):
    return {f.rule for f in rep.findings}


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------

def test_finding_and_report_plumbing():
    f = Finding("model", "lost-wakeup", "toy", "a sleeper starved",
                where="n=2 a=1")
    assert "model:lost-wakeup" in f.render() and "[n=2 a=1]" in f.render()
    good = PassReport(pass_name="range", subject="backoff")
    bad = PassReport(pass_name="model", subject="toy", findings=[f])
    assert good.ok and not bad.ok
    assert bad.to_dict()["findings"][0]["rule"] == "lost-wakeup"
    json.dumps([good.to_dict(), bad.to_dict()])
    assert all_findings([good, bad]) == [f]
    s = summarize([good, bad])
    assert "ok" in s and "1 finding(s)" in s
    assert "lost-wakeup" in fail_fast([bad], limit=5)
    assert "more" in fail_fast([bad, bad, bad], limit=2)


# ---------------------------------------------------------------------------
# the matrix: every protocol x every pass, quick scope (the CI smoke)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("protocol", proto_names())
def test_model_check_passes_every_protocol(protocol):
    rep = check_protocol(protocol, quick=True)
    assert rep.ok, fail_fast([rep])
    assert rep.stats["states"] > 0 and rep.stats["transitions"] > 0


@pytest.mark.parametrize("protocol", proto_names())
def test_trace_audit_passes_every_protocol(protocol):
    rep = audit_protocol(protocol, quick=True)
    assert rep.ok, fail_fast([rep])
    assert rep.stats["hot_scatters"] <= rep.stats["scatter_budget"]


def test_static_fields_audit_passes():
    assert audit_static_fields().ok


# ---------------------------------------------------------------------------
# known-bad toy protocols: each seeded bug trips its intended rule
# ---------------------------------------------------------------------------

class _ToyLostWakeup(LrscWait):
    """Releases never arm the wake timer — the queued sleeper starves."""
    name = "toy_lost_wakeup"

    def wake_delay(self, p):
        return 0


class _ToyPoller(LrscWait):
    """One queue slot (held by the grantee) turns every contending
    acquire into an immediate FAIL — polling, while the contract still
    claims the paper's retry-free wait-class behaviour."""
    name = "toy_poller"
    contract = Contract(exclusive_grant=True, wait_class=True,
                        retry_free=True, queue_counts_holder=True,
                        max_hot_scatters=4)

    def q_cap(self, p, n):
        return 1


class _ToyLiveEvictor(LrscWait):
    """Watchdog that evicts the queue head without checking it is dead
    — the PR 8 stale-owner bug class, re-seeded: a slow-but-live owner
    loses the reservation and the bank double-grants."""
    name = "toy_live_evictor"

    def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
        q_cap = ctx.q_cap
        qhead, qlen = bank["qhead"], bank["qlen"]
        evict_b = stuck_b & (qlen > 0)        # BUG: ignores ``killed``
        qhead = jnp.where(evict_b, (qhead + 1) % q_cap, qhead)
        qlen = qlen - evict_b
        wake_b = evict_b & (qlen > 0)
        bank["wake_tmr"] = jnp.where(wake_b, self.wake_delay(ctx.p),
                                     bank["wake_tmr"])
        bank.update(qhead=qhead, qlen=qlen)
        kind = jnp.where(evict_b, OUT_EVICT, OUT_NONE).astype(jnp.int32)
        return cs, bank, kind


def test_toy_lost_wakeup_is_caught():
    rep = check_protocol(_ToyLostWakeup(), kill=False, configs=TINY)
    assert "lost-wakeup" in _rules(rep), fail_fast([rep]) or "no findings"


def test_toy_poller_is_caught():
    rep = check_protocol(_ToyPoller(), kill=False, configs=TINY)
    assert "retry-free" in _rules(rep), fail_fast([rep]) or "no findings"


def test_toy_live_evictor_is_caught():
    rep = check_protocol(_ToyLiveEvictor(), kill=False, configs=TINY)
    assert "live-evict" in _rules(rep), fail_fast([rep]) or "no findings"


def test_fail_requires_full_rule():
    """A FAIL with queue slots free is flagged even when the contract
    honestly gives up ``retry_free`` — the q=1 poller under lrscwait's
    own contract violates ``fail_requires_full`` instead (the queue has
    a free slot only the holder occupies... q_cap=1 with the holder
    counted IS full, so use q_cap=2: rejecting the second waiter with
    one slot free must be flagged)."""

    class _EarlyRejector(LrscWait):
        name = "toy_early_rejector"

        def q_cap(self, p, n):
            return 2

        def on_access(self, ctx, cs, bank):
            # shrink the admission test only: pretend full at qlen >= 1
            # by lying to the parent about capacity, then restore it for
            # the queue-slot arithmetic via the real q_cap in ctx
            full_ctx = dataclasses.replace(ctx, q_cap=1)
            return super().on_access(full_ctx, cs, bank)

        def fused_access(self, fx, bank):
            return super().fused_access(dataclasses.replace(fx, q_cap=1),
                                        bank)

    rep = check_protocol(_EarlyRejector(), kill=False,
                         configs=[Config(n=3, a=1, ops=1)])
    assert "fail-not-full" in _rules(rep), fail_fast([rep]) or "no findings"


# ---------------------------------------------------------------------------
# mutation checks: the repo's own shipped-and-fixed bugs, re-seeded
# ---------------------------------------------------------------------------

class _WakeGrpAliasing(ColibriHier):
    """The old ``wake_grp`` aliasing bug: ``on_wake`` consumes it as a flat
    local-queue id ``bank * G + group`` without rebasing it by the bank,
    so a wake on any bank other than bank 0 pops (and wakes from)
    ANOTHER bank's local queue.  On the ``(G, a)`` layout flat id ``f``
    is queue (bank ``f // G``, group ``f % G``): column ``f // G``, row
    ``f % G``."""
    name = "mutant_wake_grp_alias"

    def on_wake(self, ctx, cs, bank):
        from repro.core.protocols.base import MOD
        G, _, cap_l = self._geom(ctx.p, ctx.n)
        wake_tmr = bank["wake_tmr"]
        wq = bank["wake_grp"]                # BUG: missing ba * G rebase
        g, b = wq % G, wq // G               # the queue flat id wq names
        lqbuf, lqhead, lqlen = bank["lqbuf"], bank["lqhead"], bank["lqlen"]
        fire = wake_tmr == 1
        wake_tmr = jnp.maximum(wake_tmr - 1, 0)
        head_core = lqbuf[g * cap_l + lqhead[g, b], b]
        valid = fire & (lqlen[g, b] > 0)
        fire_core = jnp.where(valid, head_core, ctx.n)
        woken = jnp.zeros((ctx.n,), bool).at[fire_core].set(True,
                                                            mode="drop")
        cs["st"] = jnp.where(woken, MOD, cs["st"])
        cs["tmr"] = jnp.where(woken, ctx.mod_dur, cs["tmr"])
        oob = jnp.where(valid, g, G)
        lqhead = (lqhead.at[oob, b].add(1, mode="drop")) % cap_l
        lqlen = lqlen.at[oob, b].add(-1, mode="drop")
        bank.update(wake_tmr=wake_tmr, lqhead=lqhead, lqlen=lqlen)
        return cs, bank, (wake_tmr == 1).sum()


def test_pr6_wake_grp_aliasing_mutant_is_caught():
    """Cross-bank aliasing needs >= 2 banks to exist at all (the PR 6
    lesson: every single-bank test was green) — on the 2-bank 2-group
    config the checker must refute the mutant."""
    rep = check_protocol(_WakeGrpAliasing(), kill=False,
                         configs=[Config(n=4, a=2, ops=1, n_groups=2)])
    assert not rep.ok
    assert _rules(rep) <= {"queue-conservation", "lost-wakeup",
                           "wake-corrupt", "double-grant", "deadlock",
                           "completion-unreachable"}, fail_fast([rep])


def test_pr6_single_bank_config_misses_the_mutant():
    """On one bank the flat id and the group id coincide — the mutant
    is invisible.  This is WHY configs_for pins a multi-bank config for
    colibri_hier; the test locks that in."""
    rep = check_protocol(_WakeGrpAliasing(), kill=False,
                         configs=[Config(n=3, a=1, ops=2, n_groups=2)])
    assert rep.ok
    cfgs = model_check.configs_for("colibri_hier")
    assert any(c.a >= 2 for c in cfgs)


def test_pr8_stale_owner_recovery_is_exercised():
    """The fault pass must actually reach watchdog evictions for the
    wait-class protocols (a dead holder wedges the bank until the FIFO
    recovery hands the reservation on) — otherwise the recovery rules
    are vacuous."""
    rep = check_protocol("lrscwait", quick=False, kill=True,
                         configs=[Config(n=3, a=1, ops=1)])
    assert rep.ok, fail_fast([rep])
    # and with recovery sabotaged (never evict), the same scope must
    # deadlock: proof the kill pass depends on on_timeout being right
    class _NoRecovery(LrscWait):
        name = "mutant_no_recovery"

        def on_timeout(self, ctx, cs, bank, stuck_b, killed, owner):
            kind = jnp.zeros((ctx.a,), jnp.int32)    # OUT_NONE everywhere
            return cs, bank, kind

    bad = check_protocol(_NoRecovery(), kill=True,
                         configs=[Config(n=3, a=1, ops=1)])
    assert "recovery-deadlock" in _rules(bad), fail_fast([bad]) \
        or "no findings"


# ---------------------------------------------------------------------------
# trace-safety auditor: budgets are real, regressions are named
# ---------------------------------------------------------------------------

def test_scan_carry_count_matches_budget():
    for name in ("colibri", "lrscwait", "amo"):
        p = reference_params(name)
        assert scan_carry_count(p) == expected_scan_carries(p)


def test_feature_deltas_are_exact():
    base = reference_params("colibri")
    tele = reference_params("colibri", telemetry_windows=8)
    assert expected_scan_carries(tele) == expected_scan_carries(base) + 1
    assert scan_carry_count(tele) == scan_carry_count(base) + 1


def test_scatter_budget_regression_is_flagged(monkeypatch):
    """Tightening a protocol's declared budget below its real scatter
    count must fail the audit — i.e. a regression REINTRODUCING hot
    scatters is a finding, not a benchmark mystery."""
    from repro.core.protocols.registry import get
    proto = get("lrscwait")
    assert scatter_count(reference_params("lrscwait")) > 0
    monkeypatch.setattr(
        proto, "contract",
        dataclasses.replace(proto.contract, max_hot_scatters=0))
    rep = audit_protocol("lrscwait", quick=True)
    assert "scatter-budget" in _rules(rep)


def test_carry_contract_drift_is_flagged(monkeypatch):
    """Dropping a key from the frozen engine-carry contract desyncs the
    budget from the real scan — the audit must notice."""
    monkeypatch.setattr(trace_safety, "ENGINE_CARRY_KEYS",
                        trace_safety.ENGINE_CARRY_KEYS[:-1])
    rep = audit_protocol("amo", quick=True)
    assert "carry-count" in _rules(rep)


def test_static_knob_drift_is_flagged(monkeypatch):
    monkeypatch.setattr(trace_safety, "CARRY_AFFECTING_FIELDS",
                        trace_safety.CARRY_AFFECTING_FIELDS
                        + ("not_a_static_field",))
    rep = audit_static_fields()
    assert "static-knob" in _rules(rep)


# ---------------------------------------------------------------------------
# integer-range analyzer: the PR 3 wrap as a theorem
# ---------------------------------------------------------------------------

def test_fused_key_threshold_n1024():
    """The PR 3 bug, quantified: at n=1024 the fused arbitration path
    is safe through exactly 2_095_104 cycles."""
    t = int_range.max_safe_cycles(1024)
    assert t == 2_095_104
    # the engine's guard sits exactly on the proved threshold
    assert sim.fused_key_fits_int32(t, 1024)
    assert not sim.fused_key_fits_int32(t + 1, 1024)
    # every admitted key interval fits; the guard keeps ONE cycle of
    # headroom below the int32 no-winner sentinel, so the raw interval
    # wraps one cycle later than the guard flips
    assert int_range.fused_key_interval(1024, t).fits_int32()
    assert int_range.fused_key_interval(1024, t + 1).fits_int32()
    assert not int_range.fused_key_interval(1024, t + 2).fits_int32()


def test_interval_arithmetic():
    iv = int_range.Interval
    assert (iv(1, 3) + iv(10, 20)) == iv(11, 23)
    assert (iv(-2, 3) * iv(5, 7)) == iv(-14, 21)
    assert iv(1, 4).shl(iv(0, 3)) == iv(1, 32)
    assert iv(0, 2**31 - 1).fits_int32()
    assert not iv(0, 2**31).fits_int32()
    with pytest.raises(ValueError):
        iv(5, 4)
    with pytest.raises(ValueError):
        iv(-1, 1).shl(iv(0, 1))


def test_range_pass_is_green():
    reps = int_range.check_all()
    assert all(r.ok for r in reps), fail_fast(reps)
    fused = next(r for r in reps if r.subject == "fused-arbitration-key")
    assert fused.stats["n1024_threshold"] == 2_095_104


def test_envelope_drift_is_flagged(monkeypatch):
    monkeypatch.setitem(int_range.ANALYSIS_BOUNDS, "bogus_field", (0, 1))
    rep = int_range.check_envelope()
    assert "envelope" in _rules(rep)
    assert any("bogus_field" in f.detail for f in rep.findings)


def test_backoff_bounded_in_envelope():
    iv = int_range.backoff_interval(2**20, 8)
    assert iv.fits_int32() and iv.lo == 0


# ---------------------------------------------------------------------------
# CLI: the CI gate's entry point
# ---------------------------------------------------------------------------

def test_cli_green_run_with_json(tmp_path, capsys):
    out = tmp_path / "report.json"
    assert analysis_main(["range", "--json", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["ok"] and doc["passes"] == ["range"]
    assert {r["pass"] for r in doc["reports"]} == {"range"}
    assert "OK:" in capsys.readouterr().out


def test_cli_exits_nonzero_on_findings(monkeypatch, capsys):
    bad = PassReport(pass_name="range", subject="seeded", findings=[
        Finding("range", "key-overflow", "seeded", "seeded failure")])
    monkeypatch.setattr(int_range, "check_all",
                        lambda quick=False: [bad])
    assert analysis_main(["range"]) == 1
    assert "key-overflow" in capsys.readouterr().out


def test_run_passes_rejects_unknown_pass():
    from repro.analysis import run_passes
    with pytest.raises(ValueError, match="unknown pass"):
        run_passes(["modle"])
