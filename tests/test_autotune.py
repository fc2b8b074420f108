"""Online autotuner battery (ROADMAP item 3, controller half).

Four pillars:

* scripted-snapshot ``decide()`` tests — every controller branch
  (hold under noise below hysteresis, retune on drift, blocked on a
  reconcile error above the gate, cooldown / budget bounded
  frequency) driven from REAL window snapshots mutated in place, no
  timing dependence;
* the plan-swap seam pin — two measured iterations, a mid-training
  ``apply_plan_config`` wave 2 -> 4 swap, two more iterations must be
  BITWISE identical to an engine compiled with the second plan from
  the same checkpointed state (the swap leaks no per-plan state);
* seam atomicity — an invalid knob raises ``ValueError`` and leaves
  the engine running its current plan;
* trajectory neutrality — autotune ON (live depth retunes) vs OFF
  across the schedule x M x alpha x R grid: bitwise-identical f32
  params and losses, because the default candidate axes are the
  proven bitwise-invariant knobs.
"""
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import MachineParams, StorageRatios
from repro.data import SyntheticLM
from repro.offload import (AutotuneConfig, AutotuneController,
                           OffloadConfig, make_engine, route_seconds_error)

CFG = ArchConfig(name="autotune-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16
X0 = StorageRatios(0.0, 0.0, 0.0)

#: the acceptance grid: schedule x M x alpha x R (wave needs M % 2 == 0,
#: DP plans are vertical with M % R == 0) — the test_obs grid shape
GRID = [(sched, M, alpha, R)
        for sched in ("vertical", "horizontal", "wave")
        for M in (2, 4)
        for alpha in (0.0, 0.5)
        for R in (1, 2)
        if not (sched == "wave" and M % 2)
        and not (R > 1 and (sched != "vertical" or M % R))]


def _build(sched, M, alpha, R, workdir, depth=1, wave=None):
    W = {"vertical": 0, "horizontal": 0, "wave": 2}[sched] \
        if wave is None else wave
    ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         wave_size=W, ratios=X0, prefetch_depth=depth)
    return make_engine(CFG, ocfg, jax.random.PRNGKey(11), workdir,
                       num_ranks=R)


def _window(eng, ctl, steps=2, seed=0):
    """Run ``steps`` measured iterations and return the window
    snapshot WITHOUT committing a decision (scripted tests drive
    ``ctl.decide`` by hand)."""
    data = SyntheticLM(CFG.vocab_size, seed=seed)
    M = eng.ocfg.num_microbatches
    for _ in range(steps):
        eng.train_step(data.batch(M * MB, S))
    return eng.metrics_snapshot()


#: A machine where the lookahead LP rows genuinely bind for the tiny
#: test model: compute slow enough to be the stage bound, DRAM too
#: small to cache the optimizer tail, SSD slow enough that the
#: serialized (depth-0) reads cost real fractions of a stage — so
#: depth > 0 wins by several percent and the controller has a true
#: signal to act on. The default A100-node machine caches this whole
#: model in DRAM and every depth ties.
DRIFT_MACHINE = MachineParams(name="drift", gpu_flops=1e7,
                              ssd_read_bw=1e6, ssd_write_bw=1e6,
                              cpu_mem=2e5)
DRIFT_RATE = 1e6


def _script_drift(snap, rate=DRIFT_RATE):
    """Rewrite the window's measured route rates to a slow device,
    keeping bytes and wall seconds self-consistent so the reconcile
    gate stays green: the scripted-drift scenario (the live device got
    slower than the configured machine)."""
    for d in snap["trace"]["routes"].values():
        if d.get("bytes"):
            d["busy_wall_s"] = d["bytes"] / rate
            d["rate_bps"] = rate
    return snap


def _drift_snapshots(eng):
    """Make every window the controller measures look like the drifted
    device (the scripted-snapshot hook for full-loop tests)."""
    real = eng.metrics_snapshot
    eng.metrics_snapshot = lambda: _script_drift(real())


# ---------------------------------------------------------------------------
# the scalar gate
# ---------------------------------------------------------------------------

def test_route_seconds_error_scalar():
    assert route_seconds_error({}, {}) == 0.0
    assert route_seconds_error({"ssd->cpu": 1.0}, {}) == 0.0
    assert route_seconds_error({"ssd->cpu": 1.0},
                               {"ssd->cpu": 1.0}) == 0.0
    assert route_seconds_error({"ssd->cpu": 1.0},
                               {"ssd->cpu": 2.0}) == pytest.approx(0.5)
    # worst route wins
    assert route_seconds_error(
        {"ssd->cpu": 1.0, "cpu->ssd": 1.0},
        {"ssd->cpu": 1.1, "cpu->ssd": 4.0}) == pytest.approx(0.75)
    # both sides under the floor: micro-transfer noise is ignored
    assert route_seconds_error({"ssd->cpu": 1e-5}, {"ssd->cpu": 1e-4},
                               floor_s=1e-3) == 0.0


def test_autotune_config_validates():
    with pytest.raises(ValueError, match="interval"):
        AutotuneConfig(interval=0)
    with pytest.raises(ValueError, match="hysteresis"):
        AutotuneConfig(hysteresis=-0.1)


# ---------------------------------------------------------------------------
# scripted-snapshot decide(): every branch, no timing dependence
# ---------------------------------------------------------------------------

def test_decide_holds_when_current_is_best():
    """Default axes = current knobs only: the controller can only ever
    hold, and the decision is pure w.r.t. engine state."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.0, 1, d, depth=1)
        ctl = AutotuneController(eng, AutotuneConfig(interval=2))
        snap = _window(eng, ctl)
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "hold"
        assert dec["best"] == dec["current"]
        assert eng.ocfg.resolved_prefetch_depth() == 1   # untouched
        eng.close()


def test_decide_holds_under_noise_below_hysteresis():
    """A real predicted win that does not clear the hysteresis band is
    a hold — meter noise must not thrash the plan."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, depth=0)
        ctl = AutotuneController(
            eng, AutotuneConfig(interval=2, prefetch_depths=(0, 1),
                                hysteresis=1e9, machine=DRIFT_MACHINE))
        snap = _script_drift(_window(eng, ctl))
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "hold"
        assert "hysteresis" in dec["reason"]
        # the win was real (depth 1 strictly beats the lookahead-off
        # LP row) — just not big enough for the configured band
        assert dec["predicted_win"] is not None
        assert dec["predicted_win"] > 1.0
        eng.close()


def test_decide_retunes_on_drift():
    """With the band at zero the same predicted win becomes a retune —
    and ``decide`` stays pure: only ``post_step`` commits the swap."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, depth=0)
        ctl = AutotuneController(
            eng, AutotuneConfig(interval=2, prefetch_depths=(0, 1),
                                hysteresis=0.0, machine=DRIFT_MACHINE))
        snap = _script_drift(_window(eng, ctl))
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "retune"
        assert dec["changes"] == {"prefetch_depth": 1}
        assert dec["best"]["pred_s"] < dec["current"]["pred_s"]
        assert eng.ocfg.resolved_prefetch_depth() == 0   # decide is pure
        # candidates always lead with the current knobs
        assert dec["candidates"][0]["depth"] == 0
        eng.close()


def test_decide_blocked_on_reconcile_error():
    """A model that cannot explain the current plan's route seconds is
    not allowed to pick the next plan."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, depth=0)
        ctl = AutotuneController(
            eng, AutotuneConfig(interval=2, prefetch_depths=(0, 1),
                                hysteresis=0.0, error_gate=0.5,
                                machine=DRIFT_MACHINE))
        snap = _script_drift(_window(eng, ctl))
        # script a measured wall-clock envelope the model cannot
        # explain: 1000 s on a route the plan predicts in micro-seconds
        snap["trace"]["routes"]["cpu->ssd"]["busy_wall_s"] = 1000.0
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "blocked"
        assert dec["route_error"] > 0.5
        assert "candidates" not in dec          # never got to scoring
        eng.close()


def test_decide_bounded_frequency_cooldown_and_budget():
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, depth=0)
        ctl = AutotuneController(
            eng, AutotuneConfig(interval=2, prefetch_depths=(0, 1),
                                hysteresis=0.0, cooldown=2,
                                max_retunes=0))
        snap = _window(eng, ctl)
        # a pending cooldown short-circuits everything
        ctl._cooldown = 2
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "cooldown"
        # budget spent: measured forever, swapped never
        ctl._cooldown = 0
        dec = ctl.decide(snap, steps=2)
        assert dec["action"] == "hold"
        assert "budget" in dec["reason"]
        eng.close()


def test_post_step_loop_swaps_once_then_cools_down():
    """The committed loop end-to-end: one retune fires, the cooldown
    window follows, the swap actually landed on the engine, and the
    decision log rides in the next metrics snapshot."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, depth=0)
        _drift_snapshots(eng)
        ctl = AutotuneController(
            eng, AutotuneConfig(interval=1, prefetch_depths=(0, 1),
                                hysteresis=0.0, cooldown=1,
                                max_retunes=1, machine=DRIFT_MACHINE))
        data = SyntheticLM(CFG.vocab_size, seed=0)
        decisions = []
        for _ in range(4):
            eng.train_step(data.batch(2 * MB, S))
            dec = ctl.post_step()
            assert dec is not None              # interval=1
            decisions.append(dec)
        actions = [dc["action"] for dc in decisions]
        assert actions[0] == "retune"
        assert actions[1] == "cooldown"
        assert set(actions[2:]) <= {"hold", "blocked"}
        assert ctl.retunes == 1
        assert eng.ocfg.resolved_prefetch_depth() == 1   # swap landed
        # per-path steering signal is advisory but always logged
        assert decisions[0]["paths"][0]["least_loaded_path"] >= 0
        assert decisions[0]["paths"][0]["imbalance"] >= 0.0
        eng.finish()
        snap = eng.metrics_snapshot()
        assert [dc["action"] for dc in snap["autotune"]] == actions
        eng.close()


# ---------------------------------------------------------------------------
# the plan-swap seam: bitwise pin + atomicity
# ---------------------------------------------------------------------------

def test_wave_swap_bitwise_equals_recompile_from_checkpoint():
    """2 iters -> apply_plan_config(wave 2 -> 4) -> 2 iters must equal,
    bitwise, an engine COMPILED with the second plan from the same
    checkpointed state: the swap leaks no per-plan state (alpha gates,
    pinned fetches, spill queues, stale plan closures). The checkpoint
    goes through the engine's durable ``save_checkpoint`` /
    ``restore_checkpoint`` (``repro.offload.checkpoint``) — the
    promotion of the ad-hoc state dict this test originally grew."""
    data = SyntheticLM(CFG.vocab_size, seed=0)
    batches = [data.batch(4 * MB, S) for _ in range(4)]
    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db, \
            tempfile.TemporaryDirectory() as dc, \
            tempfile.TemporaryDirectory() as ck:
        # the swapped engine
        a = _build("wave", 4, 0.5, 1, da, depth=1, wave=2)
        losses_a = [a.train_step(b) for b in batches[:2]]
        a.apply_plan_config(wave_size=4)
        assert not a.params_c._gate              # seam cleared the gates
        assert a.ocfg.resolved_wave_size() == 4
        losses_a += [a.train_step(b) for b in batches[2:]]
        a.finish()
        params_a = [a.p_vecs[l].read().copy() for l in range(a.L)]
        a.close()

        # the reference: same first half on a twin, checkpoint...
        b_eng = _build("wave", 4, 0.5, 1, db, depth=1, wave=2)
        losses_b = [b_eng.train_step(b) for b in batches[:2]]
        assert losses_b == losses_a[:2]          # determinism baseline
        b_eng.save_checkpoint(ck)    # finish() == the seam's quiesce
        b_eng.close()

        # ...restored into an engine BORN with the second plan
        c = _build("wave", 4, 0.5, 1, dc, depth=1, wave=4)
        assert c.restore_checkpoint(ck) == b_eng.step_num
        losses_c = [c.train_step(b) for b in batches[2:]]
        c.finish()
        params_c = [c.p_vecs[l].read().copy() for l in range(c.L)]
        c.close()

    assert losses_a[2:] == losses_c              # float-exact
    for pa, pc in zip(params_a, params_c):
        assert np.array_equal(pa, pc)            # bitwise


def test_apply_plan_config_invalid_knob_is_atomic():
    """Validate-then-commit: a bad knob raises and the engine keeps
    training on its current plan with its current config."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("wave", 4, 0.0, 1, d, depth=1, wave=2)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(4 * MB, S))
        with pytest.raises(ValueError):
            eng.apply_plan_config(wave_size=3)           # 3 does not divide 4
        with pytest.raises(ValueError):
            eng.apply_plan_config(activation_policy="levitate")
        assert eng.ocfg.resolved_wave_size() == 2        # untouched
        assert eng.act_policy == "recompute"
        loss = eng.train_step(data.batch(4 * MB, S))     # still alive
        assert np.isfinite(loss)
        eng.close()


# ---------------------------------------------------------------------------
# acceptance: retuning is trajectory-neutral (autotune on vs off)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched,M,alpha,R", GRID)
def test_autotune_on_vs_off_bitwise(sched, M, alpha, R):
    """Autotune ON (live depth retunes from measured windows) vs OFF:
    identical f32 losses and bitwise-identical params on every grid
    cell — a retune changes when bytes move, never what is learned."""
    steps = 3

    def run(autotune):
        with tempfile.TemporaryDirectory() as d:
            eng = _build(sched, M, alpha, R, d, depth=0)
            ctl = None
            if autotune:
                _drift_snapshots(eng)
                ctl = AutotuneController(
                    eng, AutotuneConfig(interval=1, hysteresis=0.0,
                                        cooldown=0, machine=DRIFT_MACHINE,
                                        prefetch_depths=(0, 1, 2)))
            data = SyntheticLM(CFG.vocab_size, seed=0)
            losses = []
            for _ in range(steps):
                losses.append(eng.train_step(data.batch(M * MB, S)))
                if ctl is not None:
                    ctl.post_step()
            eng.finish()
            if R > 1:
                params = [eng.read_params(l).copy() for l in range(eng.L)]
            else:
                params = [eng.p_vecs[l].read().copy() for l in range(eng.L)]
            retunes = ctl.retunes if ctl is not None else 0
            eng.close()
        return losses, params, retunes

    l_off, p_off, _ = run(autotune=False)
    l_on, p_on, retunes = run(autotune=True)
    assert l_off == l_on
    for a, b in zip(p_off, p_on):
        assert np.array_equal(a, b)              # bitwise
    # under the drifted machine the lookahead win is only guaranteed
    # on the cells where the serialized depth-0 reads carry an α tail
    # (the fwd stall term is α-scaled; DP halves every per-rank I/O
    # term, so the R=2 LP can tie and legitimately hold) — there the
    # swap MUST have run, so the bitwise check above really covers a
    # mid-training retune
    if sched == "vertical" and alpha > 0.0 and R == 1:
        assert retunes >= 1
