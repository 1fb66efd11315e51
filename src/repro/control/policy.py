"""Control policies: one fused decision step for the whole fleet.

The paper measures non-blocking service rates online so the run-time can
*re-tune the application while it runs*; the policies here turn the
gated (Q,) fleet estimates into actuation decisions.  Three policy
families ride one evaluation:

* **replicas** — how many copies of each consumer stage keep up with the
  offered load (``ceil(headroom * lambda / mu)``, Gordon et al. / Li et
  al., the same formula ``ParallelismController`` exposes);
* **capacity** — the smallest queue capacity reaching ``target_frac`` of
  saturation throughput (the analytic M/M/1/K / M/D/1/K inversion from
  ``core.queueing``, shared with ``BufferAutotuner``);
* **admission** — shed or defer offered load when a stream's service
  rate collapses (below ``collapse_frac`` of its decayed peak, or below
  the straggler threshold vs. the fleet median) while its queue runs
  hot.

Raw targets are deliberately *not* actions.  Re-tuning perturbs the
system (the paper resizes sparingly, §V), so the decision step wraps the
targets in a gating state machine — per-queue readiness, a confirmation
counter (a change must be wanted ``confirm_ticks`` consecutive ticks),
capacity hysteresis (the ``resize_factor`` band ``BufferAutotuner``
uses), and a post-actuation cooldown — and the whole thing (targets +
gates, every queue) is **one jitted dispatch per control tick**,
cached per (config, block_q) with queue-axis padding exactly like
``run_monitor_fleet`` so ragged fleets never retrace.

The same jnp target functions back the *advisory* readouts
(``Pipeline.recommended_replicas`` / ``Engine.recommended_queue_capacity``
delegate to the policy objects below), so advice and actuation cannot
disagree.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import on_host
from repro.core.controller import (BufferAutotuner, ParallelismController,
                                   StragglerDetector)

__all__ = [
    "ControlConfig", "ControlState", "Decision",
    "control_init", "control_decide", "control_decide_trace_count",
    "ReplicaPolicy", "BufferPolicy", "AdmissionPolicy", "SLOPolicy",
    "PolicySet",
]


@dataclasses.dataclass(frozen=True)
class ControlConfig:
    """Static decision knobs (hashable: part of the jit cache key).

    The replica / capacity knobs mirror ``ParallelismController`` and
    ``BufferAutotuner`` so a policy built from existing controllers
    decides exactly what the advisory APIs recommend.
    """
    # replicas (ParallelismController knobs)
    headroom: float = 1.2
    max_replicas: int = 64
    # capacity (BufferAutotuner knobs)
    target_frac: float = 0.99
    resize_factor: float = 1.5
    min_capacity: int = 4
    max_capacity: int = 1 << 20
    search_max_k: int = 1 << 16
    # admission (shed/defer state machine)
    collapse_frac: float = 0.5     # mu below this x decayed peak => collapsed
    recover_frac: float = 0.75     # mu above this x peak re-opens the gate
    occupancy_hi: float = 0.9      # queue fill fraction that arms shedding
    occupancy_lo: float = 0.5      # fill fraction that (with recovery) reopens
    straggler_frac: float = 0.8    # mu below this x fleet median => straggler
    min_ready: int = 4             # streams needed before the median is used
    peak_decay: float = 0.995      # per-tick decay of the tracked peak rate
    # saturation escalation: a persistently full queue blocks the
    # producer, so true demand is unobservable (the paper's Pr[WRITE]
    # collapses and arrival periods are discarded) — the only sound
    # move is multiplicative scale-up until demand becomes visible
    saturation_frac: float = 0.8   # tail blocked fraction => saturated
    saturation_growth: float = 2.0  # replica multiplier while saturated
    # demand probe (scale-down of the escalated/stale regime): an
    # arrival estimate whose stream went quiet never re-converges (the
    # epoch freezes at the old high level while fresh near-zero samples
    # fold into the window), so escalated replicas would ratchet.  A
    # queue whose provision is escalation-driven or whose demand signal
    # went stale probes: every ``probe_period_ticks`` the admission gate
    # is forced open and capacity/replicas held for
    # ``probe_window_ticks`` so real demand (if any) becomes observable
    # again; a window that stays dark end-to-end decays replicas by
    # ``saturation_growth`` (AIMD's multiplicative decrease).
    stale_frac: float = 0.5        # window mean below this x gated lam => stale
    probe_period_ticks: int = 16   # ticks between probe windows
    probe_window_ticks: int = 4    # gate-open ticks per probe window
    # SLO / error-budget leg (multi-window burn rate a la the SRE
    # runbooks): per-queue latency targets arrive as a queue-padded
    # operand (NaN = no SLO); the fraction of the last window's
    # observations over target, divided by the budget fraction, is the
    # instantaneous burn rate, folded into fast (~5-tick) and slow
    # (~60-tick) EMAs carried in ControlState.  Both windows hot =>
    # the replica leg escalates (latency pressure scales the stage even
    # when rates balance); a fast burn above ``slo_shed_burn`` arms the
    # admission gate (the budget is burning too fast to scale out of).
    slo_enabled: bool = False
    slo_budget_frac: float = 0.01  # error budget: frac of traffic allowed over
    slo_fast_ticks: int = 5        # fast burn EMA window (control ticks)
    slo_slow_ticks: int = 60       # slow burn EMA window (control ticks)
    slo_burn_hi: float = 1.0       # both EMAs above => SLO-hot (escalate)
    slo_burn_lo: float = 0.5       # fast EMA below => SLO-hot releases
    slo_shed_burn: float = 6.0     # fast EMA above => arm admission
    # gating
    confirm_ticks: int = 2         # consecutive agreeing ticks before acting
    cooldown_ticks: int = 4        # ticks a queue rests after an actuation
    block_q: int = 256             # queue-axis padding block (jit cache key)
    # which policy legs are live (PolicySet sets these): a disabled
    # leg's phantom decisions must not fire or burn cooldown — an
    # admission-only engine under overload would otherwise have its
    # resizes throttled by replica decisions nobody actuates
    replica_enabled: bool = True
    buffer_enabled: bool = True
    admission_enabled: bool = True


class ControlState(NamedTuple):
    """Per-queue gating state carried across control ticks (jax arrays,
    donated into each decision dispatch like ``FleetMonitorState``)."""
    cooldown: jnp.ndarray      # (Q,) i32  ticks until the queue may act again
    rep_agree: jnp.ndarray     # (Q,) i32  signed consecutive-want counter
    cap_agree: jnp.ndarray     # (Q,) i32  signed consecutive-want counter
    shedding: jnp.ndarray      # (Q,) bool admission gate currently shut
    peak_mu: jnp.ndarray       # (Q,) f32  decayed peak service rate seen
    escalated: jnp.ndarray     # (Q,) bool provision last set by escalation
    probe_timer: jnp.ndarray   # (Q,) i32  ticks into the probe cycle
    burn_fast: jnp.ndarray     # (Q,) f32  fast-window SLO burn-rate EMA
    burn_slow: jnp.ndarray     # (Q,) f32  slow-window SLO burn-rate EMA
    slo_hot: jnp.ndarray       # (Q,) bool SLO-escalation memory (hysteresis)


class Decision(NamedTuple):
    """One control tick's verdict for every queue (numpy on readout)."""
    target_replicas: jnp.ndarray   # (Q,) i32
    scale_mask: jnp.ndarray        # (Q,) bool  apply target_replicas now
    target_caps: jnp.ndarray       # (Q,) i32
    resize_mask: jnp.ndarray       # (Q,) bool  apply target_caps now
    shed: jnp.ndarray              # (Q,) bool  admission gate shut
    straggler: jnp.ndarray         # (Q,) bool  below fleet-median threshold
    probing: jnp.ndarray           # (Q,) bool  gate-open demand-probe window
    slo_hot: jnp.ndarray           # (Q,) bool  burn-rate escalation active


def control_init(cfg: ControlConfig, n: int) -> ControlState:
    return ControlState(
        cooldown=jnp.zeros((n,), jnp.int32),
        rep_agree=jnp.zeros((n,), jnp.int32),
        cap_agree=jnp.zeros((n,), jnp.int32),
        shedding=jnp.zeros((n,), bool),
        peak_mu=jnp.zeros((n,), jnp.float32),
        escalated=jnp.zeros((n,), bool),
        probe_timer=jnp.zeros((n,), jnp.int32),
        burn_fast=jnp.zeros((n,), jnp.float32),
        burn_slow=jnp.zeros((n,), jnp.float32),
        slo_hot=jnp.zeros((n,), bool),
    )


_TRACE_COUNT = [0]


def control_decide_trace_count() -> int:
    """(Re)trace count of the cached decision dispatch — the ragged-fleet
    no-retrace regression hook, mirroring ``fleet_dispatch_trace_count``."""
    return _TRACE_COUNT[0]


# -- shared target functions (advice == actuation) ---------------------------
#
# Every formula below is written against an ``xp`` array namespace and
# evaluated two ways from the SAME source: traced with xp=jnp into the
# cached jitted dispatch (the accelerator contract), or executed
# directly with xp=np (the host fast path — this box's ~150 us
# per-dispatch XLA floor dwarfs the ~40 us the whole fleet's decision
# costs in numpy).  Parity between the forms is regression-tested.

def _replica_targets(cfg: ControlConfig, lam, mu, replicas, xp=jnp,
                     headroom=None, max_reps=None):
    """``ParallelismController.replicas_fleet``, normalized by the live
    replica count: the monitored ``mu`` is the *aggregate* consumption
    rate of all current replicas, so one replica is worth
    ``mu / replicas`` and the stage needs ``ceil(headroom * lam /
    (mu / replicas))`` copies (identical to the scalar formula when
    replicas == 1).  ``max_replicas`` when the rate is unobservable.
    ``headroom``/``max_reps`` may be (Q,) arrays — the multi-tenant
    per-queue overrides — defaulting to the config scalars."""
    hr = cfg.headroom if headroom is None else headroom
    mr = cfg.max_replicas if max_reps is None else max_reps
    mu_per = mu / xp.maximum(replicas.astype(xp.float32), 1.0)
    n = xp.ceil(hr * lam / xp.where(mu_per > 0, mu_per, 1.0))
    n = xp.where(mu_per <= 0, mr, n)
    return xp.clip(n, 1, mr).astype(xp.int32)


def _capacity_targets(cfg: ControlConfig, lam, mu, cv2, current, xp=jnp):
    """``optimal_buffer_size``'s answer in closed form: the smallest K
    whose M/M/1/K (or, for cv2 < 0.5, M/D/1/K) accepted throughput
    reaches ``target_frac * min(lam, mu)``.

    The search in ``core.queueing`` brackets the monotone throughput
    curve with ~33 gallop+bisect evaluations — fine per resize event,
    but ~70 pow-heavy passes over (Q,) inside a per-tick decision (the
    dominant 11 ms at Q=4096).  The blocking condition inverts exactly
    instead: with f = target_frac, b = 1 - f*min(lam,mu)/lam and
    x = rho^K, ``P_K <= b`` is linear in x, giving x* = (1-f)/(1-f*rho)
    for rho < 1 and (1 - f/rho)/(1-f) for rho > 1, so

        K* = ceil(log(x*) / log(rho))        (rho -> 1: K* = f/(1-f))

    and the M/D/1/K case maps through its K_eff = 2K - 1 exponent
    correction.  Agrees with the search everywhere except occasional
    +/-1-slot float boundaries (regression-tested); unobservable-rate
    queues keep their current capacity."""
    f = cfg.target_frac
    rho = lam / xp.where(mu > 0, mu, 1.0)
    near1 = xp.abs(rho - 1.0) < 1e-6
    # floor keeps the (masked-out) rho=0 lane finite so the numpy form
    # computes warning-free; selected lanes are never floored
    safe_rho = xp.where(near1, 0.5,
                        xp.maximum(rho, 1e-30)).astype(xp.float32)
    xstar = xp.where(rho < 1.0,
                     (1.0 - f) / (1.0 - f * safe_rho),
                     (1.0 - f / safe_rho) / (1.0 - f))
    ke = xp.log(xstar) / xp.log(safe_rho)      # continuous exponent K
    ke = xp.where(near1, f / (1.0 - f), ke)
    k_mm = xp.ceil(ke)
    k_md = xp.ceil((ke + 1.0) / 2.0)           # K_eff = 2K - 1
    k = xp.where(cv2 >= 0.5, k_mm, k_md)
    k = xp.clip(k, cfg.min_capacity, cfg.max_capacity)
    return xp.where((lam > 0) & (mu > 0), k,
                    current).astype(xp.int32)


def _step_math(xp, cfg: ControlConfig, state: ControlState, lam, mu,
               ready, replicas, rep_basis, caps, cv2, occupancy,
               saturated, scalable, fleet_med, stale, faulty, leg_rep,
               leg_buf, leg_adm, headroom, max_reps, occ_hi, occ_lo,
               pressure, slo_target, over_frac):
    """The fused decision, once, against either array namespace.

    ``leg_rep``/``leg_buf``/``leg_adm`` are the per-queue tenant masks
    (they default to the config's static ``*_enabled`` flags when no
    multi-tenant overrides are given); ``headroom``/``max_reps`` are the
    per-queue replica-policy overrides.  ``stale`` marks queues whose
    arrival estimate froze while the stream went quiet (the window mean
    collapsed below ``stale_frac`` of the gated estimate) — a stale
    ``lam`` is treated as unknown, and the demand probe takes over.
    ``faulty`` is the degraded-mode leg: a queue whose consumer stage
    tripped the supervisor's crash-loop breaker gets its admission gate
    forced shut and its replica/buffer legs held still — estimates off
    a crash-looping stage are garbage, and re-tuning on garbage only
    spirals, so partial failure degrades gracefully instead.

    ``occ_hi``/``occ_lo``/``pressure`` are the class-aware admission
    legs (QoS lanes — see ``serve.qos``), again queue-padded operands
    so class churn never retraces: per-queue occupancy bands replace
    the config scalars (a patient class arms shedding at a lower fill),
    and ``pressure`` is an externally sensed urgency — a patient lane
    carries the hottest blocking lane's occupancy, so patient traffic
    is shed *first* when blocking traffic runs hot (``pressure >=
    occ_hi`` arms regardless of the lane's own collapse state) and is
    held shed until the pressure clears (``pressure <= occ_lo`` gates
    disarm).  The defaults (config scalars, zero pressure) reproduce
    the class-less behavior exactly.

    ``slo_target``/``over_frac`` are the SLO leg's queue-padded
    operands: per-queue latency targets (seconds, NaN = no SLO) and the
    fraction of the last harvest window's observations over target
    (NaN = no observations this window, which folds as zero burn —
    nothing served consumes no error budget, and an idle/shed queue's
    burn must decay, not pin).  The leg is a static config branch
    (``cfg.slo_enabled``), so SLO-less loops trace and run the exact
    pre-SLO decision."""
    lam = lam.astype(xp.float32)
    mu = mu.astype(xp.float32)
    cv2 = cv2.astype(xp.float32)
    occ = occupancy.astype(xp.float32)
    # ready == the head (service-rate) estimate is usable; demand is
    # usable only when the arrival leg also reports (a saturated
    # queue blocks the producer, so lam goes dark under overload) AND
    # the estimate is fresh (a quiet stream never re-converges, so the
    # frozen high estimate would keep the formula wanting replicas
    # nobody feeds)
    known = ready & (lam > 0) & ~stale

    # -- targets (identical math to the advisory readouts).  mu is
    # normalized by rep_basis — the replica count in effect when the
    # estimate was *produced*, not the current one: after a scale-up
    # the consumer often starves (service rate unobservable), the
    # estimate freezes, and dividing the frozen aggregate by the new
    # replica count would spiral the target upward every tick.
    rep_formula = _replica_targets(cfg, lam, mu, rep_basis, xp,
                                   headroom, max_reps)
    escalated = xp.clip(
        xp.ceil(replicas.astype(xp.float32) * cfg.saturation_growth),
        1, max_reps).astype(xp.int32)

    # -- SLO burn-rate leg (multi-window error-budget consumption) ------
    if cfg.slo_enabled:
        tgt = slo_target.astype(xp.float32)
        have_slo = ~xp.isnan(tgt)
        # instantaneous burn: fraction over target / budget fraction.
        # NaN over_frac (empty window) folds as zero — serving nothing
        # burns nothing, so idle/shed queues decay instead of pinning.
        ovf = over_frac.astype(xp.float32)
        inst = xp.where(xp.isnan(ovf), 0.0, ovf) \
            / xp.float32(max(cfg.slo_budget_frac, 1e-9))
        a_f = xp.float32(2.0 / (cfg.slo_fast_ticks + 1.0))
        a_s = xp.float32(2.0 / (cfg.slo_slow_ticks + 1.0))
        burn_fast = xp.where(
            have_slo, (1.0 - a_f) * state.burn_fast + a_f * inst, 0.0)
        burn_slow = xp.where(
            have_slo, (1.0 - a_s) * state.burn_slow + a_s * inst, 0.0)
        # hot needs BOTH windows over (the runbooks' page condition:
        # fast = it is burning now, slow = it has been long enough to
        # matter); hysteresis releases only once the fast window cools
        slo_hot = have_slo & xp.where(
            state.slo_hot, burn_fast > cfg.slo_burn_lo,
            (burn_fast > cfg.slo_burn_hi)
            & (burn_slow > cfg.slo_burn_hi))
        # burning faster than scale-out can save: shed to stop the bleed
        shed_slo = have_slo & (burn_fast >= cfg.slo_shed_burn)
        # scale-down freeze: while the SLOW window still remembers a
        # burn, handing capacity back would re-ignite the violation the
        # escalation just paid to put out (the fast window cools in a
        # few ticks; the slow window is the runbooks' "has the budget
        # actually recovered" question)
        slo_dn_hold = have_slo & (burn_slow > cfg.slo_burn_lo)
    else:
        burn_fast = state.burn_fast
        burn_slow = state.burn_slow
        slo_hot = xp.zeros_like(saturated)
        shed_slo = slo_hot
        have_slo = slo_hot
        slo_dn_hold = slo_hot

    # -- demand probe: scale-down for the escalated / stale regime ------
    # provision counts as escalation-driven from the tick saturation
    # fires until demand is observable again outside saturation
    esc = (state.escalated | (saturated & ready)) & ~(known & ~saturated)
    # a probe is useful only while demand is dark AND the queue is not
    # actively saturated (a saturated queue just proved demand exists —
    # that is the escalation leg's regime, and a probe window that
    # re-saturates aborts the cycle instead of decaying)
    elig = (esc | stale) & ~known & ~saturated & leg_rep & scalable \
        & (replicas > 1) & ~faulty
    timer = xp.where(elig, state.probe_timer + 1, 0)
    window_end = cfg.probe_period_ticks + cfg.probe_window_ticks
    # window open: the admission gate is forced open and the replica /
    # capacity legs hold still so returning demand becomes observable
    probing = elig & (timer > cfg.probe_period_ticks)
    # the whole window stayed dark: there is no demand at this level —
    # decay multiplicatively (AIMD's MD to the escalation's MI)
    decay = elig & (timer >= window_end)
    timer = xp.where(timer >= window_end, 0, timer)
    decayed = xp.clip(
        xp.ceil(replicas.astype(xp.float32) / cfg.saturation_growth),
        1, max_reps).astype(xp.int32)

    # saturated => demand is at least capacity and unobservable:
    # escalate multiplicatively until the queue unblocks and the
    # formula can take over (then any overshoot scales back down)
    rep_t = xp.where(decay, decayed,
                     xp.where(saturated & ready, escalated,
                              xp.where(known, rep_formula, replicas)))
    # SLO pressure escalates the replica target even when the rate
    # formula is satisfied — tail latency burns while throughput
    # balances (the slo_burn bench's regime).  Multiplicative like
    # saturation: each confirmed step recomputes off live replicas,
    # and the formula's want_dn walks it back once the burn cools.
    rep_t = xp.where(slo_hot, xp.maximum(rep_t, escalated), rep_t)
    # with an SLO armed, scale-down walks one multiplicative notch per
    # confirmed step (the probe's decay target) instead of snapping to
    # the rate formula: the formula is latency-blind, so a snap-down
    # can overshoot straight back into violation — stepping gives the
    # burn signal a veto point between steps
    rep_t = xp.where(have_slo & (rep_t < replicas),
                     xp.maximum(rep_t, decayed), rep_t)
    cap_t = _capacity_targets(cfg, lam, mu, cv2, caps, xp)

    # -- replica gating: confirmation counter + cooldown.  The leg is
    #    statically off when the PolicySet has no replica policy,
    #    per-tenant off through the leg mask, and per-queue off for
    #    unscalable queues (e.g. the pipeline's sink drain) — phantom
    #    wants there would only burn cooldown ---------------------------
    # degraded mode: a faulty queue's replica leg is held outright
    can_scale = scalable & leg_rep & ~faulty
    want_up = (rep_t > replicas) & (known | (saturated & ready)
                                    | slo_hot) \
        & can_scale & ~probing
    want_dn = (rep_t < replicas) & known & ~saturated & ~slo_hot \
        & ~slo_dn_hold & can_scale & ~probing
    rep_agree = xp.where(
        want_up, xp.maximum(state.rep_agree, 0) + 1,
        xp.where(want_dn, xp.minimum(state.rep_agree, 0) - 1, 0))
    # a decay fires directly: the dark probe window itself was the
    # confirmation, and the probe period already paces consecutive steps
    scale = ((xp.abs(rep_agree) >= cfg.confirm_ticks)
             & (state.cooldown <= 0) & ~probing) | decay

    # -- capacity gating: BufferAutotuner's hysteresis band, then the
    #    same confirmation + cooldown schedule.  A saturated queue is
    #    a replica problem, not a sizing problem: its stale rates
    #    would advise shrinking a full queue (always rejected); a
    #    probing queue holds capacity so the observation window is
    #    taken at the provision being probed ---------------------------
    ratio = cap_t.astype(xp.float32) \
        / xp.maximum(caps.astype(xp.float32), 1.0)
    outside = (ratio >= cfg.resize_factor) \
        | (ratio <= 1.0 / cfg.resize_factor)
    want_grow = known & outside & (cap_t > caps) & ~saturated \
        & leg_buf & ~probing & ~faulty
    want_shrink = known & outside & (cap_t < caps) & ~saturated \
        & leg_buf & ~probing & ~faulty
    cap_agree = xp.where(
        want_grow, xp.maximum(state.cap_agree, 0) + 1,
        xp.where(want_shrink, xp.minimum(state.cap_agree, 0) - 1, 0))
    resize = (xp.abs(cap_agree) >= cfg.confirm_ticks) \
        & (state.cooldown <= 0)

    # -- admission: peak-collapse + fleet-median straggler signal
    #    (the median of the ready rates arrives as an operand —
    #    np.median's introselect beats a full XLA CPU sort ~30x, and a
    #    scalar operand keeps the dispatch shape-stable) -----------------
    peak = xp.maximum(state.peak_mu * cfg.peak_decay,
                      xp.where(ready, mu, 0.0))
    n_ready = xp.sum(ready)
    straggler = ready & (n_ready >= cfg.min_ready) \
        & (mu < cfg.straggler_frac * fleet_med)
    collapsed = ready & (mu < cfg.collapse_frac * peak)
    # a saturated queue whose replica leg is maxed out cannot grow
    # its way back: shedding is the only lever left
    exhausted = saturated & ready & (replicas >= max_reps)
    hi = occ_hi.astype(xp.float32)
    lo = occ_lo.astype(xp.float32)
    prs = pressure.astype(xp.float32)
    arm = ((collapsed | straggler | exhausted) & (occ >= hi)) \
        | (prs >= hi) | shed_slo
    recovered = (mu >= cfg.recover_frac * peak) & ~straggler \
        & ~exhausted
    disarm = (recovered | (occ <= lo)) & (prs <= lo) & ~shed_slo
    # the arm/disarm memory keeps running through a probe window; only
    # the *output* gate is forced open so shed demand can show itself.
    # A faulty queue's gate is forced SHUT regardless — feeding load to
    # a crash-looping consumer only piles up work that dies with it
    shed_m = xp.where(state.shedding, ~disarm, arm) & leg_adm
    shed = (shed_m & ~probing) | (faulty & leg_adm)

    acted = scale | resize
    cooldown = xp.where(acted, cfg.cooldown_ticks,
                        xp.maximum(state.cooldown - 1, 0))
    new_state = ControlState(
        cooldown=cooldown.astype(xp.int32),
        rep_agree=xp.where(scale, 0, rep_agree).astype(xp.int32),
        cap_agree=xp.where(resize, 0, cap_agree).astype(xp.int32),
        shedding=shed_m, peak_mu=peak.astype(xp.float32),
        escalated=esc, probe_timer=timer.astype(xp.int32),
        burn_fast=burn_fast.astype(xp.float32),
        burn_slow=burn_slow.astype(xp.float32),
        slo_hot=slo_hot)
    return new_state, Decision(rep_t, scale, cap_t, resize, shed,
                               straggler, probing, slo_hot)


@functools.lru_cache(maxsize=None)
def _decide_step(cfg: ControlConfig, donate: bool):
    """Jitted fused decision step, cached per config.  Shape-polymorphic
    through jit's shape cache: callers pad the queue axis to a
    ``cfg.block_q`` multiple, so ragged fleets share one trace."""

    def step(state: ControlState, **operands):
        _TRACE_COUNT[0] += 1       # python body runs at trace time only
        return _step_math(jnp, cfg, state, **operands)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def resolve_impl(impl: str) -> str:
    """The decision's execution form: ``"auto"`` is numpy on the host
    backend (the ~150 us per-dispatch XLA CPU floor dwarfs the decision
    itself) and jit on a device — decided by ``core.backend.on_host``,
    the same probe that picks the kernels' interpret mode."""
    if impl == "auto":
        return "numpy" if on_host() else "jit"
    if impl not in ("numpy", "jit"):
        raise ValueError(f"bad impl {impl!r}")
    return impl


# per-queue decision operands: dtype, and the fill for padded rows under
# jit (chosen so a padded row decides nothing)
_OPERANDS = {
    "lam": (np.float32, 0.0), "mu": (np.float32, 0.0),
    "ready": (bool, False), "replicas": (np.int32, 1),
    "rep_basis": (np.int32, 1), "caps": (np.int32, 1),
    "cv2": (np.float32, 1.0), "occupancy": (np.float32, 0.0),
    "saturated": (bool, False), "scalable": (bool, False),
    "stale": (bool, False), "faulty": (bool, False),
    "leg_rep": (bool, False), "leg_buf": (bool, False),
    "leg_adm": (bool, False), "headroom": (np.float32, 1.0),
    "max_reps": (np.int32, 1),
    # padded rows must never arm via pressure: hi=2 is unreachable
    "occ_hi": (np.float32, 2.0), "occ_lo": (np.float32, 0.0),
    "pressure": (np.float32, 0.0),
    # NaN pad = no SLO on padded rows (the leg's own neutral value)
    "slo_target": (np.float32, np.nan), "over_frac": (np.float32, np.nan),
}


def _jit_operands(cfg: "ControlConfig", state: "ControlState", q: int,
                  fleet_med: float, ops: dict):
    """Device operands for ``_decide_step``: every (Q,) operand (scalars
    broadcast) padded to a ``cfg.block_q`` multiple, and the state with
    it.  Returns ``(state, operands)``."""
    rpad = -(-q // cfg.block_q) * cfg.block_q - q
    out = {"fleet_med": jnp.float32(fleet_med)}
    for name, v in ops.items():
        dt, fill = _OPERANDS[name]
        a = jnp.broadcast_to(jnp.asarray(v, dt), (q,))
        out[name] = (jnp.pad(a, (0, rpad), constant_values=fill)
                     if rpad else a)
    state = ControlState(*(jnp.asarray(leaf) for leaf in state))
    if rpad:
        state = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, (0, rpad)), state)
    return state, out


def control_decide(cfg: ControlConfig, state: ControlState, *,
                   lam, mu, ready, replicas, caps, cv2=1.0, occupancy=0.0,
                   rep_basis=None, saturated=None, scalable=None,
                   stale=None, faulty=None, leg_rep=None, leg_buf=None,
                   leg_adm=None, headroom=None, max_replicas=None,
                   occ_hi=None, occ_lo=None, pressure=None,
                   slo_target=None, over_frac=None,
                   impl: str = "auto", donate: bool = True
                   ) -> tuple[ControlState, Decision]:
    """Evaluate every policy for the whole fleet in one fused pass.

    All per-queue operands are (Q,).  ``impl`` selects the execution
    form of the *same* ``_step_math`` source: ``"jit"`` pads the queue
    axis to a ``cfg.block_q`` multiple with never-ready rows so ragged
    fleet sizes share one trace (padded rows decide nothing) and runs
    the cached jitted dispatch; ``"numpy"`` executes it directly (the
    host fast path); ``"auto"`` picks by jax backend.  ``rep_basis`` is
    the per-queue replica count each ``mu`` estimate was measured at
    (the ``ControlLoop`` tracks it; defaults to ``replicas``).
    ``saturated`` marks queues whose producer end blocked persistently —
    demand there is unobservable and the replica leg escalates
    multiplicatively instead of trusting stale rates (default: none).
    ``stale`` marks queues whose arrival estimate froze after the
    stream went quiet (demand probe input; default none).  ``faulty``
    marks queues whose consumer is degraded (crash-loop breaker):
    admission is forced shut and the replica/buffer legs held — a
    queue-padded (Q,) operand like ``stale``, so the degraded-mode leg
    never retraces the dispatch (default none).  The
    multi-tenant overrides — ``leg_rep``/``leg_buf``/``leg_adm`` masks
    and per-queue ``headroom``/``max_replicas`` — default to the static
    config flags/knobs, so single-tenant behavior is unchanged.
    ``occ_hi``/``occ_lo`` are per-queue admission occupancy bands (QoS
    classes — NaN entries inherit the config scalars) and ``pressure``
    is the per-queue sibling-lane urgency (``>= occ_hi`` arms shedding
    outright; ``<= occ_lo`` is required to disarm) — all three are
    queue-padded operands with semantics-preserving defaults, so class
    churn never retraces the dispatch.  ``slo_target``/``over_frac``
    feed the burn-rate leg (see ``_step_math``): per-queue latency
    targets in seconds (NaN = no SLO) and the observed fraction of the
    last window over target (NaN = empty window), defaulting to
    all-NaN so SLO-less callers decide identically.
    Under ``"jit"`` the ``state`` is donated by default — callers keep
    only the returned state, exactly like the fleet monitor dispatch.
    """
    lam = np.asarray(lam, np.float32)
    q = lam.shape[0]
    if rep_basis is None:
        rep_basis = replicas
    if saturated is None:
        saturated = np.zeros(q, bool)
    if scalable is None:
        scalable = np.ones(q, bool)
    if stale is None:
        stale = np.zeros(q, bool)
    if faulty is None:
        faulty = np.zeros(q, bool)
    if leg_rep is None:
        leg_rep = cfg.replica_enabled
    if leg_buf is None:
        leg_buf = cfg.buffer_enabled
    if leg_adm is None:
        leg_adm = cfg.admission_enabled
    if headroom is None:
        headroom = cfg.headroom
    if max_replicas is None:
        max_replicas = cfg.max_replicas

    def band(v, default):
        # per-queue occupancy band, NaN = inherit the config scalar
        if v is None:
            return np.float32(default)
        v = np.asarray(v, np.float32)
        return np.where(np.isnan(v), np.float32(default), v)

    occ_hi = band(occ_hi, cfg.occupancy_hi)
    occ_lo = band(occ_lo, cfg.occupancy_lo)
    if pressure is None:
        pressure = 0.0
    # SLO operands: NaN target = no SLO, NaN over_frac = empty window
    # (zero burn).  NaN defaults keep the leg inert without retracing.
    if slo_target is None:
        slo_target = np.nan
    if over_frac is None:
        over_frac = np.nan
    # fleet median of the ready service rates, for the straggler leg
    # (numpy introselect off-dispatch: XLA CPU would sort, ~30x slower)
    mu_np = np.asarray(mu, np.float32)
    ready_np = np.asarray(ready, bool)
    fleet_med = (float(np.median(mu_np[ready_np]))
                 if ready_np.any() else 0.0)
    impl = resolve_impl(impl)
    ops = dict(
        lam=lam, mu=mu, ready=ready, replicas=replicas,
        rep_basis=rep_basis, caps=caps, cv2=cv2, occupancy=occupancy,
        saturated=saturated, scalable=scalable, stale=stale,
        faulty=faulty, leg_rep=leg_rep, leg_buf=leg_buf, leg_adm=leg_adm,
        headroom=headroom, max_reps=max_replicas, occ_hi=occ_hi,
        occ_lo=occ_lo, pressure=pressure, slo_target=slo_target,
        over_frac=over_frac)

    if impl == "numpy":
        def npa(name, v):
            a = np.asarray(v, _OPERANDS[name][0])
            return np.broadcast_to(a, (q,)) if a.ndim == 0 else a

        st = ControlState(*(np.asarray(leaf) for leaf in state))
        ops = {name: npa(name, v) for name, v in ops.items()}
        # masked-out lanes (mu <= 0 etc.) compute garbage by design and
        # are discarded by the final where — same as under XLA, minus
        # the numpy warnings
        with np.errstate(divide="ignore", invalid="ignore"):
            return _step_math(np, cfg, st, fleet_med=np.float32(fleet_med),
                              **ops)

    state, operands = _jit_operands(cfg, state, q, fleet_med, ops)
    state, dec = _decide_step(cfg, donate)(state, **operands)
    if state.cooldown.shape[0] != q:       # drop the padded rows
        state = jax.tree_util.tree_map(lambda a: a[:q], state)
        dec = jax.tree_util.tree_map(lambda a: a[:q], dec)
    return state, dec


# -- policy objects: the advisory surface over the same math -----------------

class ReplicaPolicy:
    """Stage-duplication policy.  ``targets`` is the advisory readout;
    the control loop's fused decision computes the identical jnp
    expression, so ``Pipeline.recommended_replicas`` can never disagree
    with what the loop actuates.  Knobs come from (and stay in sync
    with) a ``ParallelismController``."""

    def __init__(self, ctrl: Optional[ParallelismController] = None):
        self.ctrl = ctrl or ParallelismController()

    def config_kwargs(self) -> dict:
        return {"headroom": self.ctrl.headroom,
                "max_replicas": self.ctrl.max_replicas}

    def targets(self, lam, mu, replicas=1) -> np.ndarray:
        """(Q,) replica targets.  ``mu`` is the measured aggregate stage
        rate; pass the live ``replicas`` it was measured at (default 1,
        the scalar-formula case) so the per-copy rate normalizes.
        Evaluated in numpy — an advisory poll must not pay eager XLA
        dispatches; the jitted decision traces the same function."""
        cfg = ControlConfig(**self.config_kwargs())
        q = np.shape(np.asarray(lam))[0]
        reps = np.broadcast_to(np.asarray(replicas, np.int32), (q,))
        return _replica_targets(
            cfg, np.asarray(lam, np.float32),
            np.asarray(mu, np.float32), reps, np)


class BufferPolicy:
    """Queue-capacity policy over ``BufferAutotuner``'s analytic sizing
    (and its hysteresis band, applied inside the fused decision)."""

    def __init__(self, tuner: Optional[BufferAutotuner] = None):
        self.tuner = tuner or BufferAutotuner()

    def config_kwargs(self) -> dict:
        t = self.tuner
        return {"target_frac": t.target_frac,
                "resize_factor": t.resize_factor,
                "min_capacity": t.min_capacity,
                "max_capacity": t.max_capacity}

    def targets(self, lam, mu, current, cv2=1.0) -> np.ndarray:
        cfg = ControlConfig(**self.config_kwargs())
        with np.errstate(divide="ignore", invalid="ignore"):
            return _capacity_targets(
                cfg, np.asarray(lam, np.float32),
                np.asarray(mu, np.float32),
                np.asarray(cv2, np.float32),
                np.asarray(current, np.int32), np)


class AdmissionPolicy:
    """Admission gate policy: shed (reject now) or defer (block until
    the gate reopens) when a stream's service rate collapses while its
    queue runs hot.  The straggler leg shares ``StragglerDetector``'s
    threshold semantics (below ``straggler_frac`` x fleet median)."""

    def __init__(self, detector: Optional[StragglerDetector] = None, *,
                 mode: str = "shed", collapse_frac: float = 0.5,
                 recover_frac: float = 0.75, occupancy_hi: float = 0.9,
                 occupancy_lo: float = 0.5):
        if mode not in ("shed", "defer"):
            raise ValueError(f"bad admission mode {mode!r}")
        self.detector = detector or StragglerDetector()
        self.mode = mode
        self.collapse_frac = collapse_frac
        self.recover_frac = recover_frac
        self.occupancy_hi = occupancy_hi
        self.occupancy_lo = occupancy_lo

    def config_kwargs(self) -> dict:
        return {"collapse_frac": self.collapse_frac,
                "recover_frac": self.recover_frac,
                "occupancy_hi": self.occupancy_hi,
                "occupancy_lo": self.occupancy_lo,
                "straggler_frac": self.detector.threshold,
                "min_ready": self.detector.min_hosts}


class SLOPolicy:
    """Latency-SLO / error-budget policy (the burn-rate leg).

    ``target_s`` is the default per-queue latency target in seconds
    (scalar, (Q,) array, or None to rely entirely on actuator-supplied
    targets — ``serve.Engine`` derives per-lane targets from its QoS
    class deadlines).  ``budget_frac`` is the error budget: the
    fraction of observations allowed over target; the burn rate is
    budget consumed per unit budgeted (1.0 = burning exactly at
    budget).  Fast/slow window lengths and thresholds follow the
    multi-window burn-rate runbooks: escalate replicas when both
    windows exceed ``burn_hi``; arm admission when the fast window
    exceeds ``shed_burn`` (too hot to scale out of)."""

    def __init__(self, target_s=None, *, budget_frac: float = 0.01,
                 fast_ticks: int = 5, slow_ticks: int = 60,
                 burn_hi: float = 1.0, burn_lo: float = 0.5,
                 shed_burn: float = 6.0):
        self.target_s = target_s
        self.budget_frac = float(budget_frac)
        self.fast_ticks = int(fast_ticks)
        self.slow_ticks = int(slow_ticks)
        self.burn_hi = float(burn_hi)
        self.burn_lo = float(burn_lo)
        self.shed_burn = float(shed_burn)

    def config_kwargs(self) -> dict:
        return {"slo_enabled": True,
                "slo_budget_frac": self.budget_frac,
                "slo_fast_ticks": self.fast_ticks,
                "slo_slow_ticks": self.slow_ticks,
                "slo_burn_hi": self.burn_hi,
                "slo_burn_lo": self.burn_lo,
                "slo_shed_burn": self.shed_burn}

    def targets(self, q: int) -> np.ndarray:
        """(Q,) default latency targets (NaN = no SLO) — the loop's
        sense step overlays actuator-supplied per-queue targets."""
        if self.target_s is None:
            return np.full(q, np.nan, np.float32)
        t = np.asarray(self.target_s, np.float32)
        return np.broadcast_to(t, (q,)).copy() if t.ndim == 0 else t


@dataclasses.dataclass
class PolicySet:
    """The policies one control loop evaluates (any may be None).  The
    merged ``ControlConfig`` is the decision dispatch's cache key, so
    every loop with the same knobs shares one compiled step."""
    replica: Optional[ReplicaPolicy] = None
    buffer: Optional[BufferPolicy] = None
    admission: Optional[AdmissionPolicy] = None
    slo: Optional[SLOPolicy] = None
    confirm_ticks: int = 2
    cooldown_ticks: int = 4
    block_q: int = 256
    probe_period_ticks: int = 16
    probe_window_ticks: int = 4

    def control_config(self) -> ControlConfig:
        kw: dict = {"confirm_ticks": self.confirm_ticks,
                    "cooldown_ticks": self.cooldown_ticks,
                    "block_q": self.block_q,
                    "probe_period_ticks": self.probe_period_ticks,
                    "probe_window_ticks": self.probe_window_ticks,
                    "replica_enabled": self.replica is not None,
                    "buffer_enabled": self.buffer is not None,
                    "admission_enabled": self.admission is not None}
        for p in (self.replica, self.buffer, self.admission, self.slo):
            if p is not None:
                kw.update(p.config_kwargs())
        return ControlConfig(**kw)
