"""Device-resident heartbeat wave: fused eligibility→score→pick.

One wave of the online matcher (`ShardedMatcher.match_wave`) is, on the
host path, a Python loop over machines in descending-capacity order, each
iteration running `Matcher.match_batch` — the last per-heartbeat O(m)
Python loop in the system.  This module turns the whole wave into one
registry op (``match_wave`` in `core/engine/kernels.py`) with three
implementations:

  numpy  — the extracted host loop, bit-for-bit the historical wave
           (now passing the wave's ``active`` mask into ``match_batch``
           instead of compressing the batch per machine).
  xla    — a ``lax.scan`` over the host-computed machine order that fuses
           eligibility, pack scoring, bundling/deficit gating and the
           ``avail[m] -= demand`` update into ONE device launch per wave,
           plus at most one dirty-row upload launch.
  pallas — the same fused walk as a single sequential Pallas program
           (`kernels/placement_scan`), interpret-validated off-TPU.

Exactness.  The matcher's decisions must stay bit-identical to the numpy
oracle, which rules out float32 and *also* rules out letting XLA contract
multiply→add chains into fused-multiply-adds (XLA CPU contracts them
unconditionally; ``--xla_allow_excess_precision=false`` does not stop it).
Two measures make the device arithmetic reproduce numpy float64 exactly:

  * every float op runs in float64 under ``jax.enable_x64``;
  * every product that feeds an add/sub is *laundered* through
    ``where(p == p, p, 0.0)`` — a bitwise identity XLA cannot see through,
    so the add rounds the already-rounded product exactly like numpy does.

The one numpy op with no portable bit pattern — the BLAS matvec the
matcher used for its packing score — was reformulated in
`core/online.py::seq_dot` as an explicit left-to-right accumulation, which
both the numpy oracle and the device kernels now share.

State residency.  A `DeviceWaveState` (one per `ShardedMatcher`) keeps the
``avail`` matrix, candidate columns, EMA pair and dense deficit ledger on
device across waves.  Host-side shadows detect what actually changed:
``avail`` rows touched by task finishes/failures re-upload (dirty rows
only), candidate columns re-upload only when `TaskPool.refresh` rebuilt
them (array identity), and the EMA/deficit ledgers re-upload only if the
host replay diverged from the device's own update (never, absent external
edits).  Per-wave host↔device traffic is therefore the machine order, the
dirty state, and the picks list — not the O(n×m) eligibility matrix of
the PR 6 path (``match_wave.*.bytes_*`` PROFILE counters quantify it).

Fault seam: the op dispatches through ``kernels._run_op``, so an injected
``kernel_impl`` fault sticky-demotes the wave back to the numpy loop
mid-run with zero decision drift — the device impls mutate no matcher
state before their launch returns.  A real kernel failure propagates.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Callable

import numpy as np

from . import packing

if TYPE_CHECKING:  # runtime import would cycle: online -> engine -> here
    from ..online import CandidateBatch


@dataclasses.dataclass
class WaveContext:
    """One heartbeat wave's inputs, handed to the ``match_wave`` op.

    ``start_cb(row, machine)`` applies a pick's side effects (including
    the host ``avail[machine] -= demand`` update); rows index ``batch``.
    """

    sm: object                 # the owning core.shard.ShardedMatcher
    avail: np.ndarray          # (m, d) float64, mutated by start_cb
    alive: np.ndarray          # (m,) bool
    batch: CandidateBatch
    start_cb: Callable[[int, int], None]


# ----------------------------------------------------------------------
# numpy implementation — the reference wave loop
# ----------------------------------------------------------------------

def match_wave_numpy(ctx: WaveContext) -> int:
    """The host wave loop (decision oracle for the fused kernels).

    Identical to the historical `ShardedMatcher.match_wave` body except
    that the wave's ``active`` mask is passed straight into
    ``match_batch`` (O(1) per-machine allocations) instead of compressing
    the batch with ``batch.take`` per machine (O(m) copies per wave) —
    decision-identical, see `Matcher.match_batch`.
    """
    sm, avail, alive, batch = ctx.sm, ctx.avail, ctx.alive, ctx.batch
    start_cb = ctx.start_cb
    eligible, machine_any = sm.eligibility(avail, batch.dem)
    active = np.ones(len(batch), dtype=bool)
    n_active = len(batch)
    order = np.argsort(-avail.sum(axis=1))
    # visit only machines that can possibly pick: dead, drained, or
    # candidate-less machines are guaranteed matcher no-ops
    ok = (alive[order] & (avail[order] > 1e-9).any(axis=1)
          & machine_any[order])
    matcher = sm.matcher
    cfg = sm.cfg
    n_picks = 0
    for m in order[ok].tolist():
        if n_active == 0:
            break
        if not (eligible[:, m] & active).any():
            continue
        picks = matcher.match_batch(m, avail[m], batch, active=active)
        if picks:
            ledger = sm.shard_matchers[sm.plan.shard_of(m)].deficits
            for gi, _over in picks:
                start_cb(gi, m)
                active[gi] = False
                ledger.allocated(int(batch.grp[gi]),
                                 cfg.fairness(batch.dem[gi]))
            n_active -= len(picks)
            n_picks += len(picks)
    return n_picks


# ----------------------------------------------------------------------
# fused device kernel (shared by the xla and pallas implementations)
# ----------------------------------------------------------------------

try:
    import jax
    import jax.numpy as jnp
    from jax import lax

    _HAVE_JAX = True
except ImportError:  # pragma: no cover - jax-less installs use numpy only
    jax = jnp = lax = None
    _HAVE_JAX = False


def have_jax() -> bool:
    """Whether the fused device wave can run (gates its registration)."""
    return _HAVE_JAX


#: consts vector layout (f64 scalars passed per call, not traced into the
#: compiled bucket): eps, overbook slack, remote penalty, eta_m,
#: must-serve threshold (kappa * capacity), EMA step a, 1 - a, srpt floor
_C_EPS, _C_SLACK, _C_RP, _C_ETA_M, _C_THRESH, _C_A, _C_1MA, _C_FLOOR = \
    range(8)
_EMA_A = 0.05
_EMA_1MA = 1.0 - 0.05          # bound host-side once; uploaded, never re-derived
_SRPT_FLOOR = 1e-12


def _launder(p):
    """Bitwise identity that XLA cannot fold away: blocks the (otherwise
    unconditional on CPU) contraction of this product into an FMA with a
    following add/sub, so the sum rounds the product exactly like numpy."""
    return jnp.where(p == p, p, 0.0)


def wave_core(avail, order, dem, pri, srpt, gidx, loc, taken0, ema,
              deficit, share, fd_mask, rd_mask, fg_mask, consts, *,
              bundle_limit: int, use_packing: bool, use_srpt: bool,
              use_overbooking: bool, drf: bool):
    """The fused wave: scan machines in order, bundle picks per machine.

    Pure jnp/f64 — traced both by the jitted xla implementation and
    inside the Pallas kernel, so the two device paths share one set of
    semantics.  Every float op mirrors one numpy op of
    `Matcher.match_batch` in the same order (see module docstring).

    Shapes: avail (m, d) f64 resident; order (m,) i32 (visit order,
    -1-padded after host-side alive/drained filtering — visiting a
    machine the host would have *skipped* is decision-free, it can pick
    nothing and mutates nothing, so the eligibility prefilter needs no
    launch of its own); dem (n, d) f64 with pad rows pre-taken; deficit
    (G,) f64 dense in ledger key order, -inf pads (share pads 0, so
    ``allocated`` leaves them at -inf and ``argmax`` never picks one).

    Returns (avail', ema', deficit', rows, machines, over, obs, count):
    the pick list in pick order plus each pick's observed score
    ``pri*base`` (host-side EMA replay needs it — ``base`` depends on the
    in-kernel local avail the host never sees).
    """
    n = dem.shape[0]
    d = dem.shape[1]
    eps = consts[_C_EPS]
    neg_inf = jnp.float64(-jnp.inf)
    i32 = jnp.int32

    def visit(carry, mid):
        avail, taken, ema_s, ema_r, deficit, rows, mach, overf, obs, cnt \
            = carry
        row0 = avail[mid]
        rp = jnp.where((loc >= 0) & (loc != mid), consts[_C_RP], 1.0)

        def body(st):
            (j, local, row, taken, ema_s, ema_r, deficit, rows, mach,
             overf, obs, cnt, _stop) = st
            # -- eligibility (exact f64; masked-out dims always pass) ---
            thr = local + eps
            fits = jnp.where(fd_mask[None, :], dem <= thr[None, :],
                             True).all(axis=1)
            if use_overbooking:
                thr_g = (local + consts[_C_SLACK]) + eps
                over = (~fits
                        & jnp.where(rd_mask[None, :], dem <= thr[None, :],
                                    True).all(axis=1)
                        & jnp.where(fg_mask[None, :], dem <= thr_g[None, :],
                                    True).all(axis=1))
            else:
                over = jnp.zeros(n, dtype=bool)
            eligible = (fits | over) & ~taken
            # -- deficit gating (dense mirror of DeficitCounters) -------
            gstar = jnp.argmax(deficit).astype(i32)
            forced = eligible & (gidx == gstar)
            use_forced = (deficit[gstar] >= consts[_C_THRESH]) & forced.any()
            eligible = jnp.where(use_forced, forced, eligible)
            any_elig = eligible.any()
            # -- scoring (seq_dot mirror; every product laundered) ------
            if use_packing:
                av = jnp.clip(local, 0.0, None)
                acc = _launder(dem[:, 0] * av[0])
                for k in range(1, d):
                    acc = acc + _launder(dem[:, k] * av[k])
                dot = acc * rp
            else:
                dot = rp
            overshoot = jnp.clip(
                jnp.where(fg_mask[None, :], dem - local[None, :],
                          neg_inf).max(axis=1), 0.0, None)
            base = jnp.where(fits, dot,
                             dot * jnp.maximum(1.0 - overshoot, 0.05))
            if use_srpt:
                eta = (consts[_C_ETA_M] * ema_s
                       / jnp.maximum(ema_r, consts[_C_FLOOR]))
            else:
                eta = jnp.float64(0.0)
            perf = _launder(pri * base) - _launder(eta * srpt)
            pool_fit = eligible & fits
            pool = jnp.where(pool_fit.any(), pool_fit, eligible)
            score = jnp.where(pool, perf, neg_inf)
            i = jnp.argmax(score)
            ok = any_elig & jnp.isfinite(score[i])
            # -- apply the pick (no-ops when ~ok) -----------------------
            obs_i = pri[i] * base[i]
            w = dem[i].max() if drf else jnp.float64(1.0)
            taken = taken.at[i].set(taken[i] | ok)
            ema_s = jnp.where(
                ok, _launder(consts[_C_1MA] * ema_s)
                + _launder(consts[_C_A] * obs_i), ema_s)
            ema_r = jnp.where(
                ok, _launder(consts[_C_1MA] * ema_r)
                + _launder(consts[_C_A]
                           * jnp.maximum(srpt[i], consts[_C_FLOOR])), ema_r)
            deficit = jnp.where(
                ok, (deficit + _launder(share * w)).at[gidx[i]].add(-w),
                deficit)
            local = jnp.where(ok, jnp.clip(local - dem[i], 0.0, None), local)
            row = jnp.where(ok, row - dem[i], row)
            rows = rows.at[cnt].set(jnp.where(ok, i.astype(i32), rows[cnt]))
            mach = mach.at[cnt].set(jnp.where(ok, mid, mach[cnt]))
            overf = overf.at[cnt].set(
                jnp.where(ok, over[i].astype(jnp.int8), overf[cnt]))
            obs = obs.at[cnt].set(jnp.where(ok, obs_i, obs[cnt]))
            cnt = cnt + jnp.where(ok, i32(1), i32(0))
            return (j + 1, local, row, taken, ema_s, ema_r, deficit, rows,
                    mach, overf, obs, cnt, ~ok)

        def do_visit(carry):
            avail, taken, ema_s, ema_r, deficit, rows, mach, overf, obs, \
                cnt = carry
            st = (i32(0), row0, row0, taken, ema_s, ema_r, deficit, rows,
                  mach, overf, obs, cnt, False)
            st = lax.while_loop(
                lambda st: (~st[-1]) & (st[0] < bundle_limit), body, st)
            (_j, _local, row, taken, ema_s, ema_r, deficit, rows, mach,
             overf, obs, cnt, _stop) = st
            return (avail.at[mid].set(row), taken, ema_s, ema_r, deficit,
                    rows, mach, overf, obs, cnt)

        carry = lax.cond(mid >= 0, do_visit, lambda c: c,
                         (avail, taken, ema_s, ema_r, deficit, rows, mach,
                          overf, obs, cnt))
        return carry, None

    rows0 = jnp.zeros(n, dtype=i32)
    mach0 = jnp.zeros(n, dtype=i32)
    over0 = jnp.zeros(n, dtype=jnp.int8)
    obs0 = jnp.zeros(n, dtype=jnp.float64)
    init = (avail, taken0, ema[0], ema[1], deficit, rows0, mach0, over0,
            obs0, i32(0))
    out, _ = lax.scan(visit, init, order)
    avail, _taken, ema_s, ema_r, deficit, rows, mach, overf, obs, cnt = out
    return avail, jnp.stack([ema_s, ema_r]), deficit, rows, mach, overf, \
        obs, cnt


def _build_wave_fn(m, d, n_cap, g_cap, bundle_limit, use_packing,
                   use_srpt, use_overbooking, drf):
    """One compile bucket of the fused wave (donated resident avail)."""
    import functools

    core = functools.partial(wave_core, bundle_limit=bundle_limit,
                             use_packing=use_packing, use_srpt=use_srpt,
                             use_overbooking=use_overbooking, drf=drf)
    return jax.jit(core, donate_argnums=_donate())


def _build_pallas_wave_fn(m, d, n_cap, g_cap, bundle_limit, use_packing,
                          use_srpt, use_overbooking, drf):
    from ...kernels.placement_scan import ops as ps_ops

    import functools
    return functools.partial(
        ps_ops.match_wave_walk, bundle_limit=bundle_limit,
        use_packing=use_packing, use_srpt=use_srpt,
        use_overbooking=use_overbooking, drf=drf)


# built lazily (kernels.py imports this module while itself initializing)
_WAVE_FNS = None
_UPD_FNS = None


def _caches():
    global _WAVE_FNS, _UPD_FNS
    if _WAVE_FNS is None:
        from . import kernels as K

        _WAVE_FNS = K._BucketCache(_build_wave_fn, "wave")
        _UPD_FNS = K._BucketCache(_build_row_update_fn, "wave_row")
    return _WAVE_FNS, _UPD_FNS


_PALLAS_FNS = None


def _pallas_cache():
    global _PALLAS_FNS
    if _PALLAS_FNS is None:
        from . import kernels as K

        _PALLAS_FNS = K._BucketCache(_build_pallas_wave_fn, "wave_pallas")
    return _PALLAS_FNS


def _donate() -> tuple:
    """Donate the resident avail buffer where donation is implemented
    (donating on CPU only earns a warning per compile)."""
    return () if jax.default_backend() == "cpu" else (0,)


def _build_row_update_fn(r_cap):
    """Dirty-row scatter into the resident avail mirror (donated)."""
    def upd(avail, rows, vals):
        return avail.at[rows].set(vals)

    return jax.jit(upd, donate_argnums=_donate())


def pallas_wave_available() -> bool:
    """The Pallas wave needs f64, which only interpret mode provides."""
    if not _HAVE_JAX:
        return False
    from . import kernels as K

    if not K._have_pallas():
        return False
    return jax.default_backend() == "cpu"


# ----------------------------------------------------------------------
# device-resident wave state (one per ShardedMatcher)
# ----------------------------------------------------------------------

class DeviceWaveState:
    """Host-shadowed device mirrors for the fused wave.

    The shadows are plain numpy copies of what the device currently
    holds; before each wave the host state is diffed against them and
    only the difference is uploaded.  After a wave, the host replay
    applies the same arithmetic the kernel did, so the refreshed shadows
    equal the device buffers bit-for-bit and steady-state waves upload
    nothing but the machine order and whatever the simulator touched.
    """

    def __init__(self):
        self.avail_dev = None
        self.avail_shadow = None
        # candidate-column cache: the column arrays of the last upload,
        # held so their identities cannot be reused by new arrays
        self.n_cap = 0
        self.cols = None
        self.dem_dev = self.pri_dev = self.srpt_dev = None
        self.gidx_dev = self.loc_dev = self.taken0_dev = None
        self.gidx_np = None
        # ledger mirrors
        self.keys = None               # deficit key order at last upload
        self.gmap_lut = None           # group id -> dense ledger index
        self.g_cap = 0
        self.ema_dev = None
        self.ema_shadow = None
        self.deficit_dev = None
        self.deficit_shadow = None
        self.share_dev = None


def _bstat(impl: str, key: str, n) -> None:
    from . import kernels as K

    K.transfer_add(f"match_wave.{impl}.{key}", int(n))


def _sync_avail(st: DeviceWaveState, avail: np.ndarray, impl: str,
                upd_fns) -> int:
    """Upload only rows that changed since the last wave's replay.

    Returns the number of extra device launches used (0 or 1)."""
    m, d = avail.shape
    if st.avail_shadow is None or st.avail_shadow.shape != avail.shape:
        st.avail_dev = jnp.asarray(avail, dtype=jnp.float64)
        st.avail_shadow = avail.copy()
        _bstat(impl, "bytes_h2d", avail.nbytes)
        return 0
    dirty = np.flatnonzero((st.avail_shadow != avail).any(axis=1))
    if len(dirty) == 0:
        return 0
    if len(dirty) * 2 >= m:
        st.avail_dev = jnp.asarray(avail, dtype=jnp.float64)
        st.avail_shadow = avail.copy()
        _bstat(impl, "bytes_h2d", avail.nbytes)
        return 0
    from . import kernels as K

    r_cap = K.bucket(len(dirty), floor=8)
    rows = np.zeros(r_cap, dtype=np.int32)
    rows[:len(dirty)] = dirty
    rows[len(dirty):] = dirty[0]       # duplicate writes carry equal values
    vals = avail[rows].astype(np.float64)
    st.avail_dev = upd_fns.get((r_cap,))(st.avail_dev, jnp.asarray(rows),
                                         jnp.asarray(vals))
    st.avail_shadow[dirty] = avail[dirty]
    _bstat(impl, "bytes_h2d", rows.nbytes + vals.nbytes)
    return 1


def _sync_batch(st: DeviceWaveState, batch: CandidateBatch, gmap_lut,
                impl: str) -> bool:
    """Upload candidate columns that `TaskPool.refresh` actually rebuilt.

    Column array identity is the dirtiness signal: the pool reuses cached
    rows for clean jobs and swaps only the srpt column on srpt-only
    refreshes, so steady-state waves re-upload nothing.  The state holds
    the uploaded arrays themselves: comparing bare ``id()`` values would
    let a freshly built column that reuses a freed array's address pass
    for the uploaded one.  Returns False when a candidate's group is
    unknown to the ledger (numpy fallback).
    """
    n = len(batch)
    from . import kernels as K

    n_cap = K.bucket(n, floor=64)
    cols = (batch.dem, batch.pri, batch.srpt, batch.grp, batch.loc)
    same = ([a is b for a, b in zip(cols, st.cols)] if st.cols is not None
            else [False] * len(cols))
    if st.n_cap == n_cap and all(same) and st.gidx_np is not None:
        return True
    rebuild = st.n_cap != n_cap or not same[0]
    if rebuild or not same[3]:
        if batch.grp.min(initial=0) < 0 \
                or batch.grp.max(initial=-1) >= len(gmap_lut):
            return False
        gidx = gmap_lut[batch.grp]
        if (gidx < 0).any():
            return False
        gp = np.zeros(n_cap, dtype=np.int32)
        gp[:n] = gidx
        st.gidx_np = gidx
        st.gidx_dev = jnp.asarray(gp)
        _bstat(impl, "bytes_h2d", gp.nbytes)
    if rebuild:
        dem = np.full((n_cap, batch.dem.shape[1]), 2.0, dtype=np.float64)
        dem[:n] = batch.dem
        taken0 = np.ones(n_cap, dtype=bool)
        taken0[:n] = False
        st.dem_dev = jnp.asarray(dem)
        st.taken0_dev = jnp.asarray(taken0)
        _bstat(impl, "bytes_h2d", dem.nbytes + taken0.nbytes)
    if rebuild or not same[1]:
        pri = np.zeros(n_cap, dtype=np.float64)
        pri[:n] = batch.pri
        st.pri_dev = jnp.asarray(pri)
        _bstat(impl, "bytes_h2d", pri.nbytes)
    if rebuild or not same[2]:
        srpt = np.zeros(n_cap, dtype=np.float64)
        srpt[:n] = batch.srpt
        st.srpt_dev = jnp.asarray(srpt)
        _bstat(impl, "bytes_h2d", srpt.nbytes)
    if rebuild or not same[4]:
        loc = np.full(n_cap, -1, dtype=np.int32)
        loc[:n] = batch.loc
        st.loc_dev = jnp.asarray(loc)
        _bstat(impl, "bytes_h2d", loc.nbytes)
    st.n_cap = n_cap
    st.cols = cols
    return True


def _sync_ledgers(st: DeviceWaveState, matcher, impl: str) -> np.ndarray:
    """EMA pair + dense deficit/share mirrors (key order = dict order).

    Returns the group-id → dense-index lookup table.  Steady state, the
    post-replay shadows match the host exactly and nothing uploads.
    """
    from . import kernels as K

    keys = list(matcher.deficits.deficit.keys())
    g_cap = max(K.pad8(len(keys)), 8)
    dfc = matcher.deficits
    if keys != st.keys or g_cap != st.g_cap:
        st.keys = keys
        st.g_cap = g_cap
        share = np.zeros(g_cap, dtype=np.float64)
        share[:len(keys)] = [dfc.share[g] for g in keys]
        st.share_dev = jnp.asarray(share)
        st.deficit_shadow = None
        _bstat(impl, "bytes_h2d", share.nbytes)
        lut_len = (max(keys) + 1) if keys else 1
        st.gmap_lut = np.full(lut_len, -1, dtype=np.int64)
        for i, g in enumerate(keys):
            st.gmap_lut[g] = i
        st.cols = None                 # gidx depends on the key order
    deficit = np.full(g_cap, -np.inf, dtype=np.float64)
    deficit[:len(keys)] = [dfc.deficit[g] for g in keys]
    if st.deficit_shadow is None \
            or not np.array_equal(st.deficit_shadow, deficit):
        st.deficit_dev = jnp.asarray(deficit)
        st.deficit_shadow = deficit
        _bstat(impl, "bytes_h2d", deficit.nbytes)
    ema = np.array([matcher._ema_score, matcher._ema_srpt],
                   dtype=np.float64)
    if st.ema_shadow is None or not np.array_equal(st.ema_shadow, ema):
        st.ema_dev = jnp.asarray(ema)
        st.ema_shadow = ema
        _bstat(impl, "bytes_h2d", ema.nbytes)
    return st.gmap_lut


def _device_wave(ctx: WaveContext, impl: str) -> int:
    """Shared xla/pallas driver: sync mirrors, launch, replay the picks."""
    from ..online import drf_fairness, slot_fairness

    sm, avail, alive, batch = ctx.sm, ctx.avail, ctx.alive, ctx.batch
    matcher = sm.matcher
    cfg = sm.cfg
    if cfg.fairness is drf_fairness:
        drf = True
    elif cfg.fairness is slot_fairness:
        drf = False
    else:                              # unportable fairness fn: host loop
        return match_wave_numpy(ctx)
    n = len(batch)
    m, d = avail.shape
    st = getattr(sm, "_wave_state", None)
    if st is None:
        st = sm._wave_state = DeviceWaveState()
    with jax.enable_x64(True):
        gmap_lut = _sync_ledgers(st, matcher, impl)
        if not _sync_batch(st, batch, gmap_lut, impl):
            return match_wave_numpy(ctx)
        wave_fns, upd_fns = _caches()
        launches = 1 + _sync_avail(st, avail, impl, upd_fns)
        # host-computed visit order (argsort is a host-only sort); the
        # alive/drained prefilter mirrors the numpy wave, machines it
        # would *skip* via eligibility are decision-free in-kernel visits
        order = np.argsort(-avail.sum(axis=1))
        keep = alive[order] & (avail[order] > 1e-9).any(axis=1)
        order_p = np.full(m, -1, dtype=np.int32)
        kept = order[keep]
        order_p[:len(kept)] = kept
        fd, rigid, fung = matcher.fit_dim_split()
        masks = []
        for dims in (fd, rigid, fung):
            mk = np.zeros(d, dtype=bool)
            mk[np.asarray(dims, dtype=np.int64)] = True
            masks.append(jnp.asarray(mk))
        consts = np.zeros(8, dtype=np.float64)
        consts[_C_EPS] = packing.EPS
        consts[_C_SLACK] = cfg.max_overbook - 1.0
        consts[_C_RP] = cfg.remote_penalty
        consts[_C_ETA_M] = cfg.eta_m
        consts[_C_THRESH] = matcher.deficits.kappa \
            * matcher.deficits.capacity
        consts[_C_A] = _EMA_A
        consts[_C_1MA] = _EMA_1MA
        consts[_C_FLOOR] = _SRPT_FLOOR
        key = (m, d, st.n_cap, st.g_cap, cfg.bundle_limit,
               bool(cfg.use_packing), bool(cfg.use_srpt),
               bool(cfg.use_overbooking), drf)
        fns = _pallas_cache() if impl == "pallas" else wave_fns
        fn = fns.get(key)
        pri_dev = st.pri_dev if cfg.use_priority else \
            jnp.asarray(np.concatenate([np.ones(n), np.zeros(st.n_cap - n)]))
        out = fn(st.avail_dev, jnp.asarray(order_p), st.dem_dev, pri_dev,
                 st.srpt_dev, st.gidx_dev, st.loc_dev, st.taken0_dev,
                 st.ema_dev, st.deficit_dev, st.share_dev, *masks,
                 jnp.asarray(consts))
        st.avail_dev, st.ema_dev, st.deficit_dev = out[0], out[1], out[2]
        rows = np.asarray(out[3])
        mach = np.asarray(out[4])
        overf = np.asarray(out[5])
        obs = np.asarray(out[6])
        count = int(out[7])
    _bstat(impl, "bytes_h2d",
           order_p.nbytes + consts.nbytes + 3 * d)
    _bstat(impl, "bytes_d2h",
           rows.nbytes + mach.nbytes + overf.nbytes + obs.nbytes + 4)
    _bstat(impl, "launches", launches)
    _bstat(impl, "waves", 1)
    # -- host replay: apply every pick's side effects in pick order ------
    plan = sm.plan
    fairness = cfg.fairness
    for j in range(count):
        gi = int(rows[j])
        mm = int(mach[j])
        ctx.start_cb(gi, mm)
        matcher._observe(float(obs[j]), float(batch.srpt[gi]))
        w = fairness(batch.dem[gi])
        matcher.deficits.allocated(int(batch.grp[gi]), w)
        sm.shard_matchers[plan.shard_of(mm)].deficits.allocated(
            int(batch.grp[gi]), w)
    # refresh shadows from the replayed host state: the kernel applied
    # identical float64 ops, so these equal the device buffers bit-for-bit
    # and the next wave's diffs only see *external* mutations
    st.avail_shadow = avail.copy()
    st.ema_shadow = np.array([matcher._ema_score, matcher._ema_srpt],
                             dtype=np.float64)
    dfc = matcher.deficits
    sh = np.full(st.g_cap, -np.inf, dtype=np.float64)
    sh[:len(st.keys)] = [dfc.deficit[g] for g in st.keys]
    st.deficit_shadow = sh
    return count


def match_wave_xla(ctx: WaveContext) -> int:
    return _device_wave(ctx, "xla")


def match_wave_pallas(ctx: WaveContext) -> int:
    return _device_wave(ctx, "pallas")
