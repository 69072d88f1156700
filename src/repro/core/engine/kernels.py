"""Kernel-dispatch layer: one registry for the engine's array kernels.

Every tensor-shaped inner loop of the scheduler — the windowed feasibility
scan the placement backends run, and the fit/score/heartbeat kernels the
online layers run — is registered here as an *op* with up to three
implementations:

  numpy   — the reference implementation; always available, always exact.
  xla     — a jax.jit-compiled version (shape-bucketed, float32 compute).
  pallas  — the ``src/repro/kernels/placement_scan`` Pallas kernels
            (TPU target; interpret mode elsewhere).

Selection is per-op: ``REPRO_KERNELS="scan=xla,machines_with_candidates=
pallas"`` (or ``all=<impl>``) pins an implementation; a pinned impl that
is unavailable (no jax, no pallas, wrong platform) raises instead of
quietly running something else.  Only an injected ``kernel_impl`` fault
(`core/faults.py`) demotes an impl, after which resolution walks down the
chain pallas -> xla -> numpy; a genuine exception from an impl
propagates.  ``active()`` reports what actually runs; ``PROFILE``
accumulates per-(op, impl) call counts and seconds so benchmarks can
attribute time to the kernel layer.  ``span`` (and ``clock``/``count``
for stretches too frequent for a trace event) adds the program's own
spans to the same table, on only while a ``jax.profiler`` trace records.

Exactness contract per op (docs/architecture.md "Kernel layer"):

  * ``scan`` — all implementations are bit-identical: the grid is float32
    and demands are pre-rounded with ``ceil32``, so feasibility is a pure
    float32 comparison plus integer run-length counting on every path.
  * ``fits_mask`` / ``pack_score`` / ``heartbeat_masks`` — the numpy
    implementations are the decision oracles (float64).  Accelerated
    variants compute in float32 and are therefore NOT bit-exact; they are
    only offered where a sound approximation cannot change a decision.
  * ``machines_with_candidates`` — decision-exact under every
    implementation: its masks are used exclusively to *skip* machines
    that provably cannot pick a task, so any sound superset of the exact
    eligibility yields bit-identical scheduling decisions (a falsely
    eligible machine runs the matcher and picks nothing, mutating no
    state).  The accelerated implementations compute supersets by
    directed rounding: demands rounded *down* to float32, thresholds
    ``avail + slack + eps`` rounded *up*, so no exact-eligible pair is
    ever dropped.
  * ``match_wave`` — a whole heartbeat wave (eligibility → score → pick
    bundling → avail update) as one op over a `wave.WaveContext`.
    Bit-identical where float64 is native (XLA:CPU): the xla/pallas
    kernels run float64 with FMA-contraction laundering so each pick,
    overbook flag, EMA observation and deficit update reproduces the
    numpy wave loop exactly (see ``engine/wave.py``).  A TPU emulates
    float64 and does not reproduce it, so auto-selection keeps the wave
    on numpy there (``TPU_WAVE_REASON``).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Sequence

import numpy as np

from .. import faults
from ..space import runs_of_k
from . import packing
from .base import ceil32

#: env var: comma-separated op=impl pairs, e.g. "scan=xla,all=numpy"
KERNELS_ENV = "REPRO_KERNELS"

OPS = ("scan", "fits_mask", "pack_score", "heartbeat_masks",
       "machines_with_candidates", "match_wave")
#: ops whose non-numpy implementations are approximate in ways that are
#: only safe for specific consumers (see the exactness contract above):
#: ``all=<impl>`` deliberately skips these — accelerating them requires
#: an explicit per-op opt-in, e.g. ``REPRO_KERNELS=heartbeat_masks=xla``
EXPLICIT_ONLY = ("fits_mask", "pack_score", "heartbeat_masks")
IMPLS = ("pallas", "xla", "numpy")   # fallback order, strongest first

#: heartbeat-sized ops that auto-promote to the device once the machine
#: axis is large enough to amortize launch overhead.  The two eligibility
#: ops are machine-skip filters (sound supersets are decision-exact for
#: every consumer in the repo); ``match_wave`` is bit-exact outright.
#: Above ``heartbeat_device_min_m()`` machines they auto-select xla; an
#: explicit REPRO_KERNELS pin for the op always wins.  Note the
#: heartbeat_masks caveat still applies: the auto-selected xla impl is
#: sound only for ``fits | over`` union consumers.
HEARTBEAT_AUTO_OPS = ("heartbeat_masks", "machines_with_candidates",
                      "match_wave")
#: env var overriding the auto-promotion threshold (int, machine count)
HEARTBEAT_MIN_M_ENV = "REPRO_HEARTBEAT_DEVICE_MIN_M"
_HEARTBEAT_DEFAULT_MIN_M = 1536


#: why auto-selection keeps ``match_wave`` on numpy on a TPU (measured
#: on a v5e by ``chip_probe.py``); an explicit REPRO_KERNELS pin still wins
TPU_WAVE_REASON = (
    "TPU float64 is emulated and not bit-exact: the device's EMA "
    "observation differed from the numpy wave's in the last bit (picks "
    "equal) on the wave parity corpus and at 2048 machines, and a "
    "2048-machine wave took 0.17-0.22 s against 4-10 ms for the numpy "
    "wave; the numpy wave runs, with machines_with_candidates on the "
    "device")


def heartbeat_device_min_m() -> int:
    """Machine count at/above which heartbeat ops auto-select xla."""
    raw = os.environ.get(HEARTBEAT_MIN_M_ENV, "")
    if raw:
        return int(raw)
    return _HEARTBEAT_DEFAULT_MIN_M

#: per-(op, impl) dispatch accounting: {"op.impl": [calls, seconds]}
PROFILE: dict[str, list] = {}

#: one lock for every shared accounting structure in this module (PROFILE,
#: XLA_STATS): concurrent builds (core/buildsvc.py thread mode) dispatch
#: kernels from worker threads, and unlocked ``+=`` drops increments.
#: Kernel calls are tensor-sized, so the lock is noise next to the work.
_STATS_LOCK = threading.Lock()


def reset_profile() -> None:
    with _STATS_LOCK:
        PROFILE.clear()


def profile_snapshot() -> dict[str, tuple[int, float]]:
    with _STATS_LOCK:
        return {k: (int(v[0]), float(v[1])) for k, v in PROFILE.items()}


def stat_add(key: str, n: int = 1) -> None:
    """Atomically bump one XLA_STATS counter (shared with core/engine/jit)."""
    with _STATS_LOCK:
        XLA_STATS[key] += n


def transfer_add(key: str, n: int) -> None:
    """Accumulate a host<->device transfer/launch counter in PROFILE.

    Keys follow ``"{op}.{impl}.{launches|bytes_h2d|bytes_d2h|waves}"``;
    the count lands in the calls slot of the usual PROFILE pair (seconds
    stays 0.0), so ``profile_snapshot`` deltas work unchanged and bench
    rows can derive per-wave launch/byte figures.
    """
    with _STATS_LOCK:
        slot = PROFILE.get(key)
        if slot is None:
            slot = PROFILE[key] = [0, 0.0]
        slot[0] += n


def _profile_add(key: str, seconds: float) -> None:
    """Count one call of ``seconds`` under ``PROFILE[key]``."""
    with _STATS_LOCK:
        slot = PROFILE.get(key)
        if slot is None:
            slot = PROFILE[key] = [0, 0.0]
        slot[0] += 1
        slot[1] += seconds


#: sticky runtime demotions: op -> impls hit by an injected fault at
#: dispatch, excluded from resolution until `reset_demotions` (degraded
#: mode — decision-exact because resolution lands on the numpy oracle,
#: which defines correct output for every op).  numpy is never demoted.
_DEMOTED: dict[str, set] = {}


def demote(op: str, impl: str) -> None:
    """Sticky-demote one (op, impl) after an injected dispatch fault.

    Counted in PROFILE under ``"{op}.{impl}.demoted"`` (calls slot; the
    seconds slot stays 0.0) so bench rows and `SimResult.fault_stats`
    can report demotion events alongside normal dispatch accounting.
    """
    if impl == "numpy":
        raise ValueError("the numpy oracle cannot be demoted")
    with _STATS_LOCK:
        _DEMOTED.setdefault(op, set()).add(impl)
        key = f"{op}.{impl}.demoted"
        slot = PROFILE.get(key)
        if slot is None:
            slot = PROFILE[key] = [0, 0.0]
        slot[0] += 1


def demoted_impls(op: str) -> frozenset:
    with _STATS_LOCK:
        return frozenset(_DEMOTED.get(op, ()))


def demotions_snapshot() -> dict[str, int]:
    """{"op.impl": demotion events} — the delta-able fault_stats view."""
    with _STATS_LOCK:
        return {k: int(v[0]) for k, v in PROFILE.items()
                if k.endswith(".demoted")}


def reset_demotions() -> None:
    """Re-admit every demoted impl (tests / operator re-enable)."""
    with _STATS_LOCK:
        _DEMOTED.clear()


# ----------------------------------------------------------------------
# numpy implementations (the reference semantics)
# ----------------------------------------------------------------------

def scan_starts(
    avail: np.ndarray,
    Vs: np.ndarray,
    ks: np.ndarray,
    plo: int,
    phi: int,
    reverse: bool = False,
) -> np.ndarray:
    """Feasible-start bitmaps for a batch of tasks over one window.

    For each task g (demand ``Vs[g]``, duration ``ks[g]`` ticks) and each
    physical start t in [plo, phi), bit (g, t, machine) says whether the
    whole run [t, t + ks[g]) fits on that machine inside the grid.

    Returns bool (g, (phi - plo) * m): rows are flattened over
    (start, machine) with starts ascending, or descending when
    ``reverse`` (the backward-pass walk order).
    """
    m, T, _d = avail.shape
    g = len(ks)
    W = phi - plo
    kmax = int(ks.max())
    hi_read = min(T, phi + kmax - 1)
    win = avail[:, plo:hi_read, :]                              # (m, L, d)
    L = hi_read - plo
    if g == 1:  # window extensions: skip the batched gather machinery
        k = int(ks[0])
        ok = (win >= Vs[0]).all(axis=2)                         # (m, L)
        good = runs_of_k(ok, k)
        full = np.zeros((W, m), dtype=bool)
        n = min(W, good.shape[1])
        full[:n] = good[:, :n].T
        if reverse:
            full = full[::-1]
        return np.ascontiguousarray(full).reshape(1, W * m)
    ok = (win[None, :, :, :] >= Vs[:, None, None, :]).all(axis=3)  # (g, m, L)
    if (ks == ks[0]).all():
        # stage peers usually share one duration: the per-task gather
        # degenerates to a single slice subtraction over the cumsums
        k0 = int(ks[0])
        good = np.zeros((g, m, W), dtype=bool)
        runs = runs_of_k(ok.reshape(g * m, L), k0).reshape(g, m, -1)
        n = min(W, runs.shape[2])
        good[:, :, :n] = runs[:, :, :n]
    else:
        cz = np.zeros((g, m, L + 1), dtype=np.int32)
        np.cumsum(ok, axis=2, out=cz[:, :, 1:])
        ends = np.minimum(np.arange(W, dtype=np.int64)[None, :] + ks[:, None], L)
        idx = np.broadcast_to(ends[:, None, :], (g, m, W))
        run = np.take_along_axis(cz, idx, axis=2) - cz[:, :, :W]
        # a run truncated by the grid edge counts < k and is correctly excluded
        good = run == ks[:, None, None]                         # (g, m, W)
    good = np.ascontiguousarray(np.swapaxes(good, 1, 2))        # (g, W, m)
    if reverse:
        good = good[:, ::-1, :]
    return good.reshape(g, W * m)


# ----------------------------------------------------------------------
# xla implementations
# ----------------------------------------------------------------------

try:  # the numpy paths must work without jax
    import jax
    import jax.numpy as jnp
    from jax import lax

    _HAVE_JAX = True
except ImportError:  # pragma: no cover - exercised only on jax-less installs
    jax = jnp = lax = None
    _HAVE_JAX = False


def have_jax() -> bool:
    return _HAVE_JAX


def _have_pallas() -> bool:
    if not _HAVE_JAX:
        return False
    try:
        from ...kernels import placement_scan  # noqa: F401
        return True
    except ImportError:  # pragma: no cover
        return False


def bucket(n: int, floor: int = 64) -> int:
    """Smallest size >= n on the {64, 96, 128, 192, 256, ...} ladder.

    A 1.5x-stepped power-of-two ladder: coarse enough that kernels
    retrace a handful of times per process, tight enough that padded
    compute stays within ~50% of the true shape.
    """
    if n <= floor:
        return floor
    p = floor
    while True:
        if n <= p:
            return p
        if n <= p + p // 2:
            return p + p // 2
        p *= 2


def pad8(n: int) -> int:
    return ((n + 7) // 8) * 8


#: durations above this never reach the bitmap scans (the sessions answer
#: them with chunked live probes — see core/engine/batched.py); the scan
#: buckets below lean on it to pin the window-read length per W bucket
LONG_K = 128
#: the tighter of the two window-read allowances (see scan_buckets)
SHORT_K = 32

#: window-length buckets for the compiled scans; WINDOW0 (192) dominates
W_LADDER = (192, 256, 512, 1024, 2048)


def scan_buckets(g: int, W: int, kmax: int) -> tuple[int, int, int]:
    """(gb, Lb, Wb) compile buckets for one scan shape.

    Deliberately coarse: gb in multiples of 8, Wb on a short ladder, and
    Lb = Wb plus a two-step duration allowance — SHORT_K covers the
    common case (most stage durations quantize to a few ticks), LONG_K
    the bitmap-path ceiling — a handful of distinct keys per (m, d)
    pair, so the scan's compile cache holds every one, while the window
    read (the per-launch compute) stays ~30% leaner on typical batches.
    """
    gb = pad8(g)
    for Wb in W_LADDER:
        if W <= Wb:
            break
    else:
        Wb = bucket(W)
    if kmax <= SHORT_K:
        Lb = Wb + SHORT_K
    elif kmax <= LONG_K:
        Lb = Wb + LONG_K
    else:
        Lb = bucket(Wb + kmax)
    return gb, Lb, Wb


#: entry cap of the LRU bucket caches (eligibility, wave); above it the
#: least-recently-used bucket is dropped and its recompilation counted.
#: The scan and slab-writer caches take no cap (``cap=None``): their keys
#: all sit on the program's own ladders, which bound them
BUCKET_CAP = int(os.environ.get("REPRO_JIT_BUCKET_CAP", "32"))

#: builds ("compiles") and LRU drops ("evictions") of the bucket caches
XLA_STATS = {"compiles": 0, "evictions": 0}


class _BucketCache:
    """LRU of jitted functions keyed by static shape buckets, bounded at
    ``cap`` entries; ``cap=None`` keeps every function it builds.

    Thread-safe: concurrent build sessions share these caches, so the
    pop/build/reinsert sequence runs under one per-cache lock (unlocked,
    two racing gets could both build — double-counting compiles — or
    corrupt the dict).  Holding the lock across ``build`` also means one
    key compiles once, with late arrivals waiting on the winner.

    ``name`` names the cache's ``xla.load.<name>`` span: on a miss while
    a trace records, the new function's first call (trace, then compile
    or load from the persistent cache) runs inside it.
    """

    def __init__(self, build: Callable, name: str = "bucket",
                 cap: int | None = BUCKET_CAP):
        self._build = build
        self._span_key = f"xla.load.{name}"
        self._cap = cap
        self._fns: dict[tuple, Callable] = {}
        self._lock = threading.Lock()

    def get(self, key: tuple) -> Callable:
        with self._lock:
            fn = self._fns.pop(key, None)
            if fn is None:
                if self._cap is not None and len(self._fns) >= self._cap:
                    self._fns.pop(next(iter(self._fns)))
                    stat_add("evictions")
                stat_add("compiles")
                fn = self._build(*key)
                if recording():
                    fn = self._loading(key, fn)
            self._fns[key] = fn      # (re)append = most recently used
            return fn

    def _loading(self, key: tuple, fn: Callable) -> Callable:
        """``fn`` behind a wrapper whose first call runs in the load span
        and then puts ``fn`` itself back under ``key``."""
        pending = [fn]

        def first_call(*args, **kwargs):
            try:
                pending.pop()            # atomic: one caller takes the span
            except IndexError:
                return fn(*args, **kwargs)
            try:
                with span(self._span_key):
                    return fn(*args, **kwargs)
            finally:
                with self._lock:
                    if self._fns.get(key) is first_call:
                        self._fns[key] = fn

        return first_call

    def __len__(self) -> int:
        return len(self._fns)


def _build_scan_fn(m: int, d: int, gb: int, Lb: int, Wb: int, Tb: int):
    """One (m, d, gb, Lb, Wb, Tb) bucket of the windowed feasibility scan.

    Slices a (m, Lb, d) window out of a (m, Tb, d) device grid at a
    dynamic start — in-trace, so the launch stays a single asynchronous
    dispatch (an eager host-side slice would synchronize with in-flight
    work and serialize the async session).  The grid length Tb is part of
    the trace signature on purpose: it sits on the coarse allocation
    ladder and in the cache key, so those compiles are counted like every
    other bucket.  Ticks at/after ``tlive`` (window-relative) are masked
    infeasible, which reproduces the numpy kernel's grid-edge truncation
    bit-for-bit.

    Run detection is *erosion by doubling* instead of the numpy kernel's
    cumsum+gather: E_{2^j}[t] = "ok for 2^j consecutive ticks from t" is
    built by log2(kmax) shifted ANDs, and each row's run grows by the
    ladder levels of its set bits, all ok in [t, t + 2^j + r) being
    E_{2^j}[t] and "all ok in [t + 2^j, t + 2^j + r)".  Every shift is
    static, and a row's bits only select: a per-row dynamic slice (a
    gather) in its place compiles to 20-30x the TPU code, megabytes a
    program.  Pure boolean shifts lower much better on CPU/TPU than a
    length-L integer scan plus a (g, m, W) gather, and the result is
    identical: a start is good iff all k ticks are ok.  All comparisons
    are float32-vs-float32 (demands pre-rounded via ``ceil32``), so the
    bitmaps are bit-identical to the numpy kernel.
    """
    nbits = max(LONG_K.bit_length(), (Lb - Wb).bit_length())

    def scan(buf, start, tlive, Vs, ks):
        win = lax.dynamic_slice(buf, (0, start, 0), (m, Lb, d))
        ok = (win[None, :, :, :] >= Vs[:, None, None, :]).all(axis=3)
        idx = jnp.arange(Lb, dtype=jnp.int32)
        ok = ok & (idx < tlive)[None, None, :]           # (gb, m, Lb)

        def ahead(x, s, fill):
            """x[:, :, t + s], reading the row's ``fill`` past the end."""
            tail = jnp.broadcast_to(fill, (gb, m, min(s, Lb)))
            return jnp.concatenate([x[:, :, s:], tail], axis=2)

        # erosion ladder: E[j][t] == all ok in [t, t + 2^j)
        never = jnp.zeros((gb, 1, 1), dtype=bool)
        E = [ok]
        for j in range(nbits - 1):
            E.append(E[-1] & ahead(E[-1], 1 << j, never))
        # run[t] == all ok in [t, t + r), r the bits of k taken so far; an
        # empty run (r == 0) holds past the window's end too
        run = jnp.ones((gb, m, Lb), dtype=bool)
        empty = jnp.ones((gb, 1, 1), dtype=bool)
        for j in range(nbits):
            bit = ((ks >> j) & 1).astype(bool)[:, None, None]
            run = jnp.where(bit, E[j] & ahead(run, 1 << j, empty), run)
            empty = empty & ~bit
        return jnp.swapaxes(run[:, :, :Wb], 1, 2)        # (gb, Wb, m)

    return jax.jit(scan)


# eagerly constructed (cheap — jitting happens per key inside get): a lazy
# ``global X; if X is None`` init is a check-then-act race under threads.
# Uncapped: every key comes from pad8, W_LADDER, SHORT_K/LONG_K and the
# device grid's ``alloc_len`` ladder, so the stage widths and grid spans a
# process meets bound the cache, not a count: a few hundred programs per
# (m, d), each 0.1-0.8 MB of code loaded on the device (a v5e compile).
# An LRU cap below the keys a build stream reaches only traces and
# reloads them again
_SCAN_FNS = _BucketCache(_build_scan_fn, "scan", cap=None)


def scan_fn_for(m: int, d: int, gb: int, Lb: int, Wb: int,
                Tb: int) -> Callable:
    """Compiled scan for one shape bucket (shared with the jit backend)."""
    return _SCAN_FNS.get((m, d, gb, Lb, Wb, Tb))


def _scan_xla(avail, Vs, ks, plo, phi, reverse=False):
    """Stateless XLA scan: uploads the window per call (the jit backend's
    device-resident session avoids the upload; this entry point is the
    registry implementation used for parity testing and ad-hoc routing)."""
    m, T, d = avail.shape
    g = len(ks)
    W = phi - plo
    kmax = int(ks.max())
    hi_read = min(T, phi + kmax - 1)
    L = hi_read - plo
    gb, Lb, Wb = scan_buckets(g, W, kmax)
    win_p = np.full((m, Lb, d), -1.0, dtype=np.float32)
    win_p[:, :L, :] = avail[:, plo:hi_read, :]
    Vs_p = np.full((gb, d), 2.0, dtype=np.float32)
    Vs_p[:g] = ceil32(np.asarray(Vs))
    ks_p = np.ones(gb, dtype=np.int32)
    ks_p[:g] = ks
    fn = scan_fn_for(m, d, gb, Lb, Wb, Lb)   # buffer == window here
    good = np.asarray(fn(jnp.asarray(win_p), np.int32(0), np.int32(L),
                         Vs_p, ks_p))
    good = good[:g, :W, :]
    if reverse:
        good = good[:, ::-1, :]
    return np.ascontiguousarray(good).reshape(g, W * m)


# -- heartbeat ops: sound-superset float32 formulation -------------------

def _round_down32(x: np.ndarray) -> np.ndarray:
    """Largest float32 <= x (directed rounding for sound supersets)."""
    x32 = x.astype(np.float32)
    high = x32.astype(np.float64) > x
    if high.any():
        x32[high] = np.nextafter(x32[high], np.float32(-np.inf))
    return x32


def _round_up32(x: np.ndarray) -> np.ndarray:
    """Smallest float32 >= x."""
    x32 = x.astype(np.float32)
    low = x32.astype(np.float64) < x
    if low.any():
        x32[low] = np.nextafter(x32[low], np.float32(np.inf))
    return x32


def _superset_operands(avail, demands, fit_dims, rigid_dims, fungible_dims,
                       overbook_slack, eps=packing.EPS):
    """Host-side exact prep for the accelerated eligibility kernels.

    The exact test per (candidate, machine, dim) is
    ``demand <= avail + slack + eps`` in float64.  Rounding the demand
    *down* and the float64 threshold *up* to float32 can only turn False
    into True — a sound superset, which is all the skip-only consumers
    need.  The (m, d)/(n, d) rounding runs on host (cheap); only the
    (n, m) outer comparison runs on the accelerator.
    """
    avail = np.atleast_2d(np.asarray(avail, dtype=np.float64))
    demands = np.atleast_2d(np.asarray(demands, dtype=np.float64))
    d = avail.shape[1]
    dem32 = _round_down32(demands)
    thr_fit = _round_up32(avail + eps)
    thr_fung = _round_up32(avail + max(overbook_slack, 0.0) + eps)

    def sel(dims):
        dims = np.asarray(dims, dtype=np.int64)
        return dims if len(dims) else np.empty(0, np.int64)

    return dem32, thr_fit, thr_fung, sel(fit_dims), sel(rigid_dims), \
        sel(fungible_dims)


def _eligible_superset_np(dem32, thr_fit, thr_fung, fd, rd, gd):
    """Reference formulation of the superset masks (used by tests)."""
    def fit(thr, dims):
        if len(dims) == 0:
            return np.ones((dem32.shape[0], thr.shape[0]), dtype=bool)
        return (dem32[:, None, dims] <= thr[None, :, dims]).all(axis=2)

    eligible = fit(thr_fit, fd) | (fit(thr_fit, rd) & fit(thr_fung, gd))
    return eligible, eligible.any(axis=0)


def _build_elig_fn(n_dims_key):
    def elig(dem32, thr_fit, thr_fung, fd_mask, rd_mask, gd_mask):
        # dims enter as (d,) float32 {0, 1} masks: a masked-out dim
        # compares against +inf and never fails the fit
        inf = jnp.float32(np.inf)
        tf = jnp.where(fd_mask > 0, thr_fit[None, :, :], inf)
        tr = jnp.where(rd_mask > 0, thr_fit[None, :, :], inf)
        tg = jnp.where(gd_mask > 0, thr_fung[None, :, :], inf)
        dm = dem32[:, None, :]
        fits = (dm <= tf).all(axis=2)
        rigid = (dm <= tr).all(axis=2)
        fung = (dm <= tg).all(axis=2)
        eligible = fits | (rigid & fung)
        return eligible, eligible.any(axis=0)
    return jax.jit(elig)


_ELIG_FNS = _BucketCache(_build_elig_fn, "elig")


def _eligibility_launch_args(avail, demands, fit_dims, rigid_dims,
                             fungible_dims, overbook_slack, use_overbooking):
    """Shared preamble of the accelerated eligibility ops.

    Returns ``(dem32, thr_fit, thr_fung, masks)`` ready for either launch
    path, or the ``(eligible, machine_any)`` empty-result shortcut when
    there are no candidates.  One site on purpose: the xla and pallas
    implementations must degenerate and encode dims identically or their
    decisions could drift apart.
    """
    if not use_overbooking:
        # no overbooking: eligibility is the plain fit mask; reuse the
        # fit threshold for both halves so the kernel stays one shape
        rigid_dims = fit_dims
        fungible_dims = np.empty(0, np.int64)
        overbook_slack = 0.0
    dem32, thr_fit, thr_fung, fd, rd, gd = _superset_operands(
        avail, demands, fit_dims, rigid_dims, fungible_dims, overbook_slack)
    n, d = dem32.shape
    if n == 0:
        m = thr_fit.shape[0]
        return None, (np.zeros((n, m), dtype=bool), np.zeros(m, dtype=bool))
    masks = []
    for dims in (fd, rd, gd):
        mk = np.zeros(d, dtype=np.float32)
        mk[dims] = 1.0
        masks.append(mk)
    return (dem32, thr_fit, thr_fung, masks), None


def _machines_with_candidates_xla(avail, demands, fit_dims, rigid_dims,
                                  fungible_dims, overbook_slack=0.0,
                                  use_overbooking=True):
    """Sound-superset eligibility in one device launch (see module doc)."""
    args, empty = _eligibility_launch_args(avail, demands, fit_dims,
                                           rigid_dims, fungible_dims,
                                           overbook_slack, use_overbooking)
    if empty is not None:
        return empty
    dem32, thr_fit, thr_fung, masks = args
    fn = _ELIG_FNS.get((dem32.shape[1],))
    transfer_add("machines_with_candidates.xla.launches", 1)
    transfer_add("machines_with_candidates.xla.bytes_h2d",
                 dem32.nbytes + thr_fit.nbytes + thr_fung.nbytes
                 + sum(mk.nbytes for mk in masks))
    eligible, any_m = fn(dem32, thr_fit, thr_fung, *masks)
    with span("machines_with_candidates.xla.sync"):   # blocks on the device
        eligible, any_m = np.asarray(eligible), np.asarray(any_m)
    transfer_add("machines_with_candidates.xla.bytes_d2h",
                 eligible.nbytes + any_m.nbytes)
    return eligible, any_m


def _heartbeat_masks_xla(avail, demands, fit_dims, rigid_dims, fungible_dims,
                         overbook_slack=0.0, use_overbooking=True):
    """Superset (fits, over) masks; see machines_with_candidates caveats.

    NOTE: ``over`` is derived from the superset ``fits`` via negation, so
    unlike the union mask it is *neither* a superset nor a subset of the
    exact mask — this implementation is only safe for consumers that use
    ``fits | over``.  The dispatch default therefore stays numpy.
    """
    args, empty = _eligibility_launch_args(avail, demands, fit_dims,
                                           rigid_dims, fungible_dims,
                                           overbook_slack, use_overbooking)
    if empty is not None:
        # this op's contract is (fits (n, m), over (n, m)) — not the
        # (eligible, machine_any (m,)) pair of machines_with_candidates
        return empty[0], np.zeros_like(empty[0])
    dem32, thr_fit, thr_fung, masks = args
    fn = _ELIG_FNS.get((dem32.shape[1],))
    eligible, _any = fn(dem32, thr_fit, thr_fung, *masks)
    eligible = np.asarray(eligible)
    fd_mask = masks[0] > 0
    fits = (dem32[:, None, fd_mask] <= thr_fit[None, :, fd_mask]).all(axis=2) \
        if fd_mask.any() else np.ones_like(eligible)
    return fits, eligible & ~fits


def _fits_mask_xla(avail, demand, dims=None, slack=0.0, eps=packing.EPS):
    """float32 fit mask (superset by directed rounding); not bit-exact."""
    avail = np.asarray(avail, dtype=np.float64)
    demand = np.asarray(demand, dtype=np.float64)
    if dims is not None:
        dims = np.asarray(dims, dtype=np.int64)
        if len(dims) == 0:
            if avail.ndim == 2 and demand.ndim == 2:
                return np.ones((demand.shape[0], avail.shape[0]), dtype=bool)
            shape = np.broadcast_shapes(avail.shape[:-1], demand.shape[:-1])
            return np.ones(shape, dtype=bool)
        avail = avail[..., dims]
        demand = demand[..., dims]
    thr = jnp.asarray(_round_up32(avail + slack + eps))
    dem = jnp.asarray(_round_down32(demand))
    if avail.ndim == 2 and demand.ndim == 2:
        out = (dem[:, None, :] <= thr[None, :, :]).all(axis=2)
    else:
        out = (dem <= thr).all(axis=-1)
    return np.asarray(out)


def _pack_score_xla(avail, demand, clip=False):
    """float32 Tetris dot-product scores; NOT bit-exact vs numpy float64."""
    avail = jnp.asarray(np.asarray(avail), dtype=jnp.float32)
    if clip:
        avail = jnp.clip(avail, 0.0, None)
    demand = jnp.asarray(np.asarray(demand), dtype=jnp.float32)
    if avail.ndim == 2 and demand.ndim == 2:
        return np.asarray(demand @ avail.T, dtype=np.float64)
    out = demand @ jnp.swapaxes(jnp.atleast_2d(avail), -1, -2)
    return np.asarray(out.squeeze(), dtype=np.float64)


# ----------------------------------------------------------------------
# pallas implementations (adapters over src/repro/kernels/placement_scan)
# ----------------------------------------------------------------------

def _scan_pallas(avail, Vs, ks, plo, phi, reverse=False):
    from ...kernels.placement_scan import ops as ps_ops

    m, T, d = avail.shape
    g = len(ks)
    W = phi - plo
    kmax = int(ks.max())
    hi_read = min(T, phi + kmax - 1)
    L = hi_read - plo
    # the kernel's dynamic k-slice needs L_pad >= W_pad + kmax; ticks
    # beyond t_live are masked infeasible so the padding never flips a bit
    Wb = bucket(W)
    Lp = bucket(max(Wb + kmax, L))
    gb = pad8(g)
    win_p = np.full((m, Lp, d), -1.0, dtype=np.float32)
    win_p[:, :L, :] = avail[:, plo:hi_read, :]
    Vs_p = np.full((gb, d), 2.0, dtype=np.float32)
    Vs_p[:g] = ceil32(np.asarray(Vs))
    ks_p = np.ones(gb, dtype=np.int32)
    ks_p[:g] = ks
    good = np.asarray(ps_ops.scan_bitmaps(win_p, Vs_p, ks_p, np.int32(L),
                                          W=Wb)) != 0
    good = good[:g, :W, :]
    if reverse:
        good = good[:, ::-1, :]
    return np.ascontiguousarray(good).reshape(g, W * m)


def _machines_with_candidates_pallas(avail, demands, fit_dims, rigid_dims,
                                     fungible_dims, overbook_slack=0.0,
                                     use_overbooking=True):
    from ...kernels.placement_scan import ops as ps_ops

    args, empty = _eligibility_launch_args(avail, demands, fit_dims,
                                           rigid_dims, fungible_dims,
                                           overbook_slack, use_overbooking)
    if empty is not None:
        return empty
    dem32, thr_fit, thr_fung, masks = args
    eligible = np.asarray(ps_ops.heartbeat_eligible(
        dem32, thr_fit, thr_fung, *masks)) != 0
    return eligible, eligible.any(axis=0)


# ----------------------------------------------------------------------
# registry + dispatch
# ----------------------------------------------------------------------

_REGISTRY: dict[tuple[str, str], tuple[Callable, Callable[[], bool]]] = {}


def register(op: str, impl: str, fn: Callable,
             available: Callable[[], bool] = lambda: True) -> None:
    _REGISTRY[(op, impl)] = (fn, available)


register("scan", "numpy", scan_starts)
register("fits_mask", "numpy", packing.fits_mask)
register("pack_score", "numpy", packing.pack_score)
register("heartbeat_masks", "numpy", packing.heartbeat_masks)
register("machines_with_candidates", "numpy", packing.machines_with_candidates)

# imported at the bottom on purpose: wave.py references this module's
# registry helpers lazily (inside functions), so by the time either side
# runs, both modules are fully initialized — no import cycle
from . import wave as _wave  # noqa: E402

register("match_wave", "numpy", _wave.match_wave_numpy)

if _HAVE_JAX:
    register("scan", "xla", _scan_xla, have_jax)
    register("fits_mask", "xla", _fits_mask_xla, have_jax)
    register("pack_score", "xla", _pack_score_xla, have_jax)
    register("heartbeat_masks", "xla", _heartbeat_masks_xla, have_jax)
    register("machines_with_candidates", "xla",
             _machines_with_candidates_xla, have_jax)
    register("match_wave", "xla", _wave.match_wave_xla, _wave.have_jax)
    register("scan", "pallas", _scan_pallas, _have_pallas)
    register("machines_with_candidates", "pallas",
             _machines_with_candidates_pallas, _have_pallas)
    register("match_wave", "pallas", _wave.match_wave_pallas,
             _wave.pallas_wave_available)


_REQ_CACHE: tuple[str, dict] | None = None


def _requested() -> dict[str, str]:
    """Parsed REPRO_KERNELS, cached per raw env value (dispatch-hot).

    Thread-safety: the cache is one tuple assigned in a single bytecode
    op after being fully built, and parsing is a pure function of ``raw``
    — two racing threads at worst both parse and assign equal values.
    """
    global _REQ_CACHE
    raw = os.environ.get(KERNELS_ENV, "")
    if _REQ_CACHE is not None and _REQ_CACHE[0] == raw:
        return _REQ_CACHE[1]
    out: dict[str, str] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        op, impl = part.split("=", 1)
        op, impl = op.strip(), impl.strip()
        if impl not in IMPLS:
            raise ValueError(f"unknown kernel impl {impl!r}; have {IMPLS}")
        if op == "all":
            for o in OPS:
                if o not in EXPLICIT_ONLY:
                    out.setdefault(o, impl)
        elif op in OPS:
            out[op] = impl
        else:
            raise ValueError(f"unknown kernel op {op!r}; have {OPS}")
    _REQ_CACHE = (raw, out)
    return out


def resolve(op: str) -> tuple[str, Callable]:
    """(impl name, callable) for one op, honoring env + availability.

    The requested implementation must be registered and available, or
    resolution raises.  Only a sticky demotion (an injected fault at
    dispatch) moves it down the IMPLS chain, to the next registered
    available impl; numpy is always registered and never demoted.
    """
    want = _requested().get(op, "numpy")
    demoted = _DEMOTED.get(op, ())
    for impl in IMPLS[IMPLS.index(want):]:
        if impl in demoted:
            continue
        ent = _REGISTRY.get((op, impl))
        if ent is not None and ent[1]():
            return impl, ent[0]
        if impl == want:
            raise RuntimeError(
                f"kernel op {op!r}: requested impl {want!r} is not "
                f"available on this install/platform")
    raise RuntimeError(f"no implementation available for kernel op {op!r}")


def resolve_heartbeat(op: str, n_machines: int) -> tuple[str, Callable]:
    """Machine-count-aware resolution for the heartbeat eligibility ops.

    An explicit REPRO_KERNELS pin for ``op`` always wins (including a pin
    to numpy).  Otherwise, at ``n_machines >= heartbeat_device_min_m()``
    the xla impl is selected when available, except ``match_wave`` on a
    TPU (``TPU_WAVE_REASON``); below the threshold (or without jax)
    resolution falls through to the normal chain, which lands on the
    exact numpy oracle.
    """
    if op not in HEARTBEAT_AUTO_OPS:
        raise ValueError(f"not a heartbeat op: {op!r}; have {HEARTBEAT_AUTO_OPS}")
    if (op not in _requested() and n_machines >= heartbeat_device_min_m()
            and "xla" not in _DEMOTED.get(op, ())):
        ent = _REGISTRY.get((op, "xla"))
        if ent is not None and ent[1]() and not (
                op == "match_wave" and jax.default_backend() == "tpu"):
            return "xla", ent[0]
    return resolve(op)


def heartbeat_impl(op: str, n_machines: int) -> str:
    """Impl name a heartbeat dispatch would pick at this machine count."""
    return resolve_heartbeat(op, n_machines)[0]


def active() -> dict[str, str]:
    """op -> impl actually selected right now (env + availability).

    For the HEARTBEAT_AUTO_OPS this reports the below-threshold (small-m)
    selection; use :func:`heartbeat_impl` for a machine-count-aware view.
    """
    return {op: resolve(op)[0] for op in OPS}


# -- program spans --------------------------------------------------------

if _HAVE_JAX:
    _TraceAnnotation = jax.profiler.TraceAnnotation
    #: True exactly while a jax.profiler session records (spans are on)
    recording = _TraceAnnotation.is_enabled
else:  # pragma: no cover
    def recording() -> bool:
        return False


class _Span:
    """A recording span: ``repro.<key>`` on the profiler's host plane, on
    the device trace's clock, and one call of its seconds into
    ``PROFILE[key]`` at exit."""

    __slots__ = ("key", "_ann", "_t0")

    def __init__(self, key: str, meta: dict):
        self.key = key
        self._ann = _TraceAnnotation("repro." + key, **meta)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _profile_add(self.key, dt)
        return False


#: the span while nothing records: does nothing, times nothing
_NO_SPAN = contextlib.nullcontext()


def span(key: str, **meta):
    """Context manager timing one program span under ``PROFILE[key]``.

    On exactly while a ``jax.profiler`` trace records: it then enters a
    ``TraceAnnotation("repro.<key>", **meta)``, so the span sits in the
    trace beside the device ops, and counts (1, seconds) at exit.  Off,
    it costs one ``is_enabled`` call and returns a shared no-op.
    """
    if not recording():
        return _NO_SPAN
    return _Span(key, meta)


def clock() -> float | None:
    """Start of a counted stretch: ``perf_counter()`` while a trace
    records, else None.

    For stretches that run thousands of times a second (the service pump
    and its silence scan), where a span's trace event and objects would
    slow the traced run itself: `count` then adds the stretch to
    ``PROFILE`` like a span, and the trace shows it only as part of its
    caller.
    """
    return time.perf_counter() if recording() else None


def count(key: str, t0: float | None) -> None:
    """Count the stretch begun at ``t0 = clock()`` under ``PROFILE[key]``
    (nothing where ``t0`` is None)."""
    if t0 is not None:
        _profile_add(key, time.perf_counter() - t0)


def _call_profiled(op: str, impl: str, fn: Callable, *args, **kwargs):
    key = f"{op}.{impl}"
    t0 = time.perf_counter()
    try:
        if recording():
            with _TraceAnnotation("repro." + key):
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)
    finally:
        _profile_add(key, time.perf_counter() - t0)


def _profile_calls(key: str) -> int:
    with _STATS_LOCK:
        slot = PROFILE.get(key)
        return int(slot[0]) if slot else 0


def _run_op(op: str, resolver: Callable[[], tuple[str, Callable]],
            args, kwargs):
    """Dispatch with sticky demotion on injected faults: a non-numpy impl
    that raises ``faults.InjectedFault`` (the ``kernel_impl`` seam,
    including its simulated OOM / misaligned-grid kinds) is demoted and
    the op re-resolves down the chain, which the numpy oracle terminates.
    Any other exception is a real failure of the impl and propagates: a
    device path that breaks must not pass for a healthy numpy run.  The
    fault seam is keyed by the impl's running call count so probabilistic
    plans fire per-call, not per-op."""
    while True:
        impl, fn = resolver()
        try:
            if impl != "numpy":
                faults.maybe_fail("kernel_impl", op=op, impl=impl,
                                  call=_profile_calls(f"{op}.{impl}"))
            return _call_profiled(op, impl, fn, *args, **kwargs)
        except faults.InjectedFault:
            if impl == "numpy":
                raise
            demote(op, impl)


def _dispatch(op: str, *args, **kwargs):
    return _run_op(op, lambda: resolve(op), args, kwargs)


# -- public dispatching entry points -----------------------------------

def scan(avail, Vs, ks, plo, phi, reverse=False):
    """Windowed feasibility scan through the dispatch table."""
    return _dispatch("scan", avail, Vs, ks, plo, phi, reverse)


def fits_mask(avail, demand, dims=None, slack=0.0, eps=packing.EPS):
    return _dispatch("fits_mask", avail, demand, dims, slack, eps)


def pack_score(avail, demand, clip=False):
    return _dispatch("pack_score", avail, demand, clip)


def heartbeat_masks(avail, demands, fit_dims, rigid_dims, fungible_dims,
                    overbook_slack=0.0, use_overbooking=True):
    avail = np.asarray(avail)
    return _run_op("heartbeat_masks",
                   lambda: resolve_heartbeat("heartbeat_masks",
                                             avail.shape[0]),
                   (avail, demands, fit_dims, rigid_dims, fungible_dims,
                    overbook_slack, use_overbooking), {})


def machines_with_candidates(avail, demands, fit_dims, rigid_dims,
                             fungible_dims, overbook_slack=0.0,
                             use_overbooking=True):
    avail = np.asarray(avail)
    return _run_op("machines_with_candidates",
                   lambda: resolve_heartbeat("machines_with_candidates",
                                             avail.shape[0]),
                   (avail, demands, fit_dims, rigid_dims, fungible_dims,
                    overbook_slack, use_overbooking), {})


def match_wave(ctx) -> int:
    """One fused heartbeat wave over a ``wave.WaveContext``.

    Bit-exact where float64 is native, so it auto-promotes to the xla
    kernel at ``heartbeat_device_min_m()`` machines like the eligibility
    ops (not on a TPU: see ``TPU_WAVE_REASON``); an injected
    ``kernel_impl`` fault sticky-demotes back to the numpy wave loop with
    identical decisions — the device impls mutate no matcher state before
    their launch returns.
    """
    return _run_op("match_wave",
                   lambda: resolve_heartbeat("match_wave",
                                             ctx.avail.shape[0]),
                   (ctx,), {})
