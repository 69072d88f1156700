"""Device-resident placement sessions behind the ``jit`` backend flag.

Same placement semantics as the batched backend — only the window scan
runs as a compiled XLA program against a *persistent device mirror* of
the Space's grid:

  * the mirror registers as a Space observer, so every ``commit`` and
    ``restore`` marks the touched tick range dirty instead of re-uploading
    a freshly padded window per scan (the pre-refactor behavior);
  * before a scan, only the dirty/unsynced slab is uploaded through a
    donated ``dynamic_update_slice`` — buffers are reused in place;
  * scan shapes are padded to the coarse buckets of
    ``core/engine/kernels.py``; every compiled scan and slab-writer
    program stays resident (the bucket ladders bound the keys), with
    builds counted in ``kernels.XLA_STATS``;
  * small batches fall back to the numpy kernel through the dispatch
    layer: below ``MIN_DEVICE_G`` rows the launch overhead exceeds the
    tensor work on CPU hosts, and the fallback is outcome-identical by
    construction (all scan implementations are bit-equal).

Because ``PlacementBackend.sessions`` stacks every sibling variant's
prescan into one ``scan_kernel`` call, the multi-variant node prescan is
a single device launch under this backend (the ROADMAP's "jit-stacked
node prescan").

Exactness note: the grid is float32 while demands are float64, and the
reference scan compares them in float64.  XLA (without global x64) would
silently downcast the demand, which can flip boundary comparisons.  We
instead pre-round each demand *up* to the nearest float32 (``ceil32``):
for float32 a and float64 v, ``a >= v`` iff ``a >= ceil32(v)``, so the
all-float32 kernel is bit-identical to the float64 comparison.

jax is a hard dependency of the wider repo but this module degrades
gracefully: ``JitBackend.available()`` is False when jax cannot be
imported, and ``get_backend("jit")`` then raises at session time.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from . import kernels
from .base import ceil32, register_backend
from .batched import WINDOW0, BatchedBackend, BatchedSession

try:  # gate the dependency: the numpy backends must work without jax
    import jax
    import jax.numpy as jnp
    from jax import lax

    _HAVE_JAX = True
except ImportError:  # pragma: no cover - exercised only on jax-less installs
    jax, jnp, lax = None, None, None
    _HAVE_JAX = False


#: batches smaller than this run the numpy scan instead (outcome-identical;
#: on CPU hosts the device launch overhead dominates below ~16 rows, while
#: a real accelerator amortizes a launch over ~4).  ``None`` means "not
#: resolved yet": ``min_device_g()`` consults REPRO_JIT_MIN_BATCH first and
#: otherwise auto-tunes by the detected jax platform.  Tests monkeypatch
#: this module attribute directly with an int.
MIN_DEVICE_G: int | None = None


def _detect_min_batch() -> int:
    """Default device batch floor: ~4 on real accelerators, 16 on CPU."""
    env = os.environ.get("REPRO_JIT_MIN_BATCH")
    if env:
        return int(env)
    if not _HAVE_JAX:
        return 16
    return 4 if jax.default_backend() != "cpu" else 16


def min_device_g() -> int:
    """Resolved device batch floor (cached in ``MIN_DEVICE_G``)."""
    global MIN_DEVICE_G
    if MIN_DEVICE_G is None:
        MIN_DEVICE_G = _detect_min_batch()
    return MIN_DEVICE_G


#: device scan launches (``scan_calls_per_build``, the construction
#: bench rows); launch and sync time are the ``jit.scan.*`` spans
PROFILE = {"device_calls": 0}

#: guards PROFILE: concurrent builds (core/buildsvc.py thread mode) run
#: device sessions from worker threads and unlocked ``+=`` drops counts
_PROF_LOCK = threading.Lock()


def _prof_add(key: str, n) -> None:
    with _PROF_LOCK:
        PROFILE[key] += n


def reset_profile() -> None:
    with _PROF_LOCK:
        for k in PROFILE:
            PROFILE[k] = 0


def slab_write(buf, slab, idx):
    """Write one (m, Sb, d) slab into the device grid at tick ``idx``
    (its XLA module reads ``jit_slab_write`` in a trace)."""
    return lax.dynamic_update_slice(buf, slab, (0, idx, 0))


# eager construct (a lazy ``if X is None`` init races under threads); the
# builder lambda touches jax only when a key is actually built
_UPDATE_FNS = kernels._BucketCache(
    lambda *k: jax.jit(slab_write, donate_argnums=0), "slab", cap=None)


def _update_fn(m: int, Sb: int, d: int, Tb: int):
    """Donated slab writer for one (m, Sb, d, Tb) shape bucket.

    Kept, like the scan buckets, in an uncapped bucket cache whose
    compiles are counted in ``kernels.XLA_STATS`` (the buffer length Tb is
    part of the trace signature — both slab and buffer sit on coarse
    ladders, so the key set stays small and no program is ever dropped).
    """
    return _UPDATE_FNS.get((m, Sb, d, Tb))


class DeviceGrid:
    """Persistent device mirror of one Space's capacity grid.

    Keeps a (m, Tb, d) float32 buffer on the default jax device, addressed
    in *logical* ticks (stable across ``Space._grow_front``, which only
    shifts the physical origin).  ``synced`` is the logical interval where
    device == host; ``dirty`` the sub-interval invalidated by commits or
    restores since the last upload.  ``ensure(lo, hi)`` uploads exactly one
    bridging slab (bucketed length, donated buffer) so both intervals stay
    intervals — worst case the slab spans the occupied grid, typically it
    is the few frontier ticks the last commits touched.
    """

    def __init__(self, space):
        self.space = space
        self.buf = None
        self.base = 0            # logical tick of buffer index 0
        self.Tb = 0
        self.s0 = self.s1 = 0    # synced logical interval [s0, s1)
        self.d0 = self.d1 = 0    # dirty sub-interval [d0, d1)
        space.add_observer(self)

    # -- Space.observer protocol ---------------------------------------
    def on_commit(self, task, machine, start, k, v) -> None:
        self._mark(start, start + k)

    def on_restore(self, n_placed, lo, hi) -> None:
        if lo is not None:
            self._mark(lo, hi)
        # a shrink drops cells; if the grid later regrows there, host
        # content restarts at 1.0 while the mirror kept old values — clamp
        # the synced interval to the live extent so those cells re-upload
        sp = self.space
        self.s0 = max(self.s0, sp.grid_start)
        self.s1 = min(self.s1, sp.grid_end)
        if self.s0 >= self.s1:
            self.s0 = self.s1 = 0
        self.d0 = max(self.d0, self.s0)
        self.d1 = min(self.d1, self.s1)

    def _mark(self, lo: int, hi: int) -> None:
        if self.s0 >= self.s1:
            return                       # nothing synced: nothing to dirty
        lo, hi = max(lo, self.s0), min(hi, self.s1)
        if lo >= hi:
            return
        if self.d0 >= self.d1:
            self.d0, self.d1 = lo, hi
        else:
            self.d0, self.d1 = min(self.d0, lo), max(self.d1, hi)

    # ------------------------------------------------------------------
    @staticmethod
    def alloc_len(span: int) -> int:
        """Buffer-length ladder for a grid span.

        Floored at the largest first-window read (WINDOW0 + LONG_K) so
        that ``prewarm`` — which only knows the Space's grid length — and
        ``_alloc`` — which also sees the first scan's request — land on
        the same bucket, keeping the prewarmed trace the one live
        sessions actually launch.
        """
        return kernels.bucket(2 * max(span, WINDOW0 + kernels.LONG_K),
                              floor=256)

    def _alloc(self, lo: int, hi: int) -> None:
        """(Re)allocate the buffer so logical [lo, hi) maps inside with
        headroom for growth at both ends; invalidates the synced state."""
        sp = self.space
        self.Tb = self.alloc_len(max(hi - lo, sp.T))
        self.base = lo - (self.Tb - (hi - lo)) // 2
        self.buf = jnp.ones((sp.m, self.Tb, sp.d), dtype=jnp.float32)
        self.s0 = self.s1 = 0
        self.d0 = self.d1 = 0

    def ensure(self, lo: int, hi: int, slack: int) -> None:
        """Make device == host over logical [lo, hi); keep ``slack`` ticks
        past ``lo`` inside the buffer (the scan's fixed-size dynamic_slice
        must not get clamped)."""
        if self.buf is None or lo < self.base or lo + slack > self.base + self.Tb:
            self._alloc(lo, max(hi, lo + slack))
        # pieces of [lo, hi) that are not clean on device right now
        pieces = []
        if self.s0 >= self.s1:
            pieces.append((lo, hi))
        else:
            if self.d0 < self.d1:                     # stale synced cells
                p0, p1 = max(self.d0, lo), min(self.d1, hi)
                if p0 < p1:
                    pieces.append((p0, p1))
            if lo < self.s0:                          # never-synced cells
                pieces.append((lo, min(hi, self.s0)))
            if hi > self.s1:
                pieces.append((max(lo, self.s1), hi))
        if not pieces:
            return
        u0 = min(p[0] for p in pieces)
        u1 = max(p[1] for p in pieces)
        if self.s0 < self.s1:
            # bridge to the synced interval so it stays one interval
            if u0 > self.s1:
                u0 = self.s1
            elif u1 < self.s0:
                u1 = self.s0
        sp = self.space
        # bucket the slab length (bounds update-fn retraces); clamp the
        # bucketed extension inside the buffer
        Sb = min(kernels.bucket(u1 - u0), self.Tb)
        if u0 + Sb > self.base + self.Tb:
            u0 = self.base + self.Tb - Sb
        u1 = u0 + Sb
        # cells beyond the live grid upload as 1.0 — exactly the content
        # the host materializes if the grid later grows there; until then
        # the scan's t_live mask keeps them invisible
        slab = np.ones((sp.m, Sb, sp.d), dtype=np.float32)
        h0, h1 = max(u0, sp.grid_start), min(u1, sp.grid_end)
        if h0 < h1:
            slab[:, h0 - u0 : h1 - u0, :] = np.ascontiguousarray(
                sp.avail[:, h0 + sp.off : h1 + sp.off, :])
        fn = _update_fn(sp.m, Sb, sp.d, self.Tb)
        self.buf = fn(self.buf, slab, np.int32(u0 - self.base))
        if self.s0 >= self.s1:
            self.s0, self.s1 = u0, u1
        else:
            self.s0, self.s1 = min(self.s0, u0), max(self.s1, u1)
        # drop the covered part of the dirty interval; an upload strictly
        # inside it keeps the hull (conservative: re-uploads a few clean
        # cells later rather than ever trusting a stale one)
        if self.d0 < self.d1:
            if u0 <= self.d0 and self.d1 <= u1:
                self.d0 = self.d1 = 0
            elif self.d0 < u0 and self.d1 > u1:
                pass
            elif self.d0 < u0:
                self.d1 = min(self.d1, u0)
            else:
                self.d0 = max(self.d0, u1)

    # ------------------------------------------------------------------
    def launch(self, Vs, ks, plo, phi, reverse) -> "_DeviceRows":
        """Asynchronous device scan: syncs the mirror, launches the kernel,
        returns a lazy row view — no host/device round trip until a row is
        actually walked.  The computation captures the grid state at launch
        (jax arrays are immutable), so later commits/restores cannot leak
        into the result; the session's version/edge logic treats the bitmap
        exactly like a synchronous scan of the same state.  The
        ``jit.scan.launch`` span covers the slab sync and the enqueue."""
        with kernels.span("jit.scan.launch"):
            sp = self.space
            m, T, d = sp.avail.shape
            g = len(ks)
            W = phi - plo
            kmax = int(ks.max())
            hi_read = min(T, phi + kmax - 1)
            lo_l = plo - sp.off                  # logical window start
            hi_l = hi_read - sp.off
            gb, Lb, Wb = kernels.scan_buckets(g, W, kmax)
            self.ensure(lo_l, hi_l, slack=Lb)
            Vs_p = np.full((gb, d), 2.0, dtype=np.float32)
            Vs_p[:g] = ceil32(np.asarray(Vs))
            ks_p = np.ones(gb, dtype=np.int32)
            ks_p[:g] = ks
            fn = kernels.scan_fn_for(m, d, gb, Lb, Wb, self.Tb)
            dev = fn(self.buf, np.int32(lo_l - self.base),
                     np.int32(hi_l - lo_l), Vs_p, ks_p)
        _prof_add("device_calls", 1)
        return _DeviceRows(dev, W, m, reverse)


class _DeviceRows:
    """Lazy view over an in-flight device scan's (g, Wb, m) result.

    ``row(i)`` blocks on the computation once (first access, the
    ``jit.scan.sync`` span) and hands out walk-order (W*m,) bitmap rows;
    unused rows are never transferred."""

    __slots__ = ("dev", "host", "W", "m", "reverse")

    def __init__(self, dev, W: int, m: int, reverse: bool):
        self.dev = dev
        self.host = None
        self.W = W
        self.m = m
        self.reverse = reverse

    def row(self, i: int) -> np.ndarray:
        if self.host is None:
            with kernels.span("jit.scan.sync"):
                self.host = np.asarray(self.dev)
            self.dev = None
        row = self.host[i, : self.W, :]
        if self.reverse:
            row = row[::-1]
        return np.ascontiguousarray(row).reshape(self.W * self.m)

    def loader(self, i: int):
        return lambda: self.row(i)


class JitBackend(BatchedBackend):
    name = "jit"

    #: (m, d, buffer-bucket) triples already compiled this process
    _prewarmed: set[tuple[int, int, int]] = set()
    #: held across a prewarm so concurrent sessions (build service) don't
    #: duplicate the compile work — late arrivals wait on the winner
    _prewarm_lock = threading.Lock()

    @classmethod
    def available(cls) -> bool:
        return _HAVE_JAX

    @classmethod
    def prewarm(cls, m: int, d: int, T: int | None = None) -> None:
        """Compile the common kernel buckets ahead of use.

        The scan shapes are padded to coarse buckets, so the very first
        windows of a session otherwise pay XLA compilation plus backend
        dispatch warm-up inside the timed placement path.  Larger buckets
        still compile on demand (they are cheap once the backend is warm);
        this removes the multi-second first-dispatch hit at session start.

        ``T`` (the Space's physical grid length) predicts the device
        mirror's buffer-length bucket, which is part of the scan's trace
        signature; a later reallocation to a different bucket compiles on
        demand (counted in ``kernels.XLA_STATS``) and, like every scan
        program, then stays resident for the life of the process.
        """
        if not _HAVE_JAX:
            return
        Tb = DeviceGrid.alloc_len(T if T is not None else 0)
        with cls._prewarm_lock:
            if (m, d, Tb) in cls._prewarmed:
                return
            cls._prewarmed.add((m, d, Tb))
            # compile the buckets real sessions hit: device launches carry
            # the g-1 peer rows of batches >= min_device_g(), so gb starts
            # at pad8(max(min_device_g(), 2) - 1), and the first-window
            # shape is (Wb=WINDOW0, Lb=Wb+{SHORT_K,LONG_K})
            gb0 = kernels.pad8(max(min_device_g(), 2) - 1)
            buf = jnp.ones((m, Tb, d), dtype=jnp.float32)
            for gb in (gb0, gb0 + 8):
                Vs = np.full((gb, d), 2.0, dtype=np.float32)
                ks = np.ones(gb, dtype=np.int32)
                for kmax in (kernels.SHORT_K, kernels.LONG_K):
                    _gb, Lb, Wb = kernels.scan_buckets(gb, WINDOW0, kmax)
                    np.asarray(kernels.scan_fn_for(m, d, gb, Lb, Wb, Tb)(
                        buf, np.int32(0), np.int32(16), Vs, ks))

    @staticmethod
    def mirror(space) -> DeviceGrid:
        """The Space's device mirror, created on first use."""
        dg = getattr(space, "_device_grid", None)
        if dg is None:
            dg = DeviceGrid(space)
            space._device_grid = dg
        return dg

    def scan_kernel(self, space, Vs, ks, plo, phi, reverse):
        if not _HAVE_JAX:  # pragma: no cover
            raise RuntimeError("placement backend 'jit' requires jax")
        g = len(ks)
        if g < max(min_device_g(), 2):
            # outcome-identical numpy fallback: launch overhead beats the
            # tensor work for tiny batches, and the hybrid split below
            # needs at least one peer row (see module docstring)
            return kernels.scan(space.avail, Vs, ks, plo, phi, reverse)
        # hybrid split: row 0 — the task the session walks immediately —
        # runs through the numpy g=1 fast path so the caller never blocks
        # on the device; the peer rows launch asynchronously and
        # materialize when (if ever) their tasks pop, by which time the
        # device compute has finished behind the host-side walk
        row0 = kernels.scan(space.avail, Vs[:1], ks[:1], plo, phi, reverse)
        rows = self.mirror(space).launch(Vs[1:], ks[1:], plo, phi, reverse)
        out = [None] * g
        out[0] = lambda: row0[0]
        for i in range(1, g):
            out[i] = rows.loader(i - 1)
        return out

    def session(self, space, direction: str) -> BatchedSession:
        if not _HAVE_JAX:
            raise RuntimeError("placement backend 'jit' requires jax; "
                               "use 'batched' or 'reference' instead")
        self.prewarm(space.m, space.d, space.T)
        return BatchedSession(space, direction, self)


register_backend("jit", JitBackend)
