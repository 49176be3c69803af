"""Unified impact-engine backend: one dispatch point for all CAMEO
impact/aggregate math.

Every ranking/aggregate computation in the compressor — Algorithm-2
single-delta impacts (Eq. 8), exact windowed impacts (Eq. 9), and the lagged
products of ExtractAggregates (Eq. 7) — goes through this module.  The math
itself lives exactly once: ``kernels/ref.py`` holds the pure-jnp reference
forms (also the test oracles), and ``kernels/acf_impact.py`` /
``kernels/acf_window_impact.py`` / ``kernels/lag_dot.py`` hold the Pallas
TPU kernels that implement the same formulas.  ``core/cameo.py`` and
``core/parallel.py`` are thin callers.

Backend selection
-----------------
Three backends, chosen per-call (and plumbed from ``CameoConfig.backend``):

* ``"pallas"``    — the hand-written Pallas kernels.  Compiled by Mosaic on
  TPU; in any other process they execute in ``interpret=True`` mode, which
  runs the kernel body through XLA on CPU — bit-faithful to the kernel
  semantics, so allclose-vs-oracle checks validate the real kernel logic
  (but interpret mode is *slow*; it is a correctness path, not a CPU fast
  path).
* ``"reference"`` — the pure-jnp forms from ``kernels/ref.py`` (chunked the
  same way the kernels tile VMEM, so peak memory matches).
* ``"auto"``      — platform-detected default: ``"pallas"`` on TPU,
  ``"reference"`` everywhere else.

The dispatch rule (:func:`use_kernel`, the one place it is written)
------------------------------------------------------------------
A call runs its kernel when the backend resolves to ``"pallas"`` and the
kernel can serve it:

* ``stat="acf"`` with a measure in ``KERNEL_MEASURES`` (``mae | rmse |
  cheb``, reduced in-kernel).  PACF and the other measures need the full
  hypothetical-ACF rows; they have no kernel.
* On a TPU (compiled, not interpreted) the operand dtype must be float32.
  Mosaic has no float64: the default ``CameoConfig.dtype="float64"`` keeps
  the Eq. 7 base aggregates, the dense Eq. 10/11 update and the exact
  deviation in XLA (which emulates f64 on the chip), because the ε
  guarantee rests on them.  The rounds loop ranks in float32 on purpose,
  so its tier-impact kernel (``fused_round.window_rows_pallas``) is the
  Pallas program of the default configuration.  Interpret mode runs either
  dtype.
* On a TPU, a kernel that keeps its whole problem in fast memory (the
  fused prefix kernel, ``fused_round.prefix_devs_footprint``) must fit
  ``VMEM_BUDGET`` / ``SMEM_BUDGET``.

When the kernel cannot serve a call, ``"auto"`` takes the XLA reference form
and an explicit ``backend="pallas"`` raises ``ValueError``: a requested
kernel never turns into the reference in silence.  The fused-round twins
(``fused_round.window_rows_pallas`` / ``prefix_devs_pallas``) add one
condition (:func:`use_fused_kernel`): they are the decision path only when
compiled, because their accumulation order differs from the reference
reductions; off TPU every backend name selects through the reference form
and the twins are exercised by the parity tests in interpret mode.

Environment overrides (read at trace time; ``chip_smoke.py`` refuses both):

* ``CAMEO_BACKEND=pallas|reference`` — overrides how ``"auto"`` resolves
  (explicit backend choices are never overridden).
* ``CAMEO_FORCE_INTERPRET=1`` — forces ``interpret=True`` for the Pallas
  kernels even on TPU (kernel debugging).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import measures as _measures
from repro.kernels import ref as _ref
from repro.kernels.acf_impact import acf_impact_pallas
from repro.kernels.acf_window_impact import acf_window_impact_pallas
from repro.kernels.lag_dot import lag_dot_pallas

BACKENDS = ("auto", "pallas", "reference")

# measures the kernels reduce in-register (others fall back to reference)
KERNEL_MEASURES = _ref.KERNEL_MEASURES

# Fast memory a whole-problem kernel (fused_round.prefix_devs_pallas) may
# hold on a v5e core (128 MiB VMEM, 1 MiB SMEM), set below what compiled:
# 58 MB of VMEM compiled and 87 MB did not (kernels/README.md).
VMEM_BUDGET = 56 << 20
SMEM_BUDGET = 896 << 10


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``"auto"`` to a concrete backend (honors ``CAMEO_BACKEND``)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    if backend == "auto":
        env = os.environ.get("CAMEO_BACKEND", "").strip()
        if env:
            if env not in ("pallas", "reference"):
                raise ValueError(f"CAMEO_BACKEND={env!r} not in "
                                 f"('pallas', 'reference')")
            return env
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return backend


def interpret_mode() -> bool:
    """Pallas interpret flag: on for non-TPU, or if CAMEO_FORCE_INTERPRET."""
    if os.environ.get("CAMEO_FORCE_INTERPRET", "").strip() not in ("", "0"):
        return True
    return jax.default_backend() != "tpu"


def agg_to_table(agg) -> jax.Array:
    """Stack an ``Aggregates`` five-tuple into the kernels' [5, L] table."""
    if isinstance(agg, jax.Array):
        return agg
    return jnp.stack(list(agg))


def _transform_fn(stat: str):
    if stat == "acf":
        return lambda r: r
    if stat == "pacf":
        from repro.core.acf import pacf_from_acf  # deferred: core imports ops
        return pacf_from_acf
    raise ValueError(f"unknown stat {stat!r}")


def kernel_refusal(dtype, stat: str = "acf", measure: str = "mae",
                   footprint=(0, 0)) -> Optional[str]:
    """Why the Pallas kernel cannot serve this call here, or ``None``.
    ``footprint`` is the ``(vmem_bytes, smem_bytes)`` a kernel that keeps
    its whole problem in fast memory needs."""
    if stat != "acf" or measure not in KERNEL_MEASURES:
        return (f"no kernel for stat={stat!r}, measure={measure!r} "
                f"(kernels reduce acf with {KERNEL_MEASURES})")
    if interpret_mode():
        return None
    if jnp.dtype(dtype) != jnp.float32:
        return (f"Mosaic compiles float32 kernels only; the operand is "
                f"{jnp.dtype(dtype).name}")
    vmem, smem = footprint
    if vmem > VMEM_BUDGET or smem > SMEM_BUDGET:
        return (f"the kernel needs {vmem} B of VMEM and {smem} B of SMEM; "
                f"the budget is {VMEM_BUDGET} and {SMEM_BUDGET}")
    return None


def use_kernel(backend: str, dtype, stat: str = "acf",
               measure: str = "mae", footprint=(0, 0)) -> bool:
    """The dispatch rule (module docstring): ``True`` runs the Pallas
    kernel, ``False`` the XLA reference form.  An explicit
    ``backend="pallas"`` that the kernel cannot serve raises."""
    if resolve_backend(backend) != "pallas":
        return False
    why = kernel_refusal(dtype, stat, measure, footprint)
    if why is None:
        return True
    if backend == "pallas":
        raise ValueError(f"backend='pallas' cannot run here: {why}")
    return False


def use_fused_kernel(backend: str, dtype, stat: str = "acf",
                     measure: str = "mae", footprint=(0, 0)) -> bool:
    """:func:`use_kernel` for the fused-round twins, which are the decision
    path only when compiled (never interpreted)."""
    return (use_kernel(backend, dtype, stat, measure, footprint)
            and not interpret_mode())


# ---------------------------------------------------------------------------
# Eq. 7 — lagged products
# ---------------------------------------------------------------------------

def lag_dot(a, L: int, *, b=None, halo=None, block: int = 4096,
            backend: str = "auto"):
    """``out[l-1] = sum_{t<m} a_t * b_ext_{t+l}`` for l=1..L, shape [L].

    Defaults (``b=None, halo=None``) give the Eq. 7 self-products ``sxx``.
    ``b`` computes cross lagged products; ``halo`` appends an L-point
    continuation of ``b`` past the chunk end (the partitioned mode's
    cross-chunk overlap terms).
    """
    if use_kernel(backend, a.dtype):
        return lag_dot_pallas(a, b, halo, L=L, block=block,
                              interpret=interpret_mode())
    b_ext = a if b is None else b
    if halo is not None:
        b_ext = jnp.concatenate([b_ext, halo[:L].astype(b_ext.dtype)])
    else:
        b_ext = jnp.pad(b_ext, (0, L))
    return _ref.lag_xdot(a, b_ext, L=L)


# ---------------------------------------------------------------------------
# Eq. 8 — single-delta impacts (Algorithm 2)
# ---------------------------------------------------------------------------

def acf_impact(y, dval, agg, p0, *, measure: str = "mae",
               block: int = 1024, backend: str = "auto"):
    """Algorithm-2 impacts for all points: D(ACF_after_delta_i, P0), [n]."""
    table = agg_to_table(agg)
    L = p0.shape[0]
    if use_kernel(backend, y.dtype, "acf", measure):
        return acf_impact_pallas(
            y, dval, table, p0, L=L, measure=measure, block=block,
            interpret=interpret_mode())
    return _ref.acf_impact_ref(y, dval, table, p0, L=L, measure=measure)


# ---------------------------------------------------------------------------
# Eq. 9 — windowed impacts
# ---------------------------------------------------------------------------

def window_impact(y, dwins, starts, agg, p0, *, measure: str = "mae",
                  backend: str = "auto"):
    """Exact Eq. 9 impacts for P candidate windows against series ``y``.

    ``dwins [P, W]`` are zero-padded delta windows starting at ``starts [P]``
    (absolute indices into ``y``).  Returns ``[P]``.
    """
    table = agg_to_table(agg)
    L = p0.shape[0]
    ny = y.shape[0]
    rows_ctx = _ref.candidate_contexts(y, starts, L=L, W=dwins.shape[1])
    if use_kernel(backend, y.dtype, "acf", measure):
        return acf_window_impact_pallas(
            rows_ctx, dwins, starts, table, p0, ny=ny, L=L, measure=measure,
            interpret=interpret_mode())
    return _ref.acf_window_impact_ref(
        rows_ctx, dwins, starts, table, p0, ny=ny, measure=measure)


# ---------------------------------------------------------------------------
# ranking engine — the GetAllImpact hot path used by the compressor
# ---------------------------------------------------------------------------

def _measure_transform(cfg):
    return _measures.get_measure(cfg.measure), _transform_fn(cfg.stat)


def _single_impacts_kernel(cfg, table, y, y_idx, dval, p0):
    """Kernel-path Eq. 8 impacts for all n x-candidates (candidate ``i``
    adds ``dval[i]`` at y position ``y_idx[i]``)."""
    return acf_impact_pallas(y, dval, table, p0, idx=y_idx, L=cfg.lags,
                             measure=cfg.measure, block=cfg.impact_chunk,
                             interpret=interpret_mode())


def _single_impacts_ref(cfg, agg, y, y_idx, dval, p0, n: int):
    """Reference-path Eq. 8 impacts, chunked like the kernel tiles VMEM."""
    mfn, transform = _measure_transform(cfg)
    chunk = min(cfg.impact_chunk, n)
    pad = (-n) % chunk
    ii = jnp.pad(y_idx, (0, pad))
    dd = jnp.pad(dval, (0, pad))

    def one_chunk(args):
        ci, cd = args
        rows = _ref.acf_after_single_delta(agg, y, ci, cd)    # [chunk, L]
        return jax.vmap(lambda r: mfn(transform(r), p0))(rows)

    nchunks = (n + pad) // chunk
    return jax.lax.map(
        one_chunk, (ii.reshape(nchunks, chunk), dd.reshape(nchunks, chunk))
    ).reshape(-1)[:n]


def _rank_single(cfg, agg, y, xr, alive, p0, n: int):
    """Algorithm-2 (single-delta) ranking impact for all n points."""
    from repro.core.aggregates import alive_neighbors, interpolate_at
    dt = cfg.jdtype()
    idx = jnp.arange(n, dtype=jnp.int32)
    prev, nxt = alive_neighbors(alive)
    xhat = interpolate_at(xr, prev, nxt, idx)
    dx = xhat - xr
    if cfg.kappa == 1:
        y_idx, dval = idx, dx
    else:
        y_idx = idx // cfg.kappa
        dval = dx / jnp.asarray(cfg.kappa, dt)

    if use_kernel(cfg.backend, y.dtype, cfg.stat, cfg.measure):
        imp = _single_impacts_kernel(cfg, agg_to_table(agg), y, y_idx, dval,
                                     p0)
    else:
        imp = _single_impacts_ref(cfg, agg, y, y_idx, dval, p0, n)

    inf = jnp.asarray(jnp.inf, dt)
    removable = alive & (idx > 0) & (idx < n - 1)
    return jnp.where(removable, imp.astype(dt), inf)


def _window_chunk(cfg, agg, y_ctx, ystart, dyw, p0, off, ny: int,
                  kernel: bool):
    """Eq. 9 impacts for one chunk of candidates against a 1-D haloed
    context ``y_ctx`` (``y_ctx[k] = y_local[k - L]``, zeros out of range)."""
    L = cfg.lags
    mfn, transform = _measure_transform(cfg)
    if kernel:
        Wy = dyw.shape[1]
        k = jnp.arange(Wy + 2 * L)
        rows_ctx = y_ctx[ystart[:, None] + k[None, :]]        # [c, Wy + 2L]
        return acf_window_impact_pallas(
            rows_ctx, dyw, off + ystart, agg_to_table(agg), p0, ny=ny, L=L,
            measure=cfg.measure, interpret=interpret_mode())
    rows = _ref.acf_after_window_delta_ctx(
        agg, y_ctx, ystart, dyw, ny=ny, off=off)
    return jax.vmap(lambda r: mfn(transform(r), p0))(rows)


def x_window_to_y(cfg, dwin, start):
    """Map x-space delta windows onto the target (aggregate) series.

    ``dwin`` is ``[..., W]`` with matching ``start`` shape ``[...]``; for
    ``kappa == 1`` this is the identity, otherwise each window is
    segment-summed onto the ``Wy = W // kappa + 2`` covered y cells.
    """
    kap = cfg.kappa
    if kap == 1:
        return dwin, start
    W = dwin.shape[-1]
    Wy = W // kap + 2
    dt = dwin.dtype
    b0 = start // kap
    j = jnp.arange(W, dtype=jnp.int32)
    seg = (start[..., None] + j) // kap - b0[..., None]
    ssum = lambda d, s: jax.ops.segment_sum(d, s, num_segments=Wy)
    if dwin.ndim == 1:
        dyw = ssum(dwin, seg)
    else:
        dyw = jax.vmap(ssum)(dwin, seg)
    return dyw / jnp.asarray(kap, dt), b0


def _rank_window_ctx(cfg, agg, y_ctx, xr_loc, alive_loc, p0, off_y, ny: int,
                     fallback: str):
    """Exact windowed (Eq. 9) ranking impact for all local candidates.

    ``y_ctx`` is the 1-D haloed target context (L left halo, >= L + W right
    pad), ``off_y`` the chunk's global y offset.  Candidates whose segment
    outgrew the static window ``W`` either fall back to the single-delta
    estimate (``fallback="single"``, global mode — their actual removal is
    still checked exactly by the dense update) or rank unremovable
    (``fallback="inf"``, partitioned mode).
    """
    from repro.core.aggregates import alive_neighbors, segment_deltas
    dt = cfg.jdtype()
    W = cfg.window
    mx = xr_loc.shape[0]
    idx = jnp.arange(mx, dtype=jnp.int32)
    prev, nxt = alive_neighbors(alive_loc)
    inf = jnp.asarray(jnp.inf, dt)
    kernel = use_kernel(cfg.backend, y_ctx.dtype, cfg.stat, cfg.measure)

    chunk = min(cfg.impact_chunk, mx)
    pad = (-mx) % chunk
    idx_p = jnp.pad(idx, (0, pad))

    def one_chunk(ci):
        dwin, start, span = segment_deltas(xr_loc, prev, nxt, ci, W)
        dyw, ystart = x_window_to_y(cfg, dwin, start)
        imp = _window_chunk(cfg, agg, y_ctx, ystart, dyw, p0, off_y, ny,
                            kernel)
        return imp.astype(dt), span

    nchunks = (mx + pad) // chunk
    imp, span = jax.lax.map(one_chunk, idx_p.reshape(nchunks, chunk))
    imp = imp.reshape(-1)[:mx]
    span = span.reshape(-1)[:mx]

    # fallback="single": overgrown entries keep their truncated-window value
    # here; ranking_impact replaces every one of them with the single-delta
    # estimate, so nothing downstream observes it.
    overgrown = span > W
    if fallback == "inf":
        imp = jnp.where(overgrown, inf, imp)

    removable = alive_loc & (idx > 0) & (idx < mx - 1)
    return jnp.where(removable, imp, inf), overgrown


def ranking_impact(cfg, agg, y, xr, alive, p0, n: int, *, rank=None):
    """GetAllImpact: ranking impact for every point of a whole series.

    Dispatches on ``rank`` (default ``cfg.rank``): ``"single"`` is the
    Algorithm-2 Eq. 8 approximation, ``"window"`` the exact Eq. 9 segment
    form with single-delta fallback for overgrown segments.
    """
    rank = cfg.rank if rank is None else rank
    if rank == "single":
        return _rank_single(cfg, agg, y, xr, alive, p0, n)
    if rank != "window":
        raise ValueError(f"unknown rank {rank!r}")
    ny = y.shape[0]
    L, W = cfg.lags, cfg.window
    dt = cfg.jdtype()
    y_ctx = jnp.pad(y, (L, L + W))
    imp, overgrown = _rank_window_ctx(
        cfg, agg, y_ctx, xr, alive, p0, 0, ny, fallback="single")
    imp_sd = _rank_single(cfg, agg, y, xr, alive, p0, n)
    return jnp.where(overgrown, imp_sd, imp).astype(dt)


def chunk_ranking_impact(cfg, agg, y_ctx, xr_c, alive_c, p0, off_y, ny: int):
    """Partitioned-mode ranking: exact windowed impacts for one partition's
    candidates (overgrown segments rank +inf — unremovable here)."""
    imp, _ = _rank_window_ctx(
        cfg, agg, y_ctx, xr_c, alive_c, p0, off_y, ny, fallback="inf")
    return imp


def window_impact_at(cfg, agg, y, xr, prev, nxt, cand, p0):
    """Exact (Eq. 9) ranking impact of removing each alive point in ``cand``
    (the sequential mode's ReHeap recompute).  Overgrown segments and series
    endpoints rank +inf."""
    from repro.core.aggregates import segment_deltas
    dt = cfg.jdtype()
    n = xr.shape[0]
    ny = y.shape[0]
    L, W = cfg.lags, cfg.window
    dwin, start, span = segment_deltas(xr, prev, nxt, cand, W)
    dyw, ystart = x_window_to_y(cfg, dwin, start)
    y_ctx = jnp.pad(y, (L, L + W))
    kernel = use_kernel(cfg.backend, y.dtype, cfg.stat, cfg.measure)
    imp = _window_chunk(cfg, agg, y_ctx, ystart, dyw, p0, 0, ny, kernel)
    interior = (cand > 0) & (cand < n - 1)
    inf = jnp.asarray(jnp.inf, dt)
    return jnp.where((span <= W) & interior, imp.astype(dt), inf)
