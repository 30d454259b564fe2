"""Fleet-scale hybrid-histogram policy update — Pallas TPU kernel.

This is the paper's own hot loop, adapted TPU-natively (DESIGN.md §2). The
paper's challenges #4/#5 demand O(µs) policy updates per invocation; at
provider scale (millions of app endpoints) the control plane batches the
idle-time observations of one scheduling tick and updates *all* app
histograms plus their policy windows in a single vectorized pass:

  for each app a in tile:                      (one VMEM tile = TA apps)
    counts[a, bin(it_a)] += 1                  (or OOB counter)
    cv[a]     <- Welford O(1) update
    head/tail <- weighted 5th/99th percentile over bins
    prewarm/keepalive <- margins + representativeness fallback

Everything is rank-2 [TA, n_bins] arithmetic — ideal VPU work. The decision
formulas are NOT written here: kernel bodies call the single-source helpers
in :mod:`repro.core.policy_math` with ``gather=False`` (masked-reduction
forms — compare-against-iota instead of row gathers), which trace inside
Pallas identically to the ``lax.scan`` engines.

Two kernels:

  * :func:`policy_update_pallas` — one scheduling tick of the control
    plane (grid (n_apps / TA,), fully parallel over app tiles);
  * :func:`fused_hybrid_sweep_step_pallas` — one simulator scan step for S
    stacked policy configurations (grid (S, n_apps / TA)); the per-config
    knobs arrive as a scalar-prefetched SMEM config block, so a new grid
    point is a new SMEM row, not a recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import policy_math


def _policy_kernel(counts_ref, oob_ref, total_ref, cvs_ref, cvss_ref,
                   bins_ref, active_ref,
                   ncounts_ref, noob_ref, ntotal_ref, ncvs_ref, ncvss_ref,
                   prewarm_ref, keep_ref, use_hist_ref, *,
                   n_bins: int, head_pct: float, tail_pct: float,
                   margin: float, bin_minutes: float, range_minutes: float,
                   cv_threshold: float, min_samples: int, oob_threshold: float):
    counts = counts_ref[...]                       # [TA, n_bins] i32
    bins = bins_ref[...]                           # [TA] i32 (bin idx; >=n_bins -> OOB)
    active = active_ref[...] != 0                  # [TA]
    TA = counts.shape[0]

    in_b = active & (bins >= 0) & (bins < n_bins)
    oob_hit = active & (bins >= n_bins)
    safe = jnp.clip(bins, 0, n_bins - 1)

    iota = jax.lax.broadcasted_iota(jnp.int32, (TA, n_bins), 1)
    onehot = (iota == safe[:, None]) & in_b[:, None]
    old = jnp.sum(jnp.where(onehot, counts, 0), axis=1)          # [TA]
    new_counts = counts + onehot.astype(jnp.int32)

    total = total_ref[...] + in_b.astype(jnp.int32)
    oob = oob_ref[...] + oob_hit.astype(jnp.int32)
    cvs, cvss = policy_math.welford_update(cvs_ref[...], cvss_ref[...],
                                           in_b, old)

    cum = jnp.cumsum(new_counts, axis=1)                          # [TA, n_bins]
    head_bin = policy_math.first_bin_ge_scaled(
        cum, policy_math.percentile_threshold_scaled(total, head_pct),
        gather=False)
    tail_bin = policy_math.first_bin_ge_scaled(
        cum, policy_math.percentile_threshold_scaled(total, tail_pct),
        gather=False) + 1
    load_at, unload_at = policy_math.window_values(
        head_bin, tail_bin, bin_minutes, range_minutes, margin)
    use_hist = policy_math.use_histogram_gate(
        total, oob, cvs, cvss, n_bins, min_samples,
        policy_math.cv_gate_factor(cv_threshold), oob_threshold)
    std_load, std_unload = policy_math.standard_window_bounds(range_minutes)
    prewarm = jnp.where(use_hist, load_at, std_load)
    keep = jnp.where(use_hist, unload_at, std_unload) - prewarm

    ncounts_ref[...] = new_counts
    noob_ref[...] = oob
    ntotal_ref[...] = total
    ncvs_ref[...] = cvs
    ncvss_ref[...] = cvss
    prewarm_ref[...] = prewarm
    keep_ref[...] = keep
    use_hist_ref[...] = use_hist.astype(jnp.int32)


def policy_update_pallas(counts, oob, total, cv_sum, cv_sum_sq, bins, active,
                         *, head_pct=5.0, tail_pct=99.0, margin=0.10,
                         bin_minutes=1.0, range_minutes=240.0,
                         cv_threshold=2.0, min_samples=5, oob_threshold=0.5,
                         tile_apps: int = 512, interpret: bool = True):
    """Batched histogram+policy update for the whole fleet.

    counts: [n_apps, n_bins] i32; oob/total: [n_apps] i32;
    cv_sum/cv_sum_sq: [n_apps] f32; bins: [n_apps] i32 (this tick's IT bin,
    >= n_bins means OOB); active: [n_apps] i32 (0/1).
    Returns (new_counts, new_oob, new_total, new_cv_sum, new_cv_sum_sq,
             prewarm, keep_alive, use_hist).
    """
    n_apps, n_bins = counts.shape
    TA = min(tile_apps, n_apps)
    pad = (-n_apps) % TA
    if pad:
        # pad with inactive rows so the app tiling covers every app
        pv = lambda x, fill=0: jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        counts, oob, total = pv(counts), pv(oob), pv(total)
        cv_sum, cv_sum_sq = pv(cv_sum), pv(cv_sum_sq)
        bins, active = pv(bins), pv(active)
        n_apps += pad
    grid = (n_apps // TA,)
    kernel = functools.partial(
        _policy_kernel, n_bins=n_bins, head_pct=head_pct, tail_pct=tail_pct,
        margin=margin, bin_minutes=bin_minutes, range_minutes=range_minutes,
        cv_threshold=cv_threshold, min_samples=min_samples,
        oob_threshold=oob_threshold)

    vec = lambda dt: pl.BlockSpec((TA,), lambda i: (i,))
    mat = pl.BlockSpec((TA, n_bins), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat, vec(None), vec(None), vec(None), vec(None), vec(None),
                  vec(None)],
        out_specs=[mat, vec(None), vec(None), vec(None), vec(None), vec(None),
                   vec(None), vec(None)],
        out_shape=[
            jax.ShapeDtypeStruct((n_apps, n_bins), jnp.int32),
            jax.ShapeDtypeStruct((n_apps,), jnp.int32),
            jax.ShapeDtypeStruct((n_apps,), jnp.int32),
            jax.ShapeDtypeStruct((n_apps,), jnp.float32),
            jax.ShapeDtypeStruct((n_apps,), jnp.float32),
            jax.ShapeDtypeStruct((n_apps,), jnp.float32),
            jax.ShapeDtypeStruct((n_apps,), jnp.float32),
            jax.ShapeDtypeStruct((n_apps,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(counts, oob, total, cv_sum, cv_sum_sq, bins, active)
    if pad:
        outs = tuple(o[:-pad] for o in outs)
    return outs


# ---------------------------------------------------------------------------
# Fused simulator sweep step: bin-update + CV + percentile decision +
# warm/cold accounting for S stacked policy configurations, one pass per
# scan step over the whole fleet. The per-config knobs ride in SMEM as a
# scalar-prefetched config block — adding a grid point changes data, not
# the kernel.
# ---------------------------------------------------------------------------

# Column layout of the scalar-prefetched config blocks (see
# ``repro.core.simulator._build_pallas_cfg``).
CFG_I32_COLS = ("n_bins", "head_numer", "tail_numer", "min_samples")
CFG_F32_COLS = ("margin_lo", "margin_hi", "bin_minutes", "range_f32",
                "cv_factor", "oob_threshold", "standard_keep")


def _sweep_step_kernel(cfg_i_ref, cfg_f_ref, lo_ref, hi_ref, code_ref,
                       cum_ref, oob_ref, cvs_ref, cvss_ref, pre_ref,
                       unload_ref, cold_ref, waste_ref,
                       ncum_ref, noob_ref, ncvs_ref, ncvss_ref, npre_ref,
                       nunload_ref, ncold_ref, nwaste_ref):
    """One hybrid-policy scan step for (config s, tile of TA apps).

    ``cfg_i_ref``/``cfg_f_ref`` are the scalar-prefetched [S, k] config
    blocks living in SMEM; program_id(0) selects this instance's row. The
    body is exactly the single-source step in
    ``policy_math.hybrid_gap_step_math`` with the Pallas-lowerable
    ``gather=False`` lookup strategy and *traced* config scalars. Apps sit
    on lanes: per-app refs are [1, TA], the cumulative counts [n_bins, TA]
    (``bin_axis=0``), so every per-app operand broadcasts over the bins
    without a relayout.
    """
    s = pl.program_id(0)
    cfg = policy_math.HybridStepConfig(
        n_bins=cfg_i_ref[s, 0], head_numer=cfg_i_ref[s, 1],
        tail_numer=cfg_i_ref[s, 2], min_samples=cfg_i_ref[s, 3],
        margin_lo=cfg_f_ref[s, 0], margin_hi=cfg_f_ref[s, 1],
        bin_minutes=cfg_f_ref[s, 2], bin_f32=cfg_f_ref[s, 2],
        range_f32=cfg_f_ref[s, 3], cv_factor=cfg_f_ref[s, 4],
        oob_threshold=cfg_f_ref[s, 5], standard_keep=cfg_f_ref[s, 6])
    out = policy_math.hybrid_gap_step_math(
        lo_ref[...], hi_ref[...],
        policy_math.decode_gaps(code_ref[...], cfg.n_bins), cum_ref[...],
        oob_ref[...], cvs_ref[...], cvss_ref[...], pre_ref[...],
        unload_ref[...], cold_ref[...], waste_ref[...], cfg=cfg,
        gather=False, bin_axis=0)
    for ref, val in zip((ncum_ref, noob_ref, ncvs_ref, ncvss_ref, npre_ref,
                         nunload_ref, ncold_ref, nwaste_ref), out):
        ref[...] = val


def fused_hybrid_sweep_step_pallas(it_lo, it_hi, code, cum, oob, cv_sum,
                                   cv_sum_sq, prewarm, unload_at, cold,
                                   waste, cfg_i32, cfg_f32, *,
                                   tile_apps: int = 512,
                                   interpret: bool = False):
    """One fused hybrid-simulator scan step for S configs x the whole fleet.

    Lane-dense layout, apps on the last axis of every operand. The event
    column is shared by every config: ``code`` [1, n_apps] int32 gap codes
    (``policy_math.encode_gaps``: padding, first event, or the bin of the
    gap the event closes) and the gap's idle time as a float32 bracket
    ``it_lo <= it <= it_hi`` [1, n_apps] — classified and rounded on the
    host from the float64 clock, so the kernel's verdicts and histograms
    are the float64 oracle's. Per-config state is [S, 1, n_apps] (``cum``
    is [S, n_bins, n_apps] i32 *cumulative* in-bounds counts;
    (``prewarm``, ``unload_at``) are the residency bounds decided after
    each app's previous event). ``cfg_i32`` [S, 4] / ``cfg_f32`` [S, 7] are
    the per-config knob blocks (column layout
    ``CFG_I32_COLS``/``CFG_F32_COLS``), delivered to SMEM via scalar
    prefetch. Grid: (S, n_apps / TA) — fully parallel; each block is
    updated in place (the state operands alias the outputs). Returns the
    updated (cum, oob, cv_sum, cv_sum_sq, prewarm, unload_at, cold, waste).
    Designed to sit inside ``jax.lax.scan`` over padded event columns;
    ``interpret=True`` runs it off-TPU.
    """
    S, n_bins, n_apps = cum.shape
    state = (cum, oob, cv_sum, cv_sum_sq, prewarm, unload_at, cold, waste)
    if n_apps == 0 or S == 0:
        return state
    TA = min(tile_apps, n_apps)
    pad = (-n_apps) % TA
    if pad:
        pv = lambda x, fill=0: jnp.concatenate(
            [x, jnp.full(x.shape[:-1] + (pad,), fill, x.dtype)], axis=-1)
        code = pv(code, policy_math.GAP_PAD)     # padded apps never act
        it_lo, it_hi = pv(it_lo), pv(it_hi)
        state = tuple(pv(x) for x in state)
        n_apps += pad
    grid = (S, n_apps // TA)

    # The config axis is squeezed out of every block (``None``), so a
    # kernel instance sees [1, TA] vectors and an [n_bins, TA] tile: both
    # minor block dims are either whole or lane-aligned, as Mosaic needs.
    col = pl.BlockSpec((1, TA), lambda s, i, *refs: (0, i))
    vec = pl.BlockSpec((None, 1, TA), lambda s, i, *refs: (s, 0, i))
    mat = pl.BlockSpec((None, n_bins, TA), lambda s, i, *refs: (s, 0, i))
    specs = [mat] + [vec] * 7
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[col] * 3 + specs, out_specs=specs)
    outs = pl.pallas_call(
        _sweep_step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        # operands 0-4 are the two config blocks and the event column
        input_output_aliases={5 + k: k for k in range(len(state))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="hybrid_sweep_step",
    )(cfg_i32, cfg_f32, it_lo, it_hi, code, *state)
    if pad:
        outs = tuple(o[..., :-pad] for o in outs)
    return tuple(outs)


def fused_hybrid_step_pallas(t_now, prev_t, cum, oob, cv_sum, cv_sum_sq,
                             prewarm, unload_at, cold, waste, *,
                             head_pct=5.0, tail_pct=99.0, margin=0.10,
                             bin_minutes=1.0, range_minutes=240.0,
                             cv_threshold=2.0, min_samples=5,
                             oob_threshold=0.5, standard_keep=240.0,
                             tile_apps: int = 512, interpret: bool = False):
    """Single-config fused scan step: the S=1 slice of the sweep kernel.

    Kept as the scalar-parity/benchmark surface (``ops.fused_hybrid_step``)
    in the row layout (``cum`` [n_apps, n_bins], per-app state [n_apps]);
    the knobs are packed into a one-row SMEM config block exactly as the
    sweep driver would (``HybridStepConfig.from_host`` owns the rounding).
    """
    n_apps, n_bins = cum.shape
    if n_apps == 0:
        return (prev_t, cum, oob, cv_sum, cv_sum_sq, prewarm, unload_at,
                cold, waste)
    c = policy_math.HybridStepConfig.from_host(
        n_bins=n_bins, head_pct=head_pct, tail_pct=tail_pct, margin=margin,
        bin_minutes=bin_minutes, range_minutes=range_minutes,
        cv_threshold=cv_threshold, min_samples=min_samples,
        oob_threshold=oob_threshold, standard_keep=standard_keep)
    cfg_i32 = jnp.asarray(
        [[c.n_bins, c.head_numer, c.tail_numer, c.min_samples]], jnp.int32)
    cfg_f32 = jnp.asarray(
        [[c.margin_lo, c.margin_hi, c.bin_f32, c.range_f32, c.cv_factor,
          c.oob_threshold, c.standard_keep]], jnp.float32)
    # The row layout's clock stays outside the kernel: classify the gap in
    # float32 here, exactly as the sweep step would.
    valid = jnp.isfinite(t_now)
    first = ~jnp.isfinite(prev_t)
    it = t_now - prev_t
    code = policy_math.encode_gaps(it, valid, first, c.bin_f32, n_bins)
    lane = lambda v: v[None, None]                 # [n] -> [1, 1, n]
    outs = fused_hybrid_sweep_step_pallas(
        it[None], it[None], code[None], cum.T[None], lane(oob), lane(cv_sum),
        lane(cv_sum_sq), lane(prewarm), lane(unload_at), lane(cold),
        lane(waste), cfg_i32, cfg_f32, tile_apps=tile_apps,
        interpret=interpret)
    return (jnp.where(valid, t_now, prev_t),) + tuple(
        o[0].T if k == 0 else o[0, 0] for k, o in enumerate(outs))
