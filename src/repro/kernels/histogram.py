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
  * :func:`hybrid_sweep_scan_pallas` — the simulator's whole scan over a
    chunk's event columns for S stacked policy configurations (grid
    (S, n_apps / TA, width / TB)): each (config, app tile)'s histogram
    state stays in VMEM while the kernel walks the time axis, and reaches
    HBM once. The per-config knobs arrive as a scalar-prefetched SMEM
    config block, so a new grid point is a new SMEM row, not a recompile.
    :func:`fused_hybrid_step_pallas` is its one-step, one-config call in
    the row layout.
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
# Fused simulator sweep scan: bin-update + CV + percentile decision +
# warm/cold accounting for S stacked policy configurations, walked through
# every event column of a chunk inside the kernel. Each (config, app tile)'s
# state stays in VMEM from the first step to the last. The per-config knobs
# ride in SMEM as a scalar-prefetched config block — adding a grid point
# changes data, not the kernel.
# ---------------------------------------------------------------------------

# Column layout of the scalar-prefetched config blocks (see
# ``repro.core.simulator._build_pallas_cfg``).
CFG_I32_COLS = ("n_bins", "head_numer", "tail_numer", "min_samples")
CFG_F32_COLS = ("margin_lo", "margin_hi", "bin_minutes", "range_f32",
                "cv_factor", "oob_threshold", "standard_keep")

# VMEM the scan kernel plans for: a tile's resident state (its input and
# output blocks, double-buffered, and the step's working copy), then the
# double-buffered event-column blocks in what the state leaves. Half of
# the 16 MiB that Mosaic scopes by default on a v5e, so the step's spilled
# temporaries (several copies of ``cum``) fit beside it.
_SCAN_VMEM_BYTES = 8 << 20
# The most vregs a tile's ``cum`` may span: 60 bins x 1,024 apps packed
# (60) and 240 bins x 512 lane-only (120) are the tiles checked on a v5e
# against the per-step kernel it replaced, bit for bit.
_MAX_TILE_VREGS = 128
_N_COLUMNS = 3            # it_lo, it_hi, code
_N_VECTORS = 7            # the per-app state vectors beside ``cum``
# A tile of whole vregs (8 sublanes x 128 lanes of apps) is laid out
# packed: bins on a leading axis, each per-app vector one dense vreg.
_PACKED_APPS = 8 * 128


def _vmem_bytes(rows: int, lanes: int) -> int:
    """VMEM bytes of a 32-bit [rows, lanes] block: (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-lanes // 128) * 128) * 4


def _tile_vregs(n_bins: int, ta: int) -> int:
    """Vregs of a TA-app tile's ``cum``."""
    if ta % _PACKED_APPS == 0:
        return n_bins * (ta // _PACKED_APPS)
    return -(-n_bins // 8) * -(-ta // 128)


def _state_bytes(n_bins: int, ta: int) -> int:
    """VMEM bytes of one copy of a TA-app tile's state."""
    if ta % _PACKED_APPS == 0:
        return (n_bins + _N_VECTORS) * ta * 4
    return _vmem_bytes(n_bins, ta) + _N_VECTORS * _vmem_bytes(1, ta)


def scan_blocks(n_apps: int, width: int, n_bins: int,
                tile_apps: int) -> tuple:
    """(TA, TB): the apps and the event steps of one scan-kernel block.

    TA is ``tile_apps``, halved while its ``cum`` would span more than
    ``_MAX_TILE_VREGS``, or the whole chunk when that is smaller (a chunk
    below one tile is its own tile). TB is what the resident state (five
    copies) leaves of the VMEM plan for the double-buffered event
    columns. A chunk that fits is one block (no time padding); a longer
    one splits into equal blocks of a multiple of 8 steps, at most 7
    steps a block padding.
    """
    ta = tile_apps
    while _tile_vregs(n_bins, ta) > _MAX_TILE_VREGS and ta % 256 == 0:
        ta //= 2
    ta = min(ta, n_apps)
    step = 2 * _N_COLUMNS * _vmem_bytes(8, ta) // 8
    tb_max = max((_SCAN_VMEM_BYTES - 5 * _state_bytes(n_bins, ta))
                 // step // 8 * 8, 8)
    if width <= tb_max:
        return ta, width
    # of the block counts up to twice the least, the one padding fewest
    least = -(-width // tb_max)
    sizes = [-(-width // (8 * b)) * 8 for b in range(least, 2 * least)]
    return ta, min(sizes, key=lambda tb: -(-width // tb) * tb)


def _sweep_scan_kernel(cfg_i_ref, cfg_f_ref, lo_ref, hi_ref, code_ref,
                       *refs):
    """Hybrid-policy scan of config s over one tile of TA apps and one
    block of TB event steps.

    ``cfg_i_ref``/``cfg_f_ref`` are the scalar-prefetched [S, k] config
    blocks living in SMEM; program_id(0) selects this instance's row. The
    event columns are [TB, TA] blocks (or [TB, TA / 128, 128] packed, see
    :func:`hybrid_sweep_scan_pallas`), time leading and apps on lanes;
    the state blocks (``cum`` [n_bins, TA], ``bin_axis=0``, the rest
    [1, TA]) ignore the time axis of the grid, so they stay in VMEM across
    it and reach HBM once, after the last block. The first block copies
    the incoming state in. Each step is exactly the single-source step in
    ``policy_math.hybrid_gap_step_math`` with the Pallas-lowerable
    ``gather=False`` lookup strategy and *traced* config scalars, on one
    row of the columns.
    """
    init, state = refs[:len(refs) // 2], refs[len(refs) // 2:]
    s = pl.program_id(0)
    cfg = policy_math.HybridStepConfig(
        n_bins=cfg_i_ref[s, 0], head_numer=cfg_i_ref[s, 1],
        tail_numer=cfg_i_ref[s, 2], min_samples=cfg_i_ref[s, 3],
        margin_lo=cfg_f_ref[s, 0], margin_hi=cfg_f_ref[s, 1],
        bin_minutes=cfg_f_ref[s, 2], bin_f32=cfg_f_ref[s, 2],
        range_f32=cfg_f_ref[s, 3], cv_factor=cfg_f_ref[s, 4],
        oob_threshold=cfg_f_ref[s, 5], standard_keep=cfg_f_ref[s, 6])

    @pl.when(pl.program_id(2) == 0)
    def _():
        for src, dst in zip(init, state):
            dst[...] = src[...]

    def step(r, _):
        row = lambda ref: ref[pl.ds(r, 1)]
        out = policy_math.hybrid_gap_step_math(
            row(lo_ref), row(hi_ref),
            policy_math.decode_gaps(row(code_ref), cfg.n_bins),
            *(ref[...] for ref in state), cfg=cfg, gather=False, bin_axis=0)
        for ref, val in zip(state, out):
            ref[...] = val

    jax.lax.fori_loop(0, lo_ref.shape[0], step, None)


def hybrid_sweep_scan_pallas(it_lo, it_hi, code, state, cfg_i32, cfg_f32, *,
                             tile_apps: int = 1024, interpret: bool = False):
    """A chunk's whole hybrid-simulator scan for S configs in one call.

    Event columns are [width, n_apps], time on the first axis and apps on
    the last (lanes), and are shared by every config: ``code`` int32 gap
    codes (``policy_math.encode_gaps``: padding, first event, or the bin of
    the gap the event closes) and the gap's idle time as a float32 bracket
    ``it_lo <= it <= it_hi`` — classified and rounded on the host from the
    float64 clock, so the kernel's verdicts and histograms are the float64
    oracle's. ``state`` is the initial per-config state (``cum``
    [S, n_bins, n_apps] i32 *cumulative* in-bounds counts; ``oob``,
    ``cv_sum``, ``cv_sum_sq``, ``prewarm``, ``unload_at``, ``cold``,
    ``waste`` [S, 1, n_apps]; (``prewarm``, ``unload_at``) are the
    residency bounds decided after each app's previous event).
    ``cfg_i32`` [S, 4] / ``cfg_f32`` [S, 7] are the per-config knob blocks
    (column layout ``CFG_I32_COLS``/``CFG_F32_COLS``), delivered to SMEM
    via scalar prefetch.

    Grid: (S, n_apps / TA, width / TB) from :func:`scan_blocks`; configs
    and app tiles are parallel, the time axis is walked in order, and
    inside a block a loop takes the TB steps one row at a time. The apps
    are padded to the tile and the steps to the block with ``GAP_PAD``
    codes, which change no state. Returns the final (cum, oob, cv_sum,
    cv_sum_sq, prewarm, unload_at, cold, waste). ``interpret=True`` runs
    it off-TPU.
    """
    S, n_bins, n_apps = state[0].shape
    width = code.shape[0]
    if n_apps == 0 or S == 0 or width == 0:
        return tuple(state)
    TA, TB = scan_blocks(n_apps, width, n_bins, tile_apps)
    pad_apps, pad_steps = (-n_apps) % TA, (-width) % TB
    if pad_apps or pad_steps:
        pad = lambda x, fill, steps=0: jnp.pad(
            x, [(0, 0)] * (x.ndim - 2) + [(0, steps), (0, pad_apps)],
            constant_values=fill)
        code = pad(code, policy_math.GAP_PAD, pad_steps)
        it_lo, it_hi = pad(it_lo, 0.0, pad_steps), pad(it_hi, 0.0, pad_steps)
        state = tuple(pad(x, 0) for x in state)
    grid = (S, (n_apps + pad_apps) // TA, (width + pad_steps) // TB)
    shapes = [x.shape for x in state]

    # The config axis is squeezed out of every state block (``None``), so
    # a kernel instance sees [1, TA] vectors and an [n_bins, TA] tile: both
    # minor block dims are either whole or lane-aligned, as Mosaic needs.
    if TA % _PACKED_APPS == 0:
        # A tile of whole vregs takes the apps as rows of 128 lanes (a free
        # reshape): its per-app vectors [1, TA / 128, 128] are dense vregs,
        # ``cum`` [n_bins, TA / 128, 128] has the bins on a leading axis,
        # and the step's sums, maxima and minima over bins are vreg-wise.
        rows = lambda x: x.reshape(x.shape[:-1] + (x.shape[-1] // 128, 128))
        it_lo, it_hi, code = rows(it_lo), rows(it_hi), rows(code)
        state = tuple(rows(x) for x in state)
        R = TA // 128
        col = pl.BlockSpec((TB, R, 128), lambda s, i, t, *refs: (t, i, 0))
        vec = pl.BlockSpec((None, 1, R, 128),
                           lambda s, i, t, *refs: (s, 0, i, 0))
        mat = pl.BlockSpec((None, n_bins, R, 128),
                           lambda s, i, t, *refs: (s, 0, i, 0))
    else:
        col = pl.BlockSpec((TB, TA), lambda s, i, t, *refs: (t, i))
        vec = pl.BlockSpec((None, 1, TA), lambda s, i, t, *refs: (s, 0, i))
        mat = pl.BlockSpec((None, n_bins, TA),
                           lambda s, i, t, *refs: (s, 0, i))
    specs = [mat] + [vec] * _N_VECTORS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[col] * _N_COLUMNS + specs, out_specs=specs)
    outs = pl.pallas_call(
        _sweep_scan_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        # No input_output_aliases: with the state operands aliased to the
        # outputs, one-config chunks read wrong on a v5e at every bin
        # count, tile and time block (the compiled program gave two of
        # those operands one buffer); unaliased they are bit-exact.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hybrid_sweep_scan",
    )(cfg_i32, cfg_f32, it_lo, it_hi, code, *state)
    outs = [o.reshape(shape) for o, shape in zip(outs, shapes)]
    if pad_apps:
        outs = tuple(o[..., :n_apps] for o in outs)
    return tuple(outs)


def fused_hybrid_step_pallas(t_now, prev_t, cum, oob, cv_sum, cv_sum_sq,
                             prewarm, unload_at, cold, waste, *,
                             head_pct=5.0, tail_pct=99.0, margin=0.10,
                             bin_minutes=1.0, range_minutes=240.0,
                             cv_threshold=2.0, min_samples=5,
                             oob_threshold=0.5, standard_keep=240.0,
                             tile_apps: int = 1024, interpret: bool = False):
    """Single-config fused step: a one-column, one-config call of the scan
    kernel.

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
    state = (cum.T[None],) + tuple(
        lane(v) for v in (oob, cv_sum, cv_sum_sq, prewarm, unload_at, cold,
                          waste))
    outs = hybrid_sweep_scan_pallas(
        it[None], it[None], code[None], state, cfg_i32, cfg_f32,
        tile_apps=tile_apps, interpret=interpret)
    return (jnp.where(valid, t_now, prev_t),) + tuple(
        o[0].T if k == 0 else o[0, 0] for k, o in enumerate(outs))
