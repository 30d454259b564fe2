"""The hybrid-histogram policy's simulator scan — Pallas TPU kernel.

This is the paper's own hot loop (§4.2), adapted TPU-natively: every
invocation of every app updates its idle-time histogram, its CV
statistics and its (prewarm, keep-alive) windows, and the previous
windows give the event its warm/cold verdict and wasted memory time.

:func:`hybrid_sweep_scan_pallas` walks a chunk's event columns for S
stacked policy configurations (grid (S, n_apps / TA, width / TB)): each
(config, app tile)'s histogram state stays in VMEM while the kernel walks
the time axis, and reaches HBM once. The per-config knobs arrive as a
scalar-prefetched SMEM config block, so a new grid point is a new SMEM
row, not a recompile. The event columns are made on the host from the
float64 clock (``simulator._gap_columns``), so the kernel never sees a
clock.

Everything is [n_bins, TA] and [1, TA] arithmetic, apps on lanes — VPU
work. The decision formulas are NOT written here: the kernel body calls
the single-source step ``policy_math.hybrid_gap_step_math`` with
``gather=False`` (masked-reduction forms — compare-against-iota instead
of row gathers), which traces inside Pallas exactly as it does inside the
``lax.scan`` engines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import policy_math


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
# (60) and 240 bins x 512 lane-only (120) are the largest tiles run
# bit-exact on a v5e.
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
