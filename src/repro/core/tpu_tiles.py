"""TPU adaptation of the (j,h) DSE: BlockSpec tile selection.

The paper's constraint set maps 1:1 onto Pallas/MXU tiling:

  j  (input features/clock, j | d_in)   -> K-dimension tile bk (bk | d_in)
  h  (outputs multiplexed,  h | d_out)  -> N-dim grid trips: bn = d_out/h
  C = h*d_in/j reconfigurations          -> grid steps per output tile
  multi-pixel P                          -> M-dim tile bm (output positions
                                            per grid step; lanes=128)
  continuous flow  j/h >= r              -> tile's arithmetic intensity
                                            >= the layer's stream rate

Extra constraints that exist on TPU but not on the FPGA:
  * Block legality: a tile on the lane (minor) axis is a multiple of 128
    or the whole dim, a tile on the sublane axis a multiple of 8 or the
    whole dim — what Mosaic accepts for a BlockSpec.  Tiles only ever
    grow to become legal, so capacity (and Eq. 9) only rises.
  * VMEM capacity: the working set, counted in (sublane, 128-lane) tiles
    (a 3-channel minor dim occupies 128 lanes), must fit a fraction of
    the spec's ``vmem_bytes`` — the same number every ``pallas_call``
    asks for as its ``vmem_limit_bytes``.
  * Conv routes: the conv kernels hold a whole padded frame, split into
    its stride phases (``conv_geometry``), per grid step.  A conv whose
    frame cannot fit the budget (the lane-sparse 3-channel stems) is
    planned as im2col patches through the FCU matmul instead
    (``TileChoice.im2col``).  A grouped conv's grid step holds whole
    groups (``groups_per_block``) and never runs as im2col.
  * The 'scale' join (``kernels/se_scale``) is one multiply per feature,
    bound by memory: it streams blocks of whole rows by a channel tile,
    the largest that fit ``SCALE_BLOCK_BYTES``.

Two selection paths share those constraints:

  * ``select_tile``          — the *uniform* path: one rate (or none) for
    the whole network, the original BestRate search over the constrained
    HJ set.  This is what ``kernels/*/ops.py`` fall back to when no plan
    is threaded through.
  * ``select_tile_for_impl`` — the *rate-matched* path: maps one node's
    DSE choice (a ``core.dse.LayerImpl`` from ``plan_graph``) onto a
    concrete tiling.  ``j`` becomes the bk floor and ``d_out/h`` the bn
    floor; both grow only upward (to the nearest lane-aligned divisor),
    so the continuous-flow inequality ``j/h >= r`` survives the
    adjustment.  ``GraphPlan.kernel_plan`` calls this per node to build
    the ``ImplPlan`` table the executor (models/cnn.py) dispatches on.
"""

from __future__ import annotations

import dataclasses
import math
from fractions import Fraction
from typing import Callable, List, Optional, Sequence, Tuple

from .dse import LayerImpl
from .hw_specs import TPU_V5E, TPUSpec
from .rate import divisors


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """A concrete matmul-style tiling for one layer."""

    bm: int  # output-position (pixel) tile — the multi-pixel P
    bk: int  # contraction tile  (the paper's j)
    bn: int  # output-channel tile (d_out / h)
    grid_m: int
    grid_k: int  # the paper's C: weight "reconfigurations"
    grid_n: int
    vmem_bytes: int
    mxu_aligned: bool
    # conv only: run as im2col patches through the FCU matmul (the frame
    # does not fit VMEM); the contraction is then k_taps * d_in with
    # bk = d_in, and bm tiles the batch-flattened output pixels.
    im2col: bool = False

    @property
    def j(self) -> int:
        return self.bk

    def h(self, d_out: int) -> int:
        return max(1, d_out // self.bn)


# Share of ``TPUSpec.vmem_bytes`` the tile rule budgets working sets
# against; the rest is headroom for what the count leaves out (Mosaic's
# own temporaries and relayouts).
VMEM_FRACTION = 0.5

# Kinds whose nodes run a Pallas kernel on the rate-matched path: the
# arithmetic kinds, and the 'scale' join (squeeze-and-excitation).
KERNEL_KINDS = ("conv", "dwconv", "pointwise", "dense", "scale")

# Largest VMEM block (padded bytes) of the 'scale' join's trunk: a pass
# bound by memory streams best in blocks of a few MiB, long enough that
# each copy runs at full bandwidth and small enough that the grid has
# steps for the copies to overlap.
SCALE_BLOCK_BYTES = 2 * 1024**2


def vmem_budget(spec: TPUSpec, fraction: float = VMEM_FRACTION) -> int:
    return int(spec.vmem_bytes * fraction)


def _align_ok(x: int, want: int) -> bool:
    return x % want == 0 or x < want


def legal_tiles(dim: int, align: int) -> List[int]:
    """Divisors of ``dim`` a block may take along an axis aligned to
    ``align``: the multiples of ``align``, and ``dim`` itself."""
    return [d for d in divisors(dim) if d % align == 0 or d == dim]


def fit_bm(m: int, want: int, sublanes: int = 8) -> int:
    """Largest legal pixel tile <= ``want`` that divides ``m``: a
    multiple of ``sublanes``, else the whole ``m``."""
    cands = [d for d in legal_tiles(m, sublanes) if d <= want]
    return max(cands) if cands else m


def sublane_tile(dtype_bytes: int, spec: TPUSpec) -> int:
    """Rows of one (sublane, 128-lane) VMEM tile: 8 of 4-byte values;
    packed dtypes hold 16 or 32."""
    return spec.sublanes * max(1, 4 // dtype_bytes)


def mxu_width(wo: int, dtype_bytes: int, spec: TPUSpec) -> int:
    """Output columns a whole-frame conv streams per row through the MXU:
    ``wo`` rounded up to the sublane tile.  Merging the rows of a
    ``[rows, W, C]`` tap window into MXU rows is then tile-aligned; at a
    width that is not a tile multiple (ResNet's 7, 14, 28) Mosaic
    relayouts every window instead (docs/kernels.md)."""
    sub = sublane_tile(dtype_bytes, spec)
    return -(-wo // sub) * sub


def padded_bytes(shape: Sequence[int], dtype_bytes: int, spec: TPUSpec) -> int:
    """VMEM bytes an array of ``shape`` occupies: the minor dim rounds up
    to the 128 lanes, the second-minor to the sublane tile
    (``sublane_tile``)."""
    *lead, rows, cols = (1, *shape) if len(shape) == 1 else tuple(shape)
    sub = sublane_tile(dtype_bytes, spec)
    n = -(-rows // sub) * sub * -(-cols // spec.lanes) * spec.lanes
    for d in lead:
        n *= d
    return n * dtype_bytes


def matmul_vmem_bytes(
    bm: int, bk: int, bn: int, *, dtype_bytes: int, spec: TPUSpec
) -> int:
    """Working set of one FCU grid step: the x (bm, bk), w (bk, bn) and
    out (bm, bn) blocks double-buffered, plus the f32 accumulator."""
    blocks = sum(
        padded_bytes(s, dtype_bytes, spec) for s in ((bm, bk), (bk, bn), (bm, bn))
    )
    return 2 * blocks + padded_bytes((bm, bn), 4, spec)


def same_pads(size: int, k: int, s: int) -> Tuple[int, Tuple[int, int]]:
    """Output size and (before, after) padding of a SAME window."""
    out = -(-size // s)
    total = max(0, (out - 1) * s + k - size)
    return out, (total // 2, total - total // 2)


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """A SAME-padded conv input split into its stride phases (§II-E).

    Padded row ``y`` is row ``y // s`` of phase ``y % s``, so tap
    ``(dy, dx)`` of every output reads one contiguous ``out_hw`` window
    of phase ``(dy % s, dx % s)`` at offset ``(dy // s, dx // s)`` — the
    stride never reaches the kernel's loads.  Only the phases some tap
    reads are kept (a 1x1 stride-2 conv keeps one of four): skipped
    windows are never materialized.

    ``wo_mxu`` >= ``out_hw[1]`` is the width of every tap window
    (``mxu_width``): the right padding adds zero columns until each
    window of that width lies inside its phase.  Outputs past
    ``out_hw[1]`` are computed and discarded.
    """

    out_hw: Tuple[int, int]
    # ((top, bottom), (left, right)): SAME, then up to a stride multiple
    pads: Tuple[Tuple[int, int], Tuple[int, int]]
    stride: int
    phases: Tuple[Tuple[int, int], ...]
    phase_hw: Tuple[int, int]
    wo_mxu: int

    def tap(self, dy: int, dx: int) -> Tuple[int, int, int]:
        """(phase index, row offset, column offset) of tap (dy, dx)."""
        s = self.stride
        return self.phases.index((dy % s, dx % s)), dy // s, dx // s


def conv_geometry(
    in_hw: Tuple[int, int],
    kernel: Tuple[int, int],
    stride: int,
    wo_mxu: Optional[int] = None,
) -> ConvGeometry:
    """``wo_mxu`` (the true output width without it) widens every tap
    window, and the phases with it, by ``wo_mxu - Wo`` columns."""
    (h, w), (kh, kw), s = in_hw, kernel, stride
    ho, (pt, pb) = same_pads(h, kh, s)
    wo, (pl, pr) = same_pads(w, kw, s)
    wo_mxu = wo if wo_mxu is None else wo_mxu
    assert wo_mxu >= wo, f"window width {wo_mxu} < output width {wo}"
    hq = -(-(h + pt + pb) // s)
    wq = -(-(w + pl + pr) // s) + wo_mxu - wo
    phases = sorted({(dy % s, dx % s) for dy in range(kh) for dx in range(kw)})
    return ConvGeometry(
        out_hw=(ho, wo),
        pads=((pt, hq * s - h - pt), (pl, wq * s - w - pl)),
        stride=s,
        phases=tuple(phases),
        phase_hw=(hq, wq),
        wo_mxu=wo_mxu,
    )


def conv_frame_vmem_bytes(
    geo: ConvGeometry,
    kernel: Tuple[int, int],
    bci: int,
    bco: int,
    *,
    dtype_bytes: int,
    spec: TPUSpec,
    depthwise: bool = False,
    frames: int = 1,
    group_in: Optional[int] = None,
) -> int:
    """Working set of one whole-frame conv grid step (kpu_conv, or
    dw_conv with ``depthwise``) holding ``frames`` frames: the
    phase-split input, weight and output blocks double-buffered, the f32
    accumulator and one f32 tap product, and one loaded tap window, the
    last three ``geo.wo_mxu`` columns wide.  Every term but the weight
    block scales with ``frames``.  A grouped conv's weight block reads
    ``group_in`` input channels (its group width), not ``bci``."""
    (hq, wq), (ho, wo), (kh, kw) = geo.phase_hw, geo.out_hw, kernel
    x = padded_bytes((len(geo.phases), hq, wq, bci), dtype_bytes, spec)
    w_in = bci if group_in is None else group_in
    w_shape = (kh, kw, bci) if depthwise else (kh, kw, w_in, bco)
    w = padded_bytes(w_shape, dtype_bytes, spec)
    o = padded_bytes((ho, wo, bco), dtype_bytes, spec)
    acc = padded_bytes((ho, geo.wo_mxu, bco), 4, spec)
    win = padded_bytes((ho, geo.wo_mxu, bci), dtype_bytes, spec)
    return 2 * (frames * (x + o) + w) + frames * (2 * acc + win)


def group_blocks(groups: int, group_in: int, group_out: int, lanes: int) -> List[int]:
    """Groups a grouped conv's grid step may hold (``gpb``): the
    divisors of ``groups`` whose input and output channel blocks are
    legal lane blocks (a multiple of ``lanes``, or every channel)."""
    return [
        g
        for g in divisors(groups)
        if g == groups or (g * group_in % lanes == 0 and g * group_out % lanes == 0)
    ]


def groups_per_block(
    groups: int,
    group_in: int,
    group_out: int,
    floor_out: int,
    fits: Callable[[int], bool],
    lanes: int,
) -> int:
    """Whole groups per grid step of a grouped conv: the fewest legal
    ones (``group_blocks``) whose output block reaches ``floor_out``
    channels, narrowed to fewer while the step's working set does not
    fit (``fits``); the fewest legal when none fits."""
    legal = group_blocks(groups, group_in, group_out, lanes)
    want = next((g for g in legal if g * group_out >= floor_out), groups)
    fitting = [g for g in legal if g <= want and fits(g)]
    return max(fitting) if fitting else legal[0]


def conv_block_frames(
    n: int,
    out_px: int,
    bm: Optional[int],
    fits: Optional[Callable[[int], bool]] = None,
) -> int:
    """Frames a whole-frame conv grid step holds: the largest divisor of
    the ``n`` frames whose output pixels stay within the pixel tile
    ``bm`` (the plan's multi-pixel P) and, given ``fits``, whose working
    set fits.  One frame when ``bm`` is None or below a frame."""
    cap = max(bm or 0, out_px)
    return max(
        d
        for d in divisors(n)
        if d == 1 or (d * out_px <= cap and (fits is None or fits(d)))
    )


def scale_tile(
    hw: Tuple[int, int],
    c: int,
    floor: int,
    *,
    dtype_bytes: int,
    spec: TPUSpec,
) -> Tuple[int, int, int]:
    """(rows, channel tile, padded block bytes) of the 'scale' join's
    blocks ``[1, rows, W, bc]``: the largest block within
    ``SCALE_BLOCK_BYTES`` over the row counts that divide H and the legal
    channel tiles >= ``floor`` (the node's j: tiles only grow), ties to
    the wider channel tile; the smallest block when none fits."""
    h, w = hw
    cands = [
        (padded_bytes((bh, w, bc), dtype_bytes, spec), bc, bh)
        for bh in divisors(h)
        for bc in legal_tiles(c, spec.lanes)
        if bc >= min(floor, c)
    ]
    fits = [t for t in cands if t[0] <= SCALE_BLOCK_BYTES]
    nbytes, bc, bh = max(fits) if fits else min(cands)
    return bh, bc, nbytes


def select_tile(
    m: int,
    d_in: int,
    d_out: int,
    *,
    rate: Optional[Fraction] = None,
    dtype_bytes: int = 2,
    spec: TPUSpec = TPU_V5E,
    vmem_fraction: float = VMEM_FRACTION,
) -> TileChoice:
    """Choose (bm, bk, bn) for an [m, d_in] x [d_in, d_out] product.

    The candidate set is the paper's HJ set (divisor-constrained, and
    restricted to legal block dims); the BestRate criterion becomes:
    smallest tile whose throughput covers ``rate`` (features per MXU
    pass), tie-broken toward large h (big accumulation per output tile
    => fewer HBM round-trips — the compressor-tree argument, TPU
    edition).  With ``rate=None`` the highest-intensity aligned tile is
    chosen.
    """
    budget = vmem_budget(spec, vmem_fraction)
    lane = spec.lanes  # 128
    sub = spec.sublanes  # 8

    def ws(bm, bk, bn):
        return matmul_vmem_bytes(bm, bk, bn, dtype_bytes=dtype_bytes, spec=spec)

    def capped(dim):
        tiles = legal_tiles(dim, lane)
        return [t for t in tiles if t <= 2048] or tiles[:1]

    best: Optional[Tuple] = None
    for bk in capped(d_in):
        for bn in capped(d_out):
            h = d_out // bn
            # continuous-flow feasibility (Eq. 9 analogue)
            if rate is not None and Fraction(bk, max(1, h)) < rate:
                continue
            # pick bm: as many output rows as fit VMEM, ideally lane-aligned
            bm = min(m, 512)
            while bm > sub and ws(bm, bk, bn) > budget:
                bm //= 2
            if ws(bm, bk, bn) > budget:
                continue
            # strict alignment: a dim is aligned if the tile is a lane
            # multiple OR the whole dim is too small to ever align.
            aligned = (bk % lane == 0 or d_in < lane) and (
                bn % lane == 0 or d_out < lane
            )
            # TPU tie-break (the compressor-tree argument, MXU edition):
            # deep K accumulation per pass (big bk), output tile wide
            # enough to fill lanes but small enough to keep h large
            # (many output tiles re-using the resident input block).
            bn_pref = -abs(bn - 2 * lane)
            score = (aligned, bk, bn_pref, bm)
            if best is None or score > best[0]:
                best = (score, bm, bk, bn)
    if best is None:
        # degenerate fallback: the smallest legal tiles
        bm, bk, bn = min(m, sub), capped(d_in)[0], capped(d_out)[0]
    else:
        _, bm, bk, bn = best
    return TileChoice(
        bm=bm,
        bk=bk,
        bn=bn,
        grid_m=math.ceil(m / bm),
        grid_k=max(1, d_in // bk),
        grid_n=max(1, d_out // bn),
        vmem_bytes=ws(bm, bk, bn),
        mxu_aligned=_align_ok(bk, lane) and _align_ok(bn, lane),
    )


# ==========================================================================
# Rate-matched per-layer path: one node's DSE choice -> one tiling
# ==========================================================================


def plan_dim_tile(dim: int, floor: int, lane: int) -> int:
    """Smallest legal tile of ``dim`` (a lane-multiple divisor, or
    ``dim`` itself) that is >= ``floor``.

    This is the deterministic (j, h) -> (bk, bn) adjustment rule: growing
    a tile dimension only ever *adds* capacity, so the continuous-flow
    inequality the DSE established (Eq. 9) survives the MXU alignment.
    """
    for d in legal_tiles(dim, lane):
        if d >= floor:
            return d
    return dim


def pinned_bm(
    m: int, bk: int, bn: int, *, dtype_bytes: int, budget: int, spec: TPUSpec = TPU_V5E
) -> int:
    """Largest legal divisor of ``m`` (capped at 512) whose working set
    fits the VMEM budget — the batch-pinned pixel tile.

    Because the result *divides* m, the runtime ``fit_bm`` re-fit in the
    fcu adapter is the identity: the executed bm equals the planned bm
    exactly (the ROADMAP's "plan-aware bm" item).  Falls back to the
    smallest legal divisor when even that overflows (degenerate
    budgets).
    """
    cands = [d for d in legal_tiles(m, spec.sublanes) if d <= 512] or [m]
    for bm in reversed(cands):
        if matmul_vmem_bytes(bm, bk, bn, dtype_bytes=dtype_bytes, spec=spec) <= budget:
            return bm
    return cands[0]


def select_tile_for_impl(
    impl: LayerImpl,
    *,
    dtype_bytes: int = 4,
    spec: TPUSpec = TPU_V5E,
    vmem_fraction: float = VMEM_FRACTION,
    batch: Optional[int] = None,
) -> TileChoice:
    """Map one node's DSE implementation onto its Pallas tiling.

    This is the per-layer half of the paper's claim: the tile each kernel
    runs with is derived from *that node's* ``(j, h)`` and decimation-
    adjusted demand, not from one global rate.  The mapping:

      * conv / pointwise / dense — ``bk`` = smallest legal divisor of
        ``d_in`` >= j; ``bn`` = smallest legal divisor of ``d_out`` >=
        ``d_out / h``; ``bm`` shrinks from 512 to fit VMEM.
      * conv additionally checks its whole-frame working set
        (``conv_frame_vmem_bytes``); when it exceeds the budget the conv
        is planned as im2col through the FCU (``im2col=True``, ``bk``
        grown to the whole ``d_in`` so the contraction k_taps * d_in is
        one legal block).
      * a grouped conv (``groups`` > 1) — ``_grouped_conv_tile``: whole
        groups a grid step, narrowed to fit VMEM, never im2col.
      * dwconv — the channel tile ``bk`` = smallest legal divisor of
        ``d_in`` >= j (h = 1 per §II-B: the channel multiplier replaces
        d_out); ``bn`` is reported as 1.
      * scale — ``scale_tile``: ``bk`` the channel tile (>= j), ``bm``
        the pixels of one block (rows x W), ``bn`` reported as 1.

    When the impl's own (j, h) satisfy Eq. 9 — always true for scheme
    'ours' — the resulting tile provably still satisfies
    ``bk / (d_out // bn) >= r_phase`` (every adjustment only grows
    capacity); this is re-checked here and the executor re-asserts the
    executed tile against the plan at apply time.  [11] impls carry
    bookkeeping (j, h) decoupled from their capacity formula (and can be
    outright infeasible); those are mapped best-effort with no
    feasibility claim.

    VMEM: working sets are counted in padded (sublane, lane) tiles
    against ``spec.vmem_bytes * vmem_fraction``.  The matmul routes
    shrink bm to fit (flooring at the smallest legal tile); the dwconv
    frame is reported, not enforced (it fits for every family at
    224x224; a larger frame needs spatial blocking, a ROADMAP item).

    ``batch`` pins the pixel tile to the serving shape (the ROADMAP's
    "plan-aware bm" item): with the micro-batch size known, m becomes
    ``batch * out_px`` and bm is chosen as a *divisor* of that runtime m
    (``pinned_bm``), so the kernels' batch-flattened re-fit keeps the
    planned value exactly instead of merely bounding it.  Without
    ``batch`` the m-agnostic behaviour is unchanged: bm only bounds the
    runtime re-fit.
    """
    lay = impl.layer
    if lay.kind not in KERNEL_KINDS:
        raise ValueError(
            f"{lay.name}: kind {lay.kind!r} has no kernel tiling "
            f"(non-arithmetic nodes carry no tile in an ImplPlan)"
        )
    lane = spec.lanes
    m = lay.out_hw[0] * lay.out_hw[1]
    if batch is not None:
        if batch < 1:
            raise ValueError(f"{lay.name}: batch must be >= 1, got {batch}")
        m *= batch
    r_phase = impl.demand / impl.p_raw
    budget = vmem_budget(spec, vmem_fraction)

    if lay.kind == "scale":
        bh, bc, block = scale_tile(
            lay.in_hw, lay.d_in, impl.j, dtype_bytes=dtype_bytes, spec=spec
        )
        gate = padded_bytes((1, bc), dtype_bytes, spec)
        return TileChoice(
            bm=bh * lay.in_hw[1],
            bk=bc,
            bn=1,
            grid_m=lay.in_hw[0] // bh,
            grid_k=lay.d_in // bc,
            grid_n=1,
            vmem_bytes=2 * (2 * block + gate),
            mxu_aligned=_align_ok(bc, lane),
        )

    if lay.kind == "dwconv":
        bc = plan_dim_tile(lay.d_in, min(impl.j, lay.d_in), lane)
        geo = conv_geometry(lay.in_hw, lay.kernel, lay.stride[0])
        return TileChoice(
            bm=m,
            bk=bc,
            bn=1,
            grid_m=1,
            grid_k=max(1, lay.d_in // bc),
            grid_n=1,
            vmem_bytes=conv_frame_vmem_bytes(
                geo,
                lay.kernel,
                bc,
                bc,
                dtype_bytes=dtype_bytes,
                spec=spec,
                depthwise=True,
            ),
            mxu_aligned=_align_ok(bc, lane),
        )

    if lay.kind == "conv" and lay.groups > 1:
        return _grouped_conv_tile(impl, m, batch, dtype_bytes, spec, budget)

    bk = plan_dim_tile(lay.d_in, min(impl.j, lay.d_in), lane)
    bn = plan_dim_tile(lay.d_out, max(1, lay.d_out // impl.h), lane)
    frame_bytes = None
    im2col = False
    if lay.kind == "conv":
        geo = conv_geometry(lay.in_hw, lay.kernel, lay.stride[0])
        frame_bytes = conv_frame_vmem_bytes(
            geo, lay.kernel, bk, bn, dtype_bytes=dtype_bytes, spec=spec
        )
        im2col = frame_bytes > budget
        if im2col:
            bk = lay.d_in
    # the matmul contraction block: im2col patches carry every tap
    k_block = bk * lay.k_taps if im2col else bk

    def ws(bm):
        return matmul_vmem_bytes(bm, k_block, bn, dtype_bytes=dtype_bytes, spec=spec)

    bm = _pixel_tile(m, k_block, bn, batch, dtype_bytes, budget, spec)
    h_tile = max(1, lay.d_out // bn)
    jh_holds_eq9 = Fraction(impl.j, max(1, impl.h)) >= r_phase
    if jh_holds_eq9 and Fraction(bk, h_tile) < r_phase:
        raise AssertionError(  # unreachable: growth preserves Eq. 9
            f"{lay.name}: tile (bk={bk}, h={h_tile}) lost continuous flow "
            f"for per-phase rate {r_phase}"
        )
    return TileChoice(
        bm=bm,
        bk=bk,
        bn=bn,
        grid_m=math.ceil(m / bm),
        grid_k=max(1, lay.d_in // bk),
        grid_n=max(1, lay.d_out // bn),
        vmem_bytes=ws(bm) if frame_bytes is None or im2col else frame_bytes,
        mxu_aligned=_align_ok(bk, lane) and _align_ok(bn, lane),
        im2col=im2col,
    )


def _pixel_tile(
    m: int,
    k: int,
    bn: int,
    batch: Optional[int],
    dtype_bytes: int,
    budget: int,
    spec: TPUSpec,
) -> int:
    """A conv's pixel tile ``bm``: pinned to the batch (``pinned_bm``)
    where the plan has one, else the largest power-of-two halving of 512
    whose ``[bm, k] x [k, bn]`` working set fits the budget."""
    if batch is not None:
        return pinned_bm(m, k, bn, dtype_bytes=dtype_bytes, budget=budget, spec=spec)
    bm = min(m, 512)
    while bm > spec.sublanes and matmul_vmem_bytes(
        bm, k, bn, dtype_bytes=dtype_bytes, spec=spec
    ) > budget:
        bm //= 2
    return bm


def _grouped_conv_tile(
    impl: LayerImpl,
    m: int,
    batch: Optional[int],
    dtype_bytes: int,
    spec: TPUSpec,
    budget: int,
) -> TileChoice:
    """A grouped conv's whole-frame tile: each grid step holds ``gpb``
    whole groups (``groups_per_block``), ``bk = gpb * d_in/G`` input and
    ``bn = gpb * d_out/G`` output channels, its contraction one step
    (``grid_k`` 1).  ``bn`` reaches the DSE's ``d_out / h`` floor where
    the frame fits VMEM, and narrows to fewer groups where it does not;
    a grouped conv never runs as im2col.  The tile's capacity ``d_in *
    bn / d_out`` >= ``G * j / h`` (j <= d_in/G, bn >= d_out/h): Eq. 9
    survives."""
    lay = impl.layer
    groups, g_in = lay.groups, lay.group_in
    g_out = lay.d_out // groups
    geo = conv_geometry(lay.in_hw, lay.kernel, lay.stride[0])

    def frame_bytes(gpb):
        return conv_frame_vmem_bytes(
            geo, lay.kernel, gpb * g_in, gpb * g_out,
            dtype_bytes=dtype_bytes, spec=spec, group_in=g_in,
        )

    gpb = groups_per_block(
        groups, g_in, g_out, max(1, lay.d_out // impl.h),
        lambda g: frame_bytes(g) <= budget, spec.lanes,
    )
    bk, bn = gpb * g_in, gpb * g_out
    bm = _pixel_tile(m, bk, bn, batch, dtype_bytes, budget, spec)
    return TileChoice(
        bm=bm,
        bk=bk,
        bn=bn,
        grid_m=math.ceil(m / bm),
        grid_k=1,
        grid_n=groups // gpb,
        vmem_bytes=frame_bytes(gpb),
        mxu_aligned=_align_ok(bk, spec.lanes) and _align_ok(bn, spec.lanes),
    )
