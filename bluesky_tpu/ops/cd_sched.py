"""Sparse segment-scheduled CD&R: near-physics-floor pair enumeration.

The full-grid Pallas kernel (``ops/cd_pallas.py``) visits every
[block, block] tile of the N x N pair space and skips unreachable ones.
Round-3 profiling on the v5e showed that at N=100k continental this costs
~120 ms per CD interval: ~82 ms of pair math over 7.6e8 block-granular
pairs and ~38 ms of pure grid+DMA overhead across 38k grid programs,
while the *physics floor* — pairs within ``rpz + tlookahead*(gs_i+gs_j)``
of each other, the exact conservative bound of the reference C++
prefilter idea (``bluesky/traffic/asas/src_cpp/asas.hpp:24-27``) — is
only ~5.5e7 pairs.  This module restructures the schedule so both costs
approach their floors:

* **Stripe sort** (``stripe_sort_dest``): aircraft are ordered by
  latitude stripe (stripe height >= the reach radius), longitude within
  the stripe, and each stripe is padded to a block boundary.  Unlike the
  Morton curve, this guarantees the reachable columns of any row block
  form at most ONE contiguous run per lat-reachable stripe (the lon
  window in a lon-sorted stripe is an interval), i.e. ~3 runs instead of
  Morton's fragmented ~7-21.

* **Segment schedule** (``build_windows``): from the exact block
  reachability matrix (``cd_tiled.block_reachability`` — unchanged
  bound, so the skip stays exact), each row's reachable columns are
  covered by at most ``S_cap`` contiguous segments of at most ``Wmax``
  blocks.  Rows needing more (dense geometries where everyone reaches
  everyone — e.g. the regional benchmark circle) are OVERFLOW rows,
  covered exactly by the old full-grid kernel restricted to those rows
  (``cd_pallas.full_grid_pass``), and the row-disjoint outputs merged.

* **Segment kernel** (``_sched_kernel``): ONE grid program per ownship
  block (grid = (nb,), not (nb, nb/cpp)): the program loops over its
  prefetched (start, len) segments, each an ``pl.Element``-indexed
  contiguous [Wmax, 16, block] slab DMA — no per-tile grid step, no
  gathers.  Tile math is byte-identical to the other backends
  (``cd_pallas._tile_pairs`` traced into this kernel), so results match
  the dense oracle exactly like the tiled/pallas paths do.

Semantics: identical reductions to ``cd_tiled.detect_resolve_tiled`` —
the schedule only changes WHICH provably-conflict-free tiles are
skipped, never the computed pairs' math.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cd_pallas, cd_tiled
from .cd_pallas import _ACC_NEUTRAL, _FIELDS, _IDX, _init_accumulators
from .cd_tiled import RowConflictData, block_reachability, precompute_trig

#: slab rows padded 13 -> 16 so a dynamic leading-index of a
#: [Wmax, _NFP, block] VMEM ref lands on a whole-vreg boundary
#: (16*block is a multiple of the (8, 128) vreg for block >= 128)
_NFP = 16


def _element_spec(shape, imap):
    """Element-indexed BlockSpec: ``pl.Element`` dims give the index map
    element (slab-row) granularity for the dynamic ``(start, len)``
    window DMAs."""
    return pl.BlockSpec(tuple(pl.Element(s) for s in shape), imap,
                        memory_space=pltpu.VMEM)

#: max grid rows per pallas_call.  The scalar-prefetched worklist is
#: laid out in SMEM at 512 B per grid row (128 int32 lanes), and SMEM
#: holds 1 MiB: a call of more than ~2000 rows cannot be compiled.
#: Measured on jax 0.9.0 / libtpu 0.0.34, TPU v5e (PR 21): 1986 rows
#: (N=500k) compile and run as ONE call; 3938 rows (N=1M) are refused
#: with RESOURCE_EXHAUSTED "prefetched SMEM operand 0", 2,019,328 B
#: against 1,048,576 B.  1408 rows = 360k aircraft stays inside with
#: room for the rest of SMEM (see the row-split note in
#: detect_resolve_sched).
_MAX_ROWS = 1408

#: above this many rows, skip the cross-equator kernel specialization
#: (one variant instead of two halves compile time; huge fleets
#: usually straddle the equator anyway)
_ONE_VARIANT_ROWS = 1024


#: segments a row's windows may take before the full-grid fallback
#: covers it, and the blocks one segment may span: the interval
#: (``detect_resolve_sched``) and its counters (``schedule_counts``)
#: read the same two.
S_CAP = 6
WMAX = 16


def padded_size(n, block=256, extra=32):
    """Total slots of the padded stripe-sorted layout for n aircraft."""
    block = min(block, 256)
    return (-(-n // block) + extra) * block


def spatial_layout(n, block=256, ndev=1, extra=32):
    """Padded-layout parameters for the spatial domain-decomposition
    mode: pick the extra-block count (<= ``extra``, >= 2) so the padded
    block count divides evenly into ``ndev`` contiguous device stripes.
    Returns ``(extra_eff, nb, nb_local, n_tot)``.  Shrinking ``extra``
    only makes the latitude stripes taller (stripe height is
    ``max(reach, span/(extra-1))``), never incorrect — reachability is
    recomputed from true positions every interval."""
    block = min(block, 256)
    nb0 = -(-n // block)
    extra_eff = extra - ((nb0 + extra) % ndev)
    if extra_eff < 2:
        extra_eff += ndev
    nb = nb0 + extra_eff
    return extra_eff, nb, nb // ndev, nb * block


def slot_inverse(perm, n, n_tot, fill=-1):
    """[n_tot + 1] int32 lookup: padded-slot id -> caller index
    (``fill`` for empty slots).  ``perm`` is the ``stripe_sort_dest``
    destination table (caller i -> slot perm[i]); the +1 row makes
    clipped sentinel lookups safe.  Single source of truth for the
    sorted-space -> caller-space translation (partner-table remaps in
    core/asas)."""
    return jnp.full((n_tot + 1,), fill, jnp.int32).at[
        jnp.clip(perm, 0, n_tot)].set(jnp.arange(n, dtype=jnp.int32))


def partners_to_caller(perm, partners_s, n, n_tot):
    """Translate a sorted-space partner table ``partners_s``
    [n_tot, K] into a caller-space [n, K] table (-1 = empty), the
    composition the sparse SSD-resolve branch performs: partner slot
    ids map through ``slot_inverse`` and each caller row i reads the
    row of its own slot ``perm[i]``.  Shared by core/asas (resolver
    partner plumbing) and obs/scanstats (min-separation fold)."""
    inv = slot_inverse(perm, n, n_tot)
    pc = jnp.where(partners_s >= 0,
                   inv[jnp.clip(partners_s, 0, n_tot)], -1)
    return pc[jnp.clip(perm, 0, n_tot - 1), :]


def reach_threshold_m(gs, active, tlookahead, rpz):
    """Worst-case reach radius [m]: the exact conservative CD bound at
    fleet-max closing speed (used to size stripes; per-block thresholds
    in the reachability matrix stay per-block)."""
    gsmax = jnp.max(jnp.where(active, gs, 0.0))
    return rpz + tlookahead * 2.0 * gsmax


#: per-stripe altitude layering (cruise bands + one "climber" bucket
#: collecting |vs| > _CLIMB_VS aircraft so they cannot poison a cruise
#: block's vsmax in the vertical reachability bound).  Measured at
#: N=100k CONTINENTAL the layering INCREASES scheduled pairs (5.4e8 vs
#: 3.4e8: thinning the lat-lon buckets makes blocks longitude-fat and
#: the +block-span dilation outweighs the vertical selectivity) — but
#: in DENSE geometries (the reference's 230 nm circle) the horizontal
#: windows are saturated anyway, so altitude-homogeneous blocks let the
#: exact vertical term of block_reachability prune the tile set by the
#: cruise-band fraction.  The caller (core/asas.refresh_spatial_sort)
#: therefore passes ``n_layers > 0`` only when its density estimate
#: says horizontal windows can no longer discriminate.
_CLIMB_VS = 1.0     # [m/s]


def stripe_sort_dest(lat, lon, gs, active, thresh_m, block, extra,
                     alt=None, vs=None, n_layers=0, spread_pad=False):
    """See module docstring; ``n_layers`` may be an int, or "auto" to
    gate the per-stripe altitude layering ON DEVICE from the density
    estimate (no host sync; what a pull costs on this machine is not
    measured).

    ``spread_pad`` (the SPATIAL layout): distribute the layout's free
    padding blocks between stripes proportionally to cumulative active
    count instead of leaving them all at the end — the map from
    aircraft fraction to block position becomes ~affine, so a
    contiguous equal-block device split gets ~equal aircraft counts
    (without it, low-occupancy layouts put every occupied block at the
    front and the first devices overflow their caller shards).  The
    single-chip schedule is indifferent to WHERE padding sits (empty
    blocks are skipped exactly), so this only shapes device balance."""
    return _stripe_sort_dest_impl(lat, lon, gs, active, thresh_m, block,
                                  extra, alt, vs, n_layers,
                                  spread_pad=spread_pad)


def _auto_layers(lat, lon, alt, active, thresh_m):
    """Traced layering decision: mean reachable-neighbor count over the
    active bounding box; dense (>3000 — horizontal windows saturated,
    e.g. the 230 nm circle at 100k) -> ~500 m bands, else 0."""
    act = active
    big = jnp.asarray(1e9, lat.dtype)
    n_act = jnp.sum(act)
    lat_a = jnp.where(act, lat, jnp.nan)
    lon_a = jnp.where(act, lon, jnp.nan)
    alt_a = jnp.where(act, alt, jnp.nan)
    ptp = lambda a: jnp.nanmax(a) - jnp.nanmin(a)
    dlat_km = jnp.maximum(ptp(lat_a), 0.3) * 111.0
    coslat = jnp.maximum(jnp.cos(jnp.radians(
        jnp.nanmax(jnp.abs(lat_a)))), 0.05)
    dlon_km = jnp.maximum(ptp(lon_a), 0.3) * 111.0 * coslat
    reach_km = thresh_m / 1000.0
    nbrs = n_act * jnp.pi * reach_km ** 2 / (dlat_km * dlon_km)
    # ~500 m bands: above the cruise-block vertical reach (~340 m),
    # thin enough that own+-1-band coverage prunes hard (measured 2.3x
    # fewer scheduled pairs on the 230 nm circle at N=100k)
    l0 = jnp.clip(ptp(alt_a) / 500.0, 0, 16).astype(jnp.int32)
    use = (nbrs > 3000.0) & (l0 >= 2) & (n_act > 0)
    return jnp.where(use, l0, 0)


def _stripe_sort_dest_impl(lat, lon, gs, active, thresh_m, block, extra,
                           alt=None, vs=None, n_layers=0,
                           spread_pad=False):
    """Padded stripe-major sort: per-aircraft destination slots.

    Returns ``dest`` [n] int32: aircraft i occupies padded slot dest[i]
    in a layout of ``n + extra*block`` slots where each latitude stripe
    starts on a block boundary (so no row block straddles two stripes —
    straddle blocks have airspace-wide bounding boxes that blow up their
    column windows).  Stripe height is the larger of the reach radius
    and what caps the stripe count at ``extra - 1`` (so the padding
    always fits); inactive aircraft sort into the last stripe.

    With ``alt``/``vs``, aircraft are sub-ordered inside each stripe by
    altitude band (cruisers) with climbers/descenders in a separate
    bucket, then longitude — so blocks are homogeneous in altitude and
    the vertical term of ``block_reachability`` can skip whole
    flight-level bands.  Bucket boundaries are soft: they only shape
    block contents, never correctness (the reachability bound reads the
    true per-block ranges every interval).

    Like the Morton permutation this is refreshed only every
    ``sort_every`` CD intervals (or once a chunk, whichever is longer)
    — ANY staleness is exact because block reachability is recomputed
    from true positions each interval; staleness only loosens the
    windows.  By how much depends on ``thresh_m``: with stripes exactly
    as tall as the reach radius a block reaches its own stripe and the
    two beside it on the fresh layout, and the stripe two over as soon
    as the blocks' boxes have spread by a few kilometres; the rows
    whose runs then split into more than ``S_CAP`` segments send the
    whole interval through the full-grid fallback (PERF.md, PR 28:
    what the chip showed of it).  So a caller that keeps the layout for
    ``life_s`` seconds passes the reach radius plus the drift of that
    lifetime, ``2 * gsmax * life_s`` (core/asas._sparse_sort_refresh):
    slightly taller stripes, a schedule that stays what it was made.
    """
    n = lat.shape[0]
    act = active
    big = jnp.asarray(1e9, lat.dtype)
    latmin = jnp.min(jnp.where(act, lat, big))
    latmax = jnp.max(jnp.where(act, lat, -big))
    any_act = jnp.any(act)
    latmin = jnp.where(any_act, latmin, 0.0)
    latmax = jnp.where(any_act, latmax, 1.0)
    span = jnp.maximum(latmax - latmin, 1e-6)
    # [m] -> [deg]: 1 deg of great-circle is >= 110 km everywhere, so
    # thresh/110000 over-estimates the needed stripe height -> safe.
    h = jnp.maximum(jnp.maximum(thresh_m * 1.05 / 110000.0,
                                span / (extra - 1)), 0.05)
    s = jnp.clip(jnp.floor((lat - latmin) / h), 0, extra - 2).astype(jnp.int32)
    s = jnp.where(act, s, extra - 1)

    if alt is None or (n_layers != "auto" and int(n_layers) == 0):
        nl = jnp.int32(0)
        layer = jnp.zeros((n,), jnp.int32)
    else:
        nl = _auto_layers(lat, lon, alt, active, thresh_m) \
            if n_layers == "auto" else jnp.int32(n_layers)
        amin = jnp.where(any_act, jnp.min(jnp.where(act, alt, big)), 0.0)
        amax = jnp.where(any_act, jnp.max(jnp.where(act, alt, -big)), 1.0)
        lh = jnp.maximum((amax - amin) / jnp.maximum(nl, 1), 1.0)
        layer = jnp.clip(jnp.floor((alt - amin) / lh), 0,
                         jnp.maximum(nl - 1, 0)).astype(jnp.int32)
        layer = jnp.where(jnp.abs(vs) > _CLIMB_VS, nl, layer)
        layer = jnp.where(nl > 0, layer, 0)

    qlon = jnp.clip((lon + 180.0) * (2 ** 19 / 360.0), 0, 2 ** 19 - 1)
    key = (s * (nl + 1) + layer) * (2 ** 19) + qlon.astype(jnp.int32)
    order = jnp.argsort(key)                       # sorted -> original
    ss = s[order]

    onehot = ss[:, None] == jnp.arange(extra, dtype=jnp.int32)[None, :]
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)          # [extra]
    nblocks = -(-counts // block)
    base_b = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(nblocks)[:-1]])
    if spread_pad:
        # Count-proportional dilution of the free padding blocks (see
        # the stripe_sort_dest docstring); the inactive stripe
        # (extra - 1) stays pinned at the very end of the layout.
        nb_tot = -(-n // block) + extra
        free = nb_tot - jnp.sum(nblocks)
        act_counts = counts.at[extra - 1].set(0)
        cc = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(act_counts)[:-1]])
        n_act = jnp.maximum(jnp.sum(act_counts), 1)
        pad_before = (free * cc // n_act).astype(jnp.int32)
        pad_before = pad_before.at[extra - 1].set(free)
        base_b = base_b + pad_before
    base = base_b * block
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n, dtype=jnp.int32) - first[ss]
    dest_sorted = base[ss] + rank
    return jnp.zeros((n,), jnp.int32).at[order].set(dest_sorted)


def scatter_padded(arrs, dest, n_tot, neutral=0.0):
    """Place per-aircraft columns into the padded sorted layout.

    Unfilled slots get ``neutral`` (0 -> inactive for the mask columns).
    One shared index computation; each array costs one O(n) scatter.
    """
    return [jnp.full((n_tot,), neutral, a.dtype).at[dest].set(a)
            for a in arrs]


def build_windows(reach, s_cap, wmax, pad_start):
    """Cover each row's reachable columns with <= s_cap segments of
    <= wmax blocks.

    ``reach`` [nbr, nbc] bool (square [nb, nb] on the single-grid
    paths; rectangular when the rows are a subset of the columns, e.g.
    a device's own rows against its halo window in the
    domain-decomposition mesh mode).  Returns ``(start, ln, overflow)``:
    ``start``/``ln`` [nbr, s_cap] int32 (unused slots: start=pad_start,
    ln=0), ``overflow`` [nbr] bool marking rows whose reachable set
    needs more segments than s_cap — the caller covers those with the
    full-grid fallback.  Covering a SUPERSET of reachable columns is
    always exact (extra tiles just compute provably-empty pairs), so the
    segmentation never needs to be tight, only sufficient.
    """
    nb = reach.shape[1]
    col = jnp.arange(nb, dtype=jnp.int32)
    prev = jnp.pad(reach[:, :-1], ((0, 0), (1, 0)))
    nxt = jnp.pad(reach[:, 1:], ((0, 0), (0, 1)))
    starts = reach & ~prev
    # run start id per column (within its run), then split runs at wmax
    rs = jax.lax.cummax(jnp.where(starts, col, -1), axis=1)
    off = col - rs
    newseg = reach & (starts | (off % wmax == 0))
    # a segment ENDS at a run end or just before the next wmax split
    segend = reach & (~nxt | (off % wmax == wmax - 1))
    nseg = jnp.sum(newseg, axis=1)
    overflow = nseg > s_cap

    # Extract the s-th start/end per row with a searchsorted on the
    # running flag counts — O(nb log nb) and graph-size O(1), unlike the
    # former [nb, s_cap, nb] one-hot reduction whose window-build graph
    # broke the TPU compiler around nb ~ 4000 (N = 1M).
    want = jnp.arange(1, s_cap + 1, dtype=jnp.int32)
    find = jax.vmap(lambda cnt: jnp.searchsorted(cnt, want, side="left"))
    st = find(jnp.cumsum(newseg, axis=1)).astype(jnp.int32)    # [nb, S]
    en = find(jnp.cumsum(segend, axis=1)).astype(jnp.int32)
    valid = want[None, :] <= nseg[:, None]
    ln = jnp.where(valid, en - st + 1, 0)
    use = valid & ~overflow[:, None]
    st = jnp.where(use, st, pad_start).astype(jnp.int32)
    ln = jnp.where(use, ln, 0).astype(jnp.int32)
    return st, ln, overflow


def schedule_counts(lat, lon, gs, alt, vs, active, perm, *, block, rpz,
                    hpz, tlookahead, s_cap=S_CAP, wmax=WMAX,
                    extra_blocks=32, min_reach_m=0.0, min_vreach_m=0.0):
    """What the single-grid schedule of ``detect_resolve_sched`` would
    visit for these positions under the layout ``perm``: ``(block
    pairs, overflow rows)`` as int32 scalars.  Built with the functions
    the interval itself uses (``scatter_padded``, ``block_reachability``,
    ``build_windows``, the same ``s_cap``/``wmax``/``extra_blocks``): the
    windows of the rows that fit ``s_cap`` segments plus the reachable
    pairs of the rows the full-grid fallback takes.  Observability
    only (core/asas.refresh_sparse_counted runs it once a chunk, outside
    the scan): nothing reads it back into the interval."""
    dtype = jnp.float32
    n = lat.shape[0]
    nb = -(-n // block) + extra_blocks
    plat, plon, pgs, palt, pvs, pact = scatter_padded(
        [lat.astype(dtype), lon.astype(dtype), gs.astype(dtype),
         alt.astype(dtype), vs.astype(dtype), active.astype(dtype)],
        perm, nb * block)
    reach = block_reachability(
        plat, plon, pgs, pact > 0.5, nb, block, float(rpz),
        float(tlookahead), alt=palt, vs=pvs, hpz=float(hpz),
        min_reach_m=min_reach_m, min_vreach_m=min_vreach_m)
    _, ln, overflow = build_windows(reach, s_cap, wmax, pad_start=nb)
    pairs = jnp.sum(ln, dtype=jnp.int32) \
        + jnp.sum(reach & overflow[:, None], dtype=jnp.int32)
    return pairs, jnp.sum(overflow, dtype=jnp.int32)


def tile_offsets(tiles, hr=1, hc=1):
    """Canonical neighbour offsets of the R x C tile mesh.

    Offsets are ``(dr, dc)`` tile steps (edge AND corner neighbours of
    the ``(2*hr+1) x (2*hc+1)`` block minus self).  Longitude wraps
    (``dc`` mod C) and latitude does not, so offsets that alias under
    the wrap are DEDUPED to one canonical ``(dr, dc mod C)`` entry —
    e.g. a 4x2 mesh has 5 canonical offsets, not 8: (0,1) covers both
    east and west, and each diagonal pair collapses likewise.  One
    ppermute pair-set per canonical offset is the whole exchange."""
    R, C = int(tiles[0]), int(tiles[1])
    offs, seen = [], set()
    for dr in range(-hr, hr + 1):
        if abs(dr) >= R and dr != 0:
            continue                     # no (src, dst) pair exists
        for dc in range(-hc, hc + 1):
            key = (dr, dc % C)
            if key == (0, 0) or key in seen:
                continue                 # self (incl. wrap-to-self)
            seen.add(key)
            offs.append(key)
    return tuple(offs)


def _offset_pairs(tiles, off):
    """ppermute (src, dst) pairs for one canonical offset over the
    flattened row-major (lat, lon) device space.  Longitude wraps,
    latitude clips (edge tiles simply have no partner and receive the
    collective's zero fill = invalid columns)."""
    R, C = int(tiles[0]), int(tiles[1])
    dr, dcm = off
    return [(r * C + c, (r + dr) * C + (c + dcm) % C)
            for r in range(R) for c in range(C) if 0 <= r + dr < R]


def tile_wire_blocks(tiles, budgets=None, nb_t=0):
    """Worst-case RECEIVED halo blocks per device for the canonical
    offset set: sum of the per-offset budgets (or nb_t each when
    unpinned).  Diagnostic/bench helper — the actual per-interval
    wire is the reach-selected subset."""
    offs = tile_offsets(tiles)
    if budgets:
        return int(sum(min(int(b), nb_t) if nb_t else int(b)
                       for b in budgets))
    return int(len(offs) * nb_t)


def tile_sort_dest(lat, lon, gs, active, thresh_m, block, extra, tiles,
                   alt=None, vs=None):
    """Tile-major sort destinations for the 2-D lat x lon decomposition.

    Tile ``t = r*C + c`` owns the contiguous slot range
    ``[t*S_t, (t+1)*S_t)`` of the padded layout (``S_t = (nb/(R*C)) *
    block``) — the direct 2-D analogue of the stripe layout's
    device-contiguous ranges, so the spatial re-bucketing bijection and
    partner-table remap apply unchanged.  Assignment is
    count-proportional but GRANULARITY-LIMITED:

    * latitude: the geometric reach-height stripes of
      ``stripe_sort_dest`` are grouped into R bands by cumulative
      active count — a stripe never splits across bands;
    * longitude: fine fixed cells (0.35 deg) within each band are
      grouped into C chunks by cumulative count — a cell never splits.

    Equal-block tiles therefore hold ~equal aircraft on any smooth
    density, but one over-dense stripe/cell CAN overflow its tile —
    that is exactly what the refresh's tile-occupancy guard bit
    detects (refuse / fall back, never silently spill).  Within a tile
    aircraft pack contiguously ordered by (stripe, lon); the free
    padding sits at each tile's tail (empty blocks are skipped exactly
    by the reachability bound).  Inactive aircraft return the last
    slot — callers only ever use ACTIVE rows' destinations (inactive
    rows carry the sentinel via ``dest_sent``)."""
    R, C = int(tiles[0]), int(tiles[1])
    D = R * C
    n = lat.shape[0]
    nb = -(-n // block) + extra
    n_tot = nb * block
    S_t = (nb // D) * block
    act = active
    big = jnp.asarray(1e9, lat.dtype)
    any_act = jnp.any(act)
    latmin = jnp.where(any_act, jnp.min(jnp.where(act, lat, big)), 0.0)
    latmax = jnp.where(any_act, jnp.max(jnp.where(act, lat, -big)), 1.0)
    span = jnp.maximum(latmax - latmin, 1e-6)
    h = jnp.maximum(jnp.maximum(thresh_m * 1.05 / 110000.0,
                                span / (extra - 1)), 0.05)
    s = jnp.clip(jnp.floor((lat - latmin) / h), 0,
                 extra - 2).astype(jnp.int32)
    s = jnp.where(act, s, extra - 1)
    acti = act.astype(jnp.int32)

    # stripe -> band: count-proportional over whole stripes
    sc = jnp.zeros((extra,), jnp.int32).at[s].add(acti)
    csum = jnp.cumsum(sc) - sc
    n_act = jnp.maximum(jnp.sum(acti), 1)
    band_of = jnp.clip(((csum + sc // 2) * R) // n_act, 0, R - 1)
    band = band_of[s]

    # (band, cell) -> lon chunk: count-proportional over whole cells
    ncell = 1024
    cell = jnp.clip(((lon + 180.0) * (ncell / 360.0)).astype(jnp.int32),
                    0, ncell - 1)
    bc = jnp.zeros((R, ncell), jnp.int32).at[band, cell].add(acti)
    ccsum = jnp.cumsum(bc, axis=1) - bc
    btot = jnp.maximum(jnp.sum(bc, axis=1), 1)
    chunk_of = jnp.clip(((ccsum + bc // 2) * C) // btot[:, None],
                        0, C - 1)
    tile = band * C + chunk_of[band, cell]

    # pack actives contiguously per tile, ordered (stripe, lon) within
    qlon = jnp.clip((lon + 180.0) * (2 ** 19 / 360.0),
                    0, 2 ** 19 - 1).astype(jnp.int32)
    key = s * jnp.int32(2 ** 19) + qlon
    tile_a = jnp.where(act, tile, D)
    order1 = jnp.argsort(key)
    order = order1[jnp.argsort(tile_a[order1], stable=True)]
    ta_o = tile_a[order]
    start = jnp.searchsorted(ta_o, jnp.arange(D + 1, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    rank_o = jnp.arange(n, dtype=jnp.int32) - start[jnp.clip(ta_o, 0, D)]
    dest_o = jnp.where(ta_o < D,
                       jnp.clip(ta_o * S_t + rank_o, 0, n_tot - 1),
                       n_tot - 1)
    return jnp.zeros((n,), jnp.int32).at[order].set(dest_o)


def _tile_select(reach_any, budget, nb_t):
    """Budget-capped export selection: the (ascending) local block ids
    of the sender's blocks any receiver row can reach.  Returns
    ``(sidx [budget] clipped ids, valid [budget])`` — deterministic, so
    the mesh sender and the single-chip reference agree bit-for-bit."""
    selkey = jnp.where(reach_any, jnp.arange(nb_t, dtype=jnp.int32),
                       nb_t)
    sidx = jnp.sort(selkey)[:budget]
    valid = sidx < nb_t
    return jnp.clip(sidx, 0, nb_t - 1), valid


def _tile_windows(reach_rows, gkey, nb, s_cap_t, wmax):
    """Sort the present (own + received) column slabs by global block
    id and build this tile's segment windows over them — shared
    VERBATIM by the per-device tiles shard_map body and the single-chip
    tiles reference, so both visit IDENTICAL column sets (the tiles
    bit-parity contract).  Overflow rows get a synthetic full-present
    coverage (disjoint <= wmax segments over all present slabs) instead
    of the 1-D full-grid fallback: the superset visit is exact (extra
    tiles compute provably-empty pairs / invalid slabs are inactive),
    and because both paths take this same construction, even the
    resume-keep bits cannot diverge.

    ``gkey`` [ncols]: candidate columns' global block ids, invalid
    entries = ``nb``.  Returns ``(order, gid_tab, wl)``: the slab
    reorder, the per-slab global-id table (invalid = nb) and the
    bit-packed windows."""
    ncols = gkey.shape[0]
    order = jnp.argsort(gkey)                     # stable
    gid_tab = gkey[order]
    vcol = gid_tab < nb
    reach_h = reach_rows[:, jnp.clip(gid_tab, 0, nb - 1)] & vcol[None, :]
    st, ln, overflow = build_windows(reach_h, s_cap_t, wmax,
                                     pad_start=ncols)
    ist = jnp.arange(s_cap_t, dtype=jnp.int32) * wmax
    fln = jnp.clip(ncols - ist, 0, wmax)
    st = jnp.where(overflow[:, None], jnp.minimum(ist, ncols),
                   jnp.clip(st, 0, ncols))
    ln = jnp.where(overflow[:, None], fln, ln)
    return order, gid_tab, (st | (ln << 20)).astype(jnp.int32)


def _sched_kernel(wl_ref, *refs, block, kk, s_cap, wmax, rpz, hpz,
                  tlookahead, mvpcfg, same_hemi=False, rpz_m=None,
                  reso="mvp", rstride=1, gid_mode=False):
    resume = rpz_m is not None
    if gid_mode:
        # tiles mode: the column slabs are the tile's PRESENT set (own +
        # reach-selected halo imports) ranked by global block id, which
        # is NOT an affine window of the grid — a second scalar-prefetch
        # table maps local slab index -> global block id (SMEM scalar
        # reads, same budget class as the worklist itself).
        gid_ref, own_ref = refs[0], refs[1]
        rest = refs[2:]
    else:
        gid_ref, own_ref = None, refs[0]
        rest = refs[1:]
    intr_refs = rest[:s_cap]
    rest = rest[s_cap:]
    if resume:
        pold_ref = rest[0]
        out_refs = rest[1:11]
        keep_ref, pnew_ref, pact_ref = rest[11:14]
        rest = rest[14:]
    else:
        pold_ref = keep_ref = pnew_ref = pact_ref = None
        out_refs = rest[:10]
        rest = rest[10:]
    swarm_refs = rest if reso == "swarm" else None
    i = pl.program_id(0)
    _init_accumulators(out_refs, block, kk)
    if resume:
        keep_ref[0] = jnp.zeros((kk, block), jnp.float32)
    if swarm_refs:
        for ref in swarm_refs:
            ref[0] = jnp.zeros((1, block), jnp.float32)

    oslab = own_ref[0]                                     # (_NFP, block)

    def own(k):
        return oslab[_IDX[k]:_IDX[k] + 1, :]

    # wl's trailing columns carry the global row-block base and the
    # global id of the column slab array's block 0: local row i is
    # GLOBAL row row0 + i*rstride (0/1 except under shard_map, where
    # each device owns a row subset but column and partner ids stay
    # global), and local column block j is GLOBAL block col0 + j.
    # col0 != 0 only in the spatial domain-decomposition mode, where the
    # column slabs are the device's local halo window of the global
    # grid instead of the full replicated slab array — DMA/window
    # indices stay halo-local, pair ids lift back to the global slot
    # space (the cd_pallas col0 contract, tests/test_cd_pallas_col0.py).
    row0 = wl_ref[i, s_cap]
    col0 = wl_ref[i, s_cap + 1]
    gid_own = (row0 + i * rstride) * block + jax.lax.broadcasted_iota(
        jnp.int32, (1, block), 1)
    act_o = own("active") > 0.5

    # Whole-row skip: a row block of padding/inactive slots has no work
    # in any segment.
    @pl.when(jnp.any(act_o))
    def _row():
        for s in range(s_cap):
            # (start, len) are bit-packed into one scalar-prefetch array
            # (start low 20 bits, len high 12): the scalar-prefetch SMEM
            # budget overflows with two [nb, s_cap] int32 tables around
            # nb ~ 1600 (the TPU compiler crashes ungracefully there).
            w = wl_ref[i, s]
            base = w & 0xFFFFF
            ln = w >> 20
            slab_ref = intr_refs[s]

            def body(k, _, base=base, slab_ref=slab_ref):
                islab_t = slab_ref[k].T                    # (block, _NFP)
                # (a pre-transposed slab layout was measured SLOWER:
                # per-field column reads of a (block, _NFP) VMEM slab
                # stride across lanes; one .T per tile wins)

                def intr(f):
                    return islab_t[:, _IDX[f]:_IDX[f] + 1]

                if gid_mode:
                    jb = gid_ref[base + k]                 # GLOBAL block id
                else:
                    jb = col0 + base + k                   # GLOBAL block id
                gid_int = jb * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, 1), 0)
                act_i = intr("active") > 0.5
                pairmask = (act_o & act_i) & (gid_own != gid_int)

                @pl.when(jnp.any(pairmask))
                def _tile():
                    cd_pallas._tile_pairs(
                        pairmask, gid_int, own, intr, *out_refs,
                        kk=kk, rpz=rpz, hpz=hpz, tlookahead=tlookahead,
                        mvpcfg=mvpcfg, same_hemi=same_hemi, jb=jb,
                        resume_refs=(pold_ref, keep_ref) if resume
                        else None, rpz_m=rpz_m, reso=reso,
                        swarm_refs=swarm_refs)
                return 0

            jax.lax.fori_loop(0, jnp.minimum(ln, wmax), body, 0)

    if resume:
        # ctin/cidx refs hold the finished per-ownship candidates after
        # the segment loops; fold in the surviving old partners.
        cd_pallas._merge_partners_block(
            pold_ref, keep_ref, out_refs[8], out_refs[9],
            pnew_ref, pact_ref, kk)


def detect_resolve_sched(lat, lon, trk, gs, alt, vs, gseast, gsnorth,
                         active, noreso, rpz, hpz, tlookahead, mvpcfg,
                         block=256, k_partners=8, s_cap=S_CAP, wmax=WMAX,
                         extra_blocks=32, interpret=None, perm=None,
                         cols_per_prog=4, partners=None, resume_rpz_m=None,
                         tas=None, cas=None, reso="mvp", mesh=None,
                         mesh_axis="ac", shard_mode="replicate",
                         halo_blocks=0, tile_shape=None, tile_budgets=()):
    """Sparse-scheduled equivalent of ``cd_pallas.detect_resolve_pallas``.

    ``perm`` is the cached ``stripe_sort_dest`` destination table (NOT a
    Morton permutation); recomputed when None.  Results match the other
    backends' reductions (same tile math, superset tile coverage).

    With ``mesh``, the segment kernel and its overflow fallback run
    under ``shard_map``: each device owns an interleaved subset of row
    blocks (its own worklist, partner-table rows, and Pallas program),
    the packed column slabs replicate over the mesh, and row ids carry a
    global offset — so results are bit-identical to the single-device
    schedule (asserted bit-for-bit in tests/test_sharding.py, and across
    a real 2-process jax.distributed boundary in tests/test_multihost.py).
    Communication structure per interval, verified on the compiled HLO
    (tests/test_hlo_collectives.py): GSPMD all-gathers the RAW O(N)
    per-aircraft columns (~90 B/aircraft total over ICI) and every
    device recomputes the padded layout/trig/reachability/windows
    locally — cheaper than shipping the [nb, 16, block] slab — plus one
    O(N*K) all-reduce for the partner back-permute; no all-to-alls, no
    per-tile collectives.  The pair math — the dominant cost — scales
    ~linearly with devices.

    With ``shard_mode='spatial'`` (and a real mesh) the decomposition
    changes from row-interleave-vs-replicated-columns to device-OWNED
    latitude stripes: each device holds the caller shard of exactly the
    aircraft whose sorted stripe slots it owns (the spatial refresh's
    re-bucketing invariant, core/asas.refresh_spatial_shard), builds
    its padded columns/trig/windows locally over its own O(N/D) rows,
    and the per-interval communication is ONLY the halo boundary-slab
    collective-permutes + one O(N/block) summary all-gather + scalar
    psums — zero O(N) column all-gathers (asserted on the HLO in
    tests/test_hlo_collectives.py).  ``halo_blocks`` sets the window
    half-width (0 = one full neighbour device; the exchange hops
    several neighbours when stripes are narrower than the reach).
    Results are bit-identical to the same call without a mesh — the
    single-chip reference on the identical stripe-bucketed layout
    (tests/test_spatial.py).  Without a mesh, ``shard_mode='spatial'``
    only switches the back-map to its sentinel-masked form (inactive
    rows carry the sentinel slot in spatial layouts).

    With ``shard_mode='tiles'`` the decomposition generalises to 2-D
    lat x lon tiles on a ``('lat', 'lon')`` device mesh of shape
    ``tile_shape = (R, C)``: device (r, c) owns tile ``t = r*C + c``'s
    contiguous block range of the tile-major layout
    (``tile_sort_dest``), and the per-interval exchange ships only the
    reach-SELECTED boundary slabs to the edge+corner neighbours — one
    ``ppermute`` pair per canonical offset (``tile_offsets``; wrapped
    lon offsets dedupe) with a per-offset block budget
    (``tile_budgets``, pinned by the tile refresh at 1.25x measured
    need), plus the same O(N/block) summary all-gather and scalar
    psums as the stripe mode.  The halo wire therefore scales with
    tile PERIMETER instead of stripe width.  Each device's kernel runs
    over its PRESENT columns (own + imports, ranked by global block
    id) with a scalar-prefetch gid table lifting pair/partner ids back
    to global slots; window construction (incl. the synthetic
    full-present coverage for overflow rows) is shared verbatim with
    the single-chip ``shard_mode='tiles'`` reference, which makes the
    mesh results bit-identical to it by construction
    (tests/test_spatial.py).  The refresh contract
    (core/asas.refresh_tile_shard) guarantees reachability never
    escapes the canonical neighbourhood or the budgets until the next
    refresh — violations refuse / fall back to replicate, never
    silently miss conflicts.

    With ``partners`` ([n_tot, K] int32, SORTED-space ids, -1 empty) the
    kernels also run in-kernel resume-nav (keep evaluation on every
    visited partner pair + the candidate/old merge — reference
    asas.py:409-471 without any [N,K] host gathers), and the return
    value becomes ``(rd, partners_new, active)`` where ``partners_new``
    [n_tot, K] stays in sorted space (the caller keeps the table there
    between intervals; ``rd.topk_*`` are then also sorted-space and
    mainly diagnostic) and ``active`` [n] is the caller-space ASAS
    engagement flag.
    ``resume_rpz_m`` is the margin-scaled resume radius (rpz*resofach).
    """
    n = lat.shape[0]
    dtype = jnp.float32
    block = min(block, 256)
    interpret = cd_pallas.interpret_default(interpret)
    if partners is None and n <= 2 * block:
        # Too small to schedule — the plain kernel is already one tile.
        extra = None
        if tas is not None:
            extra = {"tas": tas}
        if reso == "swarm":
            extra = {"cas": gs if cas is None else cas}
        return cd_pallas.detect_resolve_pallas(
            lat, lon, trk, gs, alt, vs, gseast, gsnorth, active, noreso,
            rpz, hpz, tlookahead, mvpcfg, block=block,
            k_partners=k_partners, interpret=interpret, reso=reso,
            extra_cols=extra)
    resume = partners is not None

    thresh = reach_threshold_m(gs.astype(dtype), active,
                               float(tlookahead), float(rpz))
    if perm is None:
        if shard_mode == "tiles" and tile_shape:
            perm = tile_sort_dest(lat.astype(dtype), lon.astype(dtype),
                                  gs.astype(dtype), active, thresh,
                                  block, extra_blocks,
                                  tuple(tile_shape),
                                  alt=alt.astype(dtype),
                                  vs=vs.astype(dtype))
        else:
            perm = stripe_sort_dest(lat.astype(dtype),
                                    lon.astype(dtype),
                                    gs.astype(dtype), active, thresh,
                                    block, extra_blocks,
                                    alt=alt.astype(dtype),
                                    vs=vs.astype(dtype))
    nb = -(-n // block) + extra_blocks
    n_tot = nb * block

    cols = {
        "lat": lat, "lon": lon, "trk": trk, "gs": gs, "alt": alt,
        "vs": vs, "gse": gseast, "gsn": gsnorth,
        # tas/gs ratio: Eby's velocity basis (ve = tr*u); 1.0 when no
        # tas given (MVP never reads it).  Swarm overloads the slot
        # with cas (see cd_pallas._FIELDS note).
        "tr": ((gs if cas is None else cas).astype(dtype)
               if reso == "swarm"
               else jnp.ones_like(gs.astype(dtype)) if tas is None
               else tas.astype(dtype)
               / jnp.maximum(gs.astype(dtype), 0.5)),
        "active": active.astype(dtype), "noreso": noreso.astype(dtype),
    }
    if reso == "swarm":
        from . import cr_swarm
        min_reach, min_vreach = cr_swarm.R_SWARM, cr_swarm.DH_SWARM
    else:
        min_reach = min_vreach = 0.0
    if nb >= 2 ** 20 or wmax >= 2 ** 11:
        raise ValueError(
            f"worklist bit-pack overflow: nb={nb} must be < 2^20 and "
            f"wmax={wmax} < 2^11 (start|len share one int32; a silent "
            "overflow would drop conflict windows)")

    ndev_sp = mesh.shape[mesh_axis] if (
        shard_mode == "spatial" and mesh is not None
        and mesh_axis in mesh.shape) else 0
    spatial = ndev_sp > 1
    if shard_mode == "spatial" and not resume:
        raise ValueError(
            "spatial shard mode requires the resume/partner-table path "
            "(the production sparse backend always passes `partners`)")
    if spatial and nb % ndev_sp != 0:
        raise ValueError(
            f"spatial shard mode: padded block count nb={nb} must divide "
            f"into {ndev_sp} devices — build the layout with "
            f"cd_sched.spatial_layout (extra_blocks={extra_blocks})")
    if spatial and n % ndev_sp != 0:
        raise ValueError(
            f"spatial shard mode: nmax={n} must be divisible by the "
            f"{ndev_sp}-device mesh")

    tiles_on = shard_mode == "tiles"
    mesh_tiles = False
    if tiles_on:
        if not tile_shape or len(tuple(tile_shape)) != 2:
            raise ValueError(
                "tiles shard mode needs tile_shape=(R, C) — set "
                "SimConfig.cd_tile_shape / SHARD TILE RxC")
        tR, tC = int(tile_shape[0]), int(tile_shape[1])
        tD = tR * tC
        if not resume:
            raise ValueError(
                "tiles shard mode requires the resume/partner-table "
                "path (the production sparse backend always passes "
                "`partners`)")
        if nb % tD:
            raise ValueError(
                f"tiles shard mode: padded block count nb={nb} must "
                f"divide into {tR}x{tC}={tD} tiles — build the layout "
                f"with cd_sched.spatial_layout (extra_blocks="
                f"{extra_blocks})")
        mshape = dict(mesh.shape) if mesh is not None else {}
        mesh_tiles = tD > 1 and mshape.get("lat") == tR \
            and mshape.get("lon") == tC
        if mesh is not None and not mesh_tiles and tD > 1:
            raise ValueError(
                f"tiles shard mode needs a ('lat', 'lon') mesh of "
                f"shape {tR}x{tC}; got axes {mshape} — build it with "
                "parallel.sharding.make_tile_mesh")
        if mesh_tiles and n % tD:
            raise ValueError(
                f"tiles shard mode: nmax={n} must be divisible by the "
                f"{tD}-device tile mesh")
        offs = tile_offsets((tR, tC))
        nb_t = nb // tD
        if tile_budgets:
            if len(tile_budgets) != len(offs):
                raise ValueError(
                    f"tile_budgets must carry one entry per canonical "
                    f"offset ({len(offs)} for {tR}x{tC}); got "
                    f"{len(tile_budgets)}")
            budgets = tuple(max(1, min(int(b), nb_t))
                            for b in tile_budgets)
        else:
            budgets = tuple(nb_t for _ in offs)
        ncols_t = nb_t + sum(budgets)
        s_cap_t = max(s_cap, -(-ncols_t // wmax))

    def make_fields(padded_cols):
        """Per-slot trig/velocity columns of the padded layout — shared
        verbatim by the single-chip prep and the per-device spatial
        shard so the two can never drift (bit-parity contract)."""
        flds = precompute_trig(padded_cols["lat"], padded_cols["lon"])
        trkrad = jnp.radians(padded_cols["trk"])
        flds.update({
            "u": padded_cols["gs"] * jnp.sin(trkrad),
            "v": padded_cols["gs"] * jnp.cos(trkrad),
            "alt": padded_cols["alt"], "vs": padded_cols["vs"],
            "gse": padded_cols["gse"], "gsn": padded_cols["gsn"],
            "tr": padded_cols["tr"],
            "active": padded_cols["active"],
            "noreso": padded_cols["noreso"],
        })
        flds["trk"] = padded_cols["trk"]
        return flds

    kk = k_partners
    pold = None
    if resume:
        pold = partners.reshape(nb, block, kk).transpose(0, 2, 1) \
            .astype(jnp.int32)                             # [nb, kk, block]
    neutral_vals = _ACC_NEUTRAL + ((0.0, -1, 0.0) if resume else ()) \
        + ((0.0,) * cd_pallas._N_SWARM if reso == "swarm" else ())
    #: per-BACKED-row neutral values for caller rows whose sort slot is
    #: the sentinel (inactive rows in spatial mode): exactly the
    #: accumulator identities a never-touched slot holds, so masked
    #: gathers and real gathers of empty slots cannot differ.
    backed_neutral = [0.0, 0.0, 0.0, 0.0, 0.0, cd_pallas._BIG]
    if resume:
        backed_neutral.append(0.0)                         # active flag
    if reso == "swarm":
        backed_neutral.extend([0.0] * cd_pallas._N_SWARM)

    if not spatial and not mesh_tiles:
        padded = dict(zip(cols, scatter_padded(
            [v.astype(dtype) for v in cols.values()], perm, n_tot)))
        fields = make_fields(padded)
        packed = jnp.stack([fields[k] for k in _FIELDS]).reshape(
            len(_FIELDS), nb, block).transpose(1, 0, 2)    # [nb, _NF, block]

        act_b = padded["active"] > 0.5
        reach = block_reachability(
            padded["lat"], padded["lon"], padded["gs"], act_b, nb, block,
            float(rpz), float(tlookahead), alt=padded["alt"],
            vs=padded["vs"], hpz=float(hpz), min_reach_m=min_reach,
            min_vreach_m=min_vreach)

        if not tiles_on:
            # Segment windows + the Wmax-block pad region the sentinel
            # slots point at (slots are clamped so every DMA stays in
            # bounds); start and len ride one bit-packed scalar-prefetch
            # array (SMEM budget, see _sched_kernel).  Tiles mode builds
            # its windows PER TILE over the present sets instead
            # (_tile_windows, below).
            st, ln, overflow = build_windows(reach, s_cap, wmax,
                                             pad_start=nb)
            st = jnp.clip(st, 0, nb)
            wl = st | (ln << 20)
            reach_f = reach & overflow[:, None]
        packed16 = jnp.concatenate([
            jnp.concatenate(                       # len(_FIELDS) -> _NFP
                [packed,                           # (zero-width at 16)
                 jnp.zeros((nb, _NFP - len(_FIELDS), block), dtype)],
                axis=1),
            jnp.zeros((wmax, _NFP, block), dtype)], axis=0)  # DMA pad

    def run_rows(wl_r, own16_r, packedown_r, pold_r, reachf_r, overflow_r,
                 row0, same_hemi, intr16, intr, rstride=1, col0=0,
                 gid_tab=None, fallback=True, s_cap_r=None):
        """Sched kernel + overflow fallback over one row subset.

        ``wl_r`` [rows, s_cap+2] carries (start|len) plus the global
        row-block base and the columns' global block-0 id in its last
        two columns (local row i = global row row0 + i*rstride, local
        column block j = global block col0 + j); ``own16_r``/
        ``packedown_r`` are the subset's ownship slabs; ``intr16``/
        ``intr`` are the column slab arrays — the FULL grid (col0 == 0)
        on the single-chip and column-replicated paths, the device's
        local halo window in the spatial mode.

        ``gid_tab`` (tiles mode) replaces the affine col0 lift with a
        per-slab global-block-id table riding a SECOND scalar-prefetch
        array (column slabs are the present set ranked by gid, not a
        contiguous window); ``fallback=False`` skips the full-grid
        overflow cond entirely (tiles overflow rows already carry the
        synthetic full-present windows, see _tile_windows);
        ``s_cap_r`` overrides the segment cap (tiles rows straddle up
        to 9 neighbour tiles, so their run count exceeds the 1-D
        default)."""
        rows = wl_r.shape[0]
        sc = s_cap if s_cap_r is None else s_cap_r
        gidm = gid_tab is not None
        imap_i = lambda i, *pf: (i, 0, 0)

        def imap_w(s):
            return lambda i, wl, *pf: (wl[i, s] & 0xFFFFF, 0, 0)

        own_spec = pl.BlockSpec((1, _NFP, block), imap_i,
                                memory_space=pltpu.VMEM)
        intr_specs = [_element_spec((wmax, _NFP, block), imap_w(s))
                      for s in range(sc)]
        acc_spec = lambda: pl.BlockSpec((1, 1, block), imap_i,
                                        memory_space=pltpu.VMEM)
        cand_spec = lambda: pl.BlockSpec((1, kk, block), imap_i,
                                         memory_space=pltpu.VMEM)
        out_shape = [jax.ShapeDtypeStruct((rows, 1, block), dtype)] * 8 + [
            jax.ShapeDtypeStruct((rows, kk, block), dtype),
            jax.ShapeDtypeStruct((rows, kk, block), jnp.int32)]
        if resume:
            out_shape = out_shape + [
                jax.ShapeDtypeStruct((rows, kk, block), dtype),     # keep
                jax.ShapeDtypeStruct((rows, kk, block), jnp.int32),  # merged
                jax.ShapeDtypeStruct((rows, 1, block), dtype)]      # active
        if reso == "swarm":
            out_shape = out_shape + [
                jax.ShapeDtypeStruct((rows, 1, block), dtype)
            ] * cd_pallas._N_SWARM
        kern = functools.partial(
            _sched_kernel, block=block, kk=kk, s_cap=sc, wmax=wmax,
            rpz=float(rpz), hpz=float(hpz), tlookahead=float(tlookahead),
            mvpcfg=mvpcfg, same_hemi=same_hemi, rstride=rstride,
            rpz_m=float(resume_rpz_m) if resume else None, reso=reso,
            gid_mode=gidm)
        in_specs = [own_spec] + [intr_specs[s] for s in range(sc)]
        out_specs = [acc_spec() for _ in range(8)] \
            + [cand_spec(), cand_spec()]
        args = [wl_r] + ([gid_tab] if gidm else []) \
            + [own16_r] + [intr16] * sc
        if resume:
            in_specs.append(cand_spec())               # pold
            args.append(pold_r)
            out_specs += [cand_spec(), cand_spec(), acc_spec()]
        if reso == "swarm":
            out_specs += [acc_spec() for _ in range(cd_pallas._N_SWARM)]
        outs_s = list(pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2 if gidm else 1,
                grid=(rows,),
                in_specs=in_specs,
                out_specs=out_specs,
            ),
            out_shape=out_shape,
            interpret=interpret,
        )(*args))
        if not fallback:
            return tuple(outs_s)

        # Overflow rows (dense geometries): exact full-grid fallback on
        # the row-restricted reachability, merged row-disjointly.
        kern_kw = dict(block=block, kk=kk, rpz=float(rpz), hpz=float(hpz),
                       tlookahead=float(tlookahead), mvpcfg=mvpcfg,
                       same_hemi=same_hemi, reso=reso)

        def fallback(rf):
            return cd_pallas.full_grid_pass(
                intr, rf, block=block, kk=kk, cpp=cols_per_prog,
                kern_kw=kern_kw, interpret=interpret, pold=pold_r,
                rpz_m=resume_rpz_m, packed_own=packedown_r, row0=row0,
                rstride=rstride, col0=col0)

        def neutral(_):
            return [jnp.full(o.shape, v, o.dtype)
                    for o, v in zip(outs_s, neutral_vals)]

        outs_f = jax.lax.cond(jnp.any(overflow_r), fallback, neutral,
                              reachf_r)
        rsel = overflow_r[:, None, None]
        return tuple(jnp.where(rsel, f, s) for f, s in zip(outs_f, outs_s))

    row0_col = lambda w, r0, c0=0: jnp.concatenate(
        [w,
         jnp.full((w.shape[0], 1), r0, jnp.int32),
         jnp.full((w.shape[0], 1), c0, jnp.int32)], axis=1)

    if spatial:
        # ------------------------------------------------------------
        # Spatial domain decomposition: device d OWNS the contiguous
        # latitude-stripe block range [d*nb_l, (d+1)*nb_l) of the
        # sorted layout — O(N/D) scatter, trig, reachability, window
        # build and kernel rows per device — and exchanges only the
        # `halo`-block boundary stripes with its lat-neighbours over
        # ICI (collective-permute), plus one O(N/block) all-gather of
        # the per-block summary vectors the exact reachability bound
        # reads.  No O(N) per-aircraft column is ever gathered
        # (asserted mechanically in tests/test_hlo_collectives.py).
        # The caller guarantees (and the spatial refresh verifies with
        # a drift margin, core/asas.refresh_spatial_shard) that the
        # halo window covers every reachable column until the next
        # refresh, and that each aircraft's caller slot lives on the
        # device owning its sorted slot — which makes the per-interval
        # scatter and result back-map DEVICE-LOCAL.
        # ------------------------------------------------------------
        from jax.sharding import PartitionSpec as P
        ndev = ndev_sp
        nb_l = nb // ndev
        S_l = nb_l * block
        halo = int(halo_blocks) if halo_blocks else nb_l
        # the halo may span several neighbour devices (narrow stripes
        # at large D): the exchange below hops ceil(halo/nb_l) devices
        # per side, wire still ~2*halo blocks per device
        halo = min(halo, (ndev - 1) * nb_l)
        n_hops = -(-halo // nb_l)
        nbh = nb_l + 2 * halo
        cols_f = {k: v.astype(dtype) for k, v in cols.items()}

        def body(cols_l, perm_l, pold_l):
            d = jax.lax.axis_index(mesh_axis)
            base = d * jnp.int32(S_l)
            in_dev = (perm_l >= base) & (perm_l < base + S_l)
            # sentinel (inactive) and off-device slots drop out of the
            # scatter; the spatial refresh guarantees the latter set is
            # empty, so dropping is exact, never lossy
            dest_loc = jnp.where(in_dev, perm_l - base, S_l)
            padded_l = {
                k: jnp.zeros((S_l,), dtype).at[dest_loc].set(
                    v, mode="drop")
                for k, v in cols_l.items()}
            fields_l = make_fields(padded_l)
            packed_l = jnp.stack(
                [fields_l[k] for k in _FIELDS]).reshape(
                    len(_FIELDS), nb_l, block).transpose(1, 0, 2)
            act_l = padded_l["active"] > 0.5

            # Exact reachability of OWN rows vs the whole grid from the
            # gathered per-block summaries (identical per-block math to
            # the single-chip block_reachability — bit-parity contract)
            summ_l = cd_tiled.block_summaries(
                padded_l["lat"], padded_l["lon"], padded_l["gs"], act_l,
                nb_l, block, alt=padded_l["alt"], vs=padded_l["vs"])
            summ_g = {k: jax.lax.all_gather(v, mesh_axis, tiled=True)
                      for k, v in summ_l.items()}
            reach_rows = cd_tiled.reachability_from_summaries(
                summ_l, summ_g, float(rpz), float(tlookahead),
                hpz=float(hpz), min_reach_m=min_reach,
                min_vreach_m=min_vreach)                   # [nb_l, nb]

            # Restrict to the halo window; out-of-grid columns (mesh
            # edges) are masked, never visited
            cg = base // block - halo + jnp.arange(nbh, dtype=jnp.int32)
            vcol = (cg >= 0) & (cg < nb)
            reach_h = reach_rows[:, jnp.clip(cg, 0, nb - 1)] \
                & vcol[None, :]
            st_l, ln_l, overflow_l = build_windows(
                reach_h, s_cap, wmax, pad_start=nbh)
            wl_l = jnp.clip(st_l, 0, nbh) | (ln_l << 20)

            # Halo exchange: ship only the boundary slabs to the
            # lat-neighbours, hopping as many devices as the halo spans
            # (h-th hop carries the h-th-nearest neighbour's share;
            # edge devices receive zeros = inactive, and their
            # out-of-grid columns are reach-masked anyway).  Wire per
            # device ~ 2 * halo * _NF * block * 4 B regardless of hops.
            parts_lo, parts_hi = [], []
            for h in range(1, n_hops + 1):
                take = halo - (h - 1) * nb_l if h == n_hops else nb_l
                lo_h = jax.lax.ppermute(
                    packed_l[nb_l - take:], mesh_axis,
                    [(i, i + h) for i in range(ndev - h)])
                hi_h = jax.lax.ppermute(
                    packed_l[:take], mesh_axis,
                    [(i, i - h) for i in range(h, ndev)])
                # ascending global order: farthest-left part first
                parts_lo.insert(0, lo_h)
                parts_hi.append(hi_h)
            halo13 = jnp.concatenate(
                parts_lo + [packed_l] + parts_hi, axis=0)
            halo16 = jnp.concatenate([
                jnp.concatenate(
                    [halo13, jnp.zeros(
                        (nbh, _NFP - len(_FIELDS), block), dtype)],
                    axis=1),
                jnp.zeros((wmax, _NFP, block), dtype)], axis=0)
            own16 = halo16[halo:halo + nb_l]

            row0 = base // block
            col0 = row0 - halo
            outs_l = run_rows(
                row0_col(wl_l, row0, col0), own16, packed_l, pold_l,
                reach_h & overflow_l[:, None], overflow_l, row0, False,
                halo16, halo13, rstride=1, col0=col0)

            # Back-map to THIS device's caller shard (device-local
            # gather; sentinel rows read the accumulator identities)
            (inconf_l, tcpamax_l, sdve_l, sdvn_l, sdvv_l, tsolv_l,
             ncnt_l, lcnt_l, ctin_l, cidx_l) = outs_l[:10]
            rows_l = [inconf_l, tcpamax_l, sdve_l, sdvn_l, sdvv_l,
                      tsolv_l, outs_l[12]]                 # + active
            if reso == "swarm":
                rows_l.extend(outs_l[13:13 + cd_pallas._N_SWARM])
            stacked_l = jnp.stack([o.reshape(S_l) for o in rows_l])
            gsl = jnp.clip(dest_loc, 0, S_l - 1)
            backed_l = jnp.where(
                in_dev[None, :], stacked_l[:, gsl],
                jnp.asarray(backed_neutral, dtype)[:, None])
            tt_l = ctin_l.transpose(0, 2, 1).reshape(S_l, kk)[gsl]
            ti_l = cidx_l.transpose(0, 2, 1).reshape(S_l, kk)[gsl]
            tt_l = jnp.where(in_dev[:, None], tt_l, cd_pallas._BIG)
            ti_l = jnp.where(in_dev[:, None], ti_l, jnp.int32(2 ** 30))
            nconf_l = jax.lax.psum(
                jnp.sum(ncnt_l.astype(jnp.int32), dtype=jnp.int32),
                mesh_axis)
            nlos_l = jax.lax.psum(
                jnp.sum(lcnt_l.astype(jnp.int32), dtype=jnp.int32),
                mesh_axis)
            return backed_l, tt_l, ti_l, outs_l[11], nconf_l, nlos_l

        col_specs = {k: P(mesh_axis) for k in cols_f}
        backed, topk_tin, ti_raw, pmerged, nconf, nlos = \
            cd_pallas.shard_map(
                body, mesh,
                (col_specs, P(mesh_axis), P(mesh_axis)),
                (P(None, mesh_axis), P(mesh_axis), P(mesh_axis),
                 P(mesh_axis), P(), P()))(cols_f, perm, pold)

        topk_idx = jnp.where(
            (topk_tin < cd_pallas._BIG) & (ti_raw < n_tot), ti_raw, -1)
        rd = RowConflictData(
            inconf=backed[0] > 0.5,
            tcpamax=backed[1],
            sum_dve=backed[2], sum_dvn=backed[3], sum_dvv=backed[4],
            tsolv=backed[5],
            nconf=nconf, nlos=nlos,
            topk_idx=topk_idx, topk_tin=topk_tin)
        partners_new = pmerged.transpose(0, 2, 1).reshape(n_tot, kk)
        active_caller = backed[6] > 0.5
        if reso == "swarm":
            return rd, partners_new, active_caller, \
                tuple(backed[7:7 + cd_pallas._N_SWARM])
        return rd, partners_new, active_caller

    if mesh_tiles:
        # ------------------------------------------------------------
        # 2-D tile decomposition: device (r, c) OWNS tile t = r*C + c's
        # contiguous block range of the tile-major layout — O(N/D)
        # scatter/trig/reachability/windows/kernel rows per device —
        # and exchanges only the reach-SELECTED boundary slabs with its
        # edge+corner neighbours: ONE ppermute pair per canonical
        # offset (wrapped lon offsets deduped), each budget-capped, so
        # the halo wire scales with tile PERIMETER instead of stripe
        # width.  The summary all-gather/psum structure matches the
        # stripe mode (O(N/block) metadata, zero O(N) column
        # collectives — asserted in tests/test_hlo_collectives.py).
        # The tile refresh (core/asas.refresh_tile_shard) guarantees
        # margin-widened reachability stays inside the canonical
        # neighbourhood AND the per-offset budgets until the next
        # refresh, and that each aircraft's caller slot lives on the
        # device owning its sorted slot — scatter and back-map stay
        # device-local.
        # ------------------------------------------------------------
        from jax.sharding import PartitionSpec as P
        axes = ("lat", "lon")
        S_t = nb_t * block
        cols_f = {k: v.astype(dtype) for k, v in cols.items()}
        pairs_o = [_offset_pairs((tR, tC), off) for off in offs]

        def body(cols_l, perm_l, pold_l):
            r_i = jax.lax.axis_index("lat")
            c_i = jax.lax.axis_index("lon")
            t = r_i * tC + c_i
            base = t * jnp.int32(S_t)
            in_dev = (perm_l >= base) & (perm_l < base + S_t)
            dest_loc = jnp.where(in_dev, perm_l - base, S_t)
            padded_l = {
                k: jnp.zeros((S_t,), dtype).at[dest_loc].set(
                    v, mode="drop")
                for k, v in cols_l.items()}
            fields_l = make_fields(padded_l)
            packed_l = jnp.stack(
                [fields_l[k] for k in _FIELDS]).reshape(
                    len(_FIELDS), nb_t, block).transpose(1, 0, 2)
            act_l = padded_l["active"] > 0.5

            summ_l = cd_tiled.block_summaries(
                padded_l["lat"], padded_l["lon"], padded_l["gs"], act_l,
                nb_t, block, alt=padded_l["alt"], vs=padded_l["vs"])
            summ_g = {k: jax.lax.all_gather(v, axes, tiled=True)
                      for k, v in summ_l.items()}
            reach_rows = cd_tiled.reachability_from_summaries(
                summ_l, summ_g, float(rpz), float(tlookahead),
                hpz=float(hpz), min_reach_m=min_reach,
                min_vreach_m=min_vreach)                   # [nb_t, nb]

            # Per-offset export: ship only the own blocks the RECEIVER
            # tile's rows can reach.  Sender and the single-chip
            # reference derive the selection from the SAME gathered
            # summaries, so the shipped sets agree bit-for-bit; gids
            # ride a parallel +1-coded int permute (0 = invalid — edge
            # tiles without a partner receive the collective's zeros).
            own_gid0 = t * jnp.int32(nb_t)
            gparts = [own_gid0 + jnp.arange(nb_t, dtype=jnp.int32)]
            sparts = [packed_l]
            for off, E, prs in zip(offs, budgets, pairs_o):
                dr, dcm = off
                tdst = jnp.clip(r_i + dr, 0, tR - 1) * tC \
                    + (c_i + dcm) % tC
                summ_dst = {
                    k: jax.lax.dynamic_slice(v, (tdst * nb_t,), (nb_t,))
                    for k, v in summ_g.items()}
                reach_out = cd_tiled.reachability_from_summaries(
                    summ_dst, summ_l, float(rpz), float(tlookahead),
                    hpz=float(hpz), min_reach_m=min_reach,
                    min_vreach_m=min_vreach)       # [dst rows, own cols]
                sidx, valid = _tile_select(
                    jnp.any(reach_out, axis=0), E, nb_t)
                buf = jnp.where(valid[:, None, None],
                                packed_l[sidx], 0.0)
                gidp = jnp.where(valid, own_gid0 + sidx + 1,
                                 0).astype(jnp.int32)
                rbuf = jax.lax.ppermute(buf, axes, prs)
                rgid = jax.lax.ppermute(gidp, axes, prs)
                gparts.append(jnp.where(rgid > 0, rgid - 1, nb))
                sparts.append(rbuf)

            gkey = jnp.concatenate(gparts)
            order, gid_tab, wl_l = _tile_windows(
                reach_rows, gkey, nb, s_cap_t, wmax)
            halo13 = jnp.concatenate(sparts, axis=0)[order]
            halo16 = jnp.concatenate([
                jnp.concatenate(
                    [halo13, jnp.zeros(
                        (ncols_t, _NFP - len(_FIELDS), block), dtype)],
                    axis=1),
                jnp.zeros((wmax, _NFP, block), dtype)], axis=0)
            own16 = jnp.concatenate(
                [packed_l,
                 jnp.zeros((nb_t, _NFP - len(_FIELDS), block), dtype)],
                axis=1)
            gid_pad = jnp.concatenate(
                [gid_tab, jnp.full((wmax,), nb, jnp.int32)])

            row0 = t * jnp.int32(nb_t)
            outs_l = run_rows(
                row0_col(wl_l, row0, 0), own16, packed_l, pold_l,
                None, None, row0, False, halo16, halo13,
                rstride=1, col0=0, gid_tab=gid_pad, fallback=False,
                s_cap_r=s_cap_t)

            # Back-map to THIS device's caller shard (device-local
            # gather; sentinel rows read the accumulator identities)
            (inconf_l, tcpamax_l, sdve_l, sdvn_l, sdvv_l, tsolv_l,
             ncnt_l, lcnt_l, ctin_l, cidx_l) = outs_l[:10]
            rows_l = [inconf_l, tcpamax_l, sdve_l, sdvn_l, sdvv_l,
                      tsolv_l, outs_l[12]]                 # + active
            if reso == "swarm":
                rows_l.extend(outs_l[13:13 + cd_pallas._N_SWARM])
            stacked_l = jnp.stack([o.reshape(S_t) for o in rows_l])
            gsl = jnp.clip(dest_loc, 0, S_t - 1)
            backed_l = jnp.where(
                in_dev[None, :], stacked_l[:, gsl],
                jnp.asarray(backed_neutral, dtype)[:, None])
            tt_l = ctin_l.transpose(0, 2, 1).reshape(S_t, kk)[gsl]
            ti_l = cidx_l.transpose(0, 2, 1).reshape(S_t, kk)[gsl]
            tt_l = jnp.where(in_dev[:, None], tt_l, cd_pallas._BIG)
            ti_l = jnp.where(in_dev[:, None], ti_l, jnp.int32(2 ** 30))
            nconf_l = jax.lax.psum(
                jnp.sum(ncnt_l.astype(jnp.int32), dtype=jnp.int32),
                axes)
            nlos_l = jax.lax.psum(
                jnp.sum(lcnt_l.astype(jnp.int32), dtype=jnp.int32),
                axes)
            return backed_l, tt_l, ti_l, outs_l[11], nconf_l, nlos_l

        col_specs = {k: P(axes) for k in cols_f}
        backed, topk_tin, ti_raw, pmerged, nconf, nlos = \
            cd_pallas.shard_map(
                body, mesh,
                (col_specs, P(axes), P(axes)),
                (P(None, axes), P(axes), P(axes),
                 P(axes), P(), P()))(cols_f, perm, pold)

        topk_idx = jnp.where(
            (topk_tin < cd_pallas._BIG) & (ti_raw < n_tot), ti_raw, -1)
        rd = RowConflictData(
            inconf=backed[0] > 0.5,
            tcpamax=backed[1],
            sum_dve=backed[2], sum_dvn=backed[3], sum_dvv=backed[4],
            tsolv=backed[5],
            nconf=nconf, nlos=nlos,
            topk_idx=topk_idx, topk_tin=topk_tin)
        partners_new = pmerged.transpose(0, 2, 1).reshape(n_tot, kk)
        active_caller = backed[6] > 0.5
        if reso == "swarm":
            return rd, partners_new, active_caller, \
                tuple(backed[7:7 + cd_pallas._N_SWARM])
        return rd, partners_new, active_caller

    if tiles_on:
        # Single-chip tiles reference: the SAME per-tile present-set
        # construction and windows as the mesh body (shared helpers),
        # run as one kernel call per tile over the global slab array —
        # a parity/debug path, not a perf path (it re-gathers each
        # tile's imports from the replicated grid).  Bit-parity with
        # the mesh is by construction: identical selection, identical
        # present ranking, identical windows, identical gid lift.
        chunks = []
        for t in range(tD):
            r0t, c0t = divmod(t, tC)
            rr = reach[t * nb_t:(t + 1) * nb_t]            # [nb_t, nb]
            reach_any = jnp.any(rr, axis=0)
            gparts = [t * nb_t + jnp.arange(nb_t, dtype=jnp.int32)]
            sparts = [packed[t * nb_t:(t + 1) * nb_t]]
            for off, E in zip(offs, budgets):
                dr, dcm = off
                ru, cu = r0t - dr, (c0t - dcm) % tC
                if 0 <= ru < tR:
                    u = ru * tC + cu
                    sidx, valid = _tile_select(
                        reach_any[u * nb_t:(u + 1) * nb_t], E, nb_t)
                    gparts.append(jnp.where(valid, u * nb_t + sidx, nb))
                    sparts.append(jnp.where(valid[:, None, None],
                                            packed[u * nb_t + sidx],
                                            0.0))
                else:
                    gparts.append(jnp.full((E,), nb, jnp.int32))
                    sparts.append(jnp.zeros((E, len(_FIELDS), block),
                                            dtype))
            gkey = jnp.concatenate(gparts)
            order, gid_tab, wl_t = _tile_windows(rr, gkey, nb,
                                                 s_cap_t, wmax)
            halo13_t = jnp.concatenate(sparts, axis=0)[order]
            halo16_t = jnp.concatenate([
                jnp.concatenate(
                    [halo13_t, jnp.zeros(
                        (ncols_t, _NFP - len(_FIELDS), block), dtype)],
                    axis=1),
                jnp.zeros((wmax, _NFP, block), dtype)], axis=0)
            gid_pad = jnp.concatenate(
                [gid_tab, jnp.full((wmax,), nb, jnp.int32)])
            chunks.append(run_rows(
                row0_col(wl_t, t * nb_t, 0),
                packed16[t * nb_t:(t + 1) * nb_t],
                packed[t * nb_t:(t + 1) * nb_t],
                None if pold is None else pold[t * nb_t:(t + 1) * nb_t],
                None, None, t * nb_t, False, halo16_t, halo13_t,
                rstride=1, col0=0, gid_tab=gid_pad, fallback=False,
                s_cap_r=s_cap_t))
        outs = [parts[0] if tD == 1 else jnp.concatenate(parts)
                for parts in zip(*chunks)]
    elif mesh is not None and mesh.shape[mesh_axis] > 1:
        # shard_map over the row blocks: each device schedules and
        # sweeps its own rows against the replicated column slabs (the
        # all-gather rides ICI); row/partner ids stay global via the
        # row0 + i*ndev mapping.  Rows are INTERLEAVED across devices
        # (device d owns global rows d, d+D, ...) — measured to cut the
        # contiguous split's 1.2-1.5x stripe-density imbalance to
        # ~1.0-1.1x (scripts/scaling_table.py).  SURVEY §5.7/5.8
        # block-distributed CD.
        from jax.sharding import PartitionSpec as P
        ndev = mesh.shape[mesh_axis]
        rows_l, nbrp, rperm, rinv = cd_pallas.interleave_rows(nb, ndev)
        pad_r = nbrp - nb

        def prep(a, fill):
            if pad_r:
                a = jnp.concatenate(
                    [a, jnp.full((pad_r,) + a.shape[1:], fill, a.dtype)])
            return a[rperm]

        # Padding rows: empty windows (start=sentinel, len=0) + inactive
        # own slabs -> the kernel's whole-row skip; overflow=False.
        wl_p = prep(wl, nb)                       # start=nb, ln=0
        own16_p = prep(packed16[:nb], 0)
        packedown_p = prep(packed, 0)
        pold_p = prep(pold, -1) if resume else None
        reachf_p = prep(reach_f, False)
        overflow_p = prep(overflow, False)

        def body(wl_l, own16_l, packedown_l, pold_l, reachf_l,
                 overflow_l, intr16_g, intr_g):
            row0 = jax.lax.axis_index(mesh_axis)
            return run_rows(row0_col(wl_l, row0), own16_l, packedown_l,
                            pold_l, reachf_l, overflow_l, row0,
                            False, intr16_g, intr_g, rstride=ndev)

        specs_in = (P(mesh_axis), P(mesh_axis), P(mesh_axis),
                    P(mesh_axis) if resume else P(),
                    P(mesh_axis), P(mesh_axis), P(), P())
        outs = cd_pallas.shard_map(
            body, mesh, specs_in, P(mesh_axis))(
                wl_p, own16_p, packedown_p,
                pold_p if resume else jnp.zeros((ndev,), jnp.int32),
                reachf_p, overflow_p, packed16, packed)
        outs = [o[rinv][:nb] for o in outs]
    elif nb > _ONE_VARIANT_ROWS:
        # Large-N: compile a single kernel variant (both equator-branch
        # variants double compile time for a ~10% saving that huge
        # fleets, which usually straddle the equator, rarely get).
        # ROW SPLIT: the worklist of one call must fit SMEM (see
        # _MAX_ROWS: ~2000 rows, N ~ 500k).  Rows are independent, so
        # slicing the grid into <=_MAX_ROWS-row pallas_call invocations
        # keeps every compiled program inside that range while the
        # concatenated outputs stay bit-identical; this is what lifts
        # the sparse backend to 1M+.
        chunks = []
        for r0 in range(0, nb, _MAX_ROWS):
            r1 = min(r0 + _MAX_ROWS, nb)
            chunks.append(run_rows(
                row0_col(wl[r0:r1], r0), packed16[r0:r1], packed[r0:r1],
                None if pold is None else pold[r0:r1],
                reach_f[r0:r1], overflow[r0:r1], r0, False,
                packed16, packed))
        outs = [parts[0] if len(chunks) == 1 else jnp.concatenate(parts)
                for parts in zip(*chunks)]
    else:
        lat_a = jnp.where(act_b, padded["lat"], 0.0)
        cross = (jnp.min(lat_a) < 0.0) & (jnp.max(lat_a) > 0.0)
        run = lambda sh: functools.partial(
            run_rows, row0_col(wl, 0), packed16, packed, pold,
            reach_f, overflow, 0, sh, packed16, packed)
        outs = jax.lax.cond(cross,
                            lambda: run(False)(),
                            lambda: run(True)())

    (inconf, tcpamax, sdve, sdvn, sdvv, tsolv, ncnt, lcnt,
     ctin, cidx) = outs[:10]

    # Map padded-sorted rows back to caller slots with ONE fused gather
    # (aircraft i lives at padded slot perm[i]; separate per-array
    # gathers serialize on TPU at ~30 ns/element).
    rows = [inconf, tcpamax, sdve, sdvn, sdvv, tsolv]
    if resume:
        rows.append(outs[12])                              # active
    sw_start = 13 if resume else 10
    if reso == "swarm":
        rows.extend(outs[sw_start:sw_start + cd_pallas._N_SWARM])
    stacked = jnp.stack([o.reshape(n_tot) for o in rows])
    if shard_mode in ("spatial", "tiles"):
        # A spatial/tiles-mode refresh stores the SENTINEL slot n_tot
        # for inactive rows (they are dropped from the padded scatter);
        # mask their gathers to the accumulator identities so this
        # single-chip reference stays bit-identical to the mesh
        # decomposition's masked device-local back-map.
        pvalid = perm < n_tot
        pc = jnp.clip(perm, 0, n_tot - 1)
        backed = jnp.where(pvalid[None, :], stacked[:, pc],
                           jnp.asarray(backed_neutral, dtype)[:, None])
        topk_tin = jnp.where(
            pvalid[:, None],
            ctin.transpose(0, 2, 1).reshape(n_tot, kk)[pc],
            cd_pallas._BIG)
        topk_idx = jnp.where(
            pvalid[:, None],
            cidx.transpose(0, 2, 1).reshape(n_tot, kk)[pc],
            jnp.int32(2 ** 30))
    else:
        backed = stacked[:, perm]                          # [6|7|+7, n]
        topk_tin = ctin.transpose(0, 2, 1).reshape(n_tot, kk)[perm]
        topk_idx = cidx.transpose(0, 2, 1).reshape(n_tot, kk)[perm]
    if not resume:
        # Translate sorted-space partner ids to caller slots via the
        # inverse scatter (sentinel-filled with n -> invalid -> -1).
        inv = slot_inverse(perm, n, n_tot, fill=n)
        topk_idx = inv[jnp.clip(topk_idx, 0, n_tot)]
    topk_idx = jnp.where((topk_tin < cd_pallas._BIG) & (topk_idx < n_tot),
                         topk_idx, -1)

    rd = RowConflictData(
        inconf=backed[0] > 0.5,
        tcpamax=backed[1],
        sum_dve=backed[2], sum_dvn=backed[3], sum_dvv=backed[4],
        tsolv=backed[5],
        nconf=jnp.sum(ncnt.astype(jnp.int32), dtype=jnp.int32),
        nlos=jnp.sum(lcnt.astype(jnp.int32), dtype=jnp.int32),
        topk_idx=topk_idx, topk_tin=topk_tin)
    nfix = 7 if resume else 6
    sw = tuple(backed[nfix:nfix + cd_pallas._N_SWARM]) \
        if reso == "swarm" else None
    if not resume:
        return (rd, sw) if sw is not None else rd
    pmerged = outs[11]
    partners_new = pmerged.transpose(0, 2, 1).reshape(n_tot, kk)
    active_caller = backed[6] > 0.5
    if sw is not None:
        return rd, partners_new, active_caller, sw
    return rd, partners_new, active_caller
