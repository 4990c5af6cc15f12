"""Mechanical verification of the sharded sparse backend's communication
structure (VERDICT r4 #1): compile one CD interval on the 8-device mesh
and assert on the HLO itself which collectives GSPMD inserted.

Measured structure (the numbers PERF_ANALYSIS §multi-chip quotes):

* ~21 all-gathers, every one O(N): the raw per-aircraft state columns
  (f32[n]/s32[n,1]) are gathered and the padded stripe-sorted layout +
  trig columns are recomputed on every device — XLA chooses this over
  gathering the [nb, 16, block] slab because the columns are smaller
  (~84 B/aircraft total vs the ~16 rows x 4 B slab) and the rebuild is
  trivial elementwise work.  Either way the wire cost per interval is
  O(N) bytes, independent of the O(N^2/D) pair work.
* ONE O(N*K) all-reduce: the sorted-space partner-table back-permute
  (outs[rinv]) lowered as one-hot scatter-add.
* ZERO all-to-alls, reduce-scatters or collective-permutes — the global
  stripe-sort / reachability / window-build ops do NOT get sharded (they
  are recomputed per device from the gathered columns), so no stray
  collectives appear around them.

The assertions are structural (op kinds + per-result element bounds +
total byte bound), not exact-count, so compiler-version noise in how
many columns fuse cannot flake the test while any O(N^2)-scale or
per-tile collective still fails it loudly.
"""
import re

import jax
import numpy as np
import pytest

from bluesky_tpu.core import asas as asasmod
from bluesky_tpu.core.asas import AsasConfig
from bluesky_tpu.ops import cd_sched
from bluesky_tpu.parallel import sharding

from test_sharding import make_mixed_scene

pytestmark = pytest.mark.slow

_COLL = re.compile(
    r'=\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s+'
    r'(all-gather|all-to-all|all-reduce|reduce-scatter|'
    r'collective-permute)\(')

_BYTES = {"f32": 4, "s32": 4, "f64": 8, "s64": 8, "pred": 1, "u32": 4,
          "bf16": 2, "s8": 1, "u8": 1}


def _collectives(hlo_text):
    out = []
    for line in hlo_text.splitlines():
        m = _COLL.search(line)
        if m:
            dtype, dims, op = m.group(1), m.group(2), m.group(3)
            shape = tuple(int(d) for d in dims.split(",") if d)
            elems = int(np.prod(shape)) if shape else 1
            out.append((op, dtype, shape,
                        elems * _BYTES.get(dtype, 4)))
    return out


def test_spatial_interval_collectives():
    """ISSUE 5 acceptance: the SPATIAL decomposition's per-interval
    communication is O(halo) — NO O(N) per-aircraft-column all-gathers
    remain (the column-replication scheme's ~21 of them are gone), no
    all-to-alls, no O(N*K) partner all-reduce (the table stays sharded).

    What IS allowed, asserted with tight byte bounds:
    * all-gathers of the per-BLOCK summary vectors the exact
      reachability bound reads — O(N/block) metadata, 256x smaller than
      a column;
    * collective-permutes of the halo boundary slabs — O(halo);
    * scalar all-reduces (nconf/nlos psums).
    """
    import jax.numpy as jnp
    from bluesky_tpu.core.traffic import Traffic

    mesh = sharding.make_mesh(8)
    rng = np.random.default_rng(7)
    # generous caller-shard headroom: stripe populations are uneven
    # and each device's bucket must fit nmax/ndev
    nmax, n = 4096, 1200
    traf = Traffic(nmax=nmax, dtype=jnp.float32, pair_matrix=False)
    traf.create(n, "B744", rng.uniform(3000, 11000, n),
                rng.uniform(130, 240, n), None,
                rng.uniform(35, 60, n), rng.uniform(-10, 30, n),
                rng.uniform(0, 360, n))
    traf.flush()
    cfg = AsasConfig()
    st, _, info = sharding.prepare_spatial(traf.state, mesh, cfg,
                                           block=256)
    nb, halo, block = info["nb"], info["halo_blocks"], 256
    n_tot = info["n_tot"]

    def one_interval(s):
        s2, _ = asasmod.update_tiled(s, cfg, block=256, impl="sparse",
                                     mesh=mesh, shard_mode="spatial",
                                     halo_blocks=halo)
        return s2

    comp = jax.jit(one_interval).lower(st).compile()
    colls = _collectives(comp.as_text())
    assert colls, "spatial program must contain halo collectives"

    by_op = {}
    for op, dtype, shape, nbytes in colls:
        by_op.setdefault(op, []).append((dtype, shape, nbytes))

    assert "all-to-all" not in by_op, by_op.get("all-to-all")

    # Every all-gather is block-summary metadata: its result holds
    # O(nb) = O(N/block) elements — NEVER an O(N) per-aircraft column
    # (n_tot or nmax elements), let alone a slab.
    for dtype, shape, nbytes in by_op.get("all-gather", []):
        elems = int(np.prod(shape)) if shape else 1
        assert elems <= 16 * nb, \
            f"O(N)-scale all-gather leaked into spatial mode: " \
            f"{dtype}{list(shape)}"

    # Halo exchange: collective-permutes bounded by the boundary slab
    # volume (2 directions x halo blocks x 16 rows x block lanes).
    halo_budget = 2 * halo * 16 * block * 4
    for dtype, shape, nbytes in by_op.get("collective-permute", []):
        assert nbytes <= halo_budget, (dtype, shape, nbytes)

    # All-reduces are scalar count psums — the O(N*K) partner
    # back-permute of the replicate scheme must NOT exist here.
    for dtype, shape, nbytes in by_op.get("all-reduce", []):
        assert int(np.prod(shape) if shape else 1) <= 64, (dtype, shape)

    # Per-interval wire total is O(halo + N/block), far under the
    # O(N)-column budget the replicate mode pays (~90 B/aircraft).
    total = sum(nbytes for _, _, _, nbytes in colls)
    assert total <= 4 * halo_budget + 64 * 16 * nb, total
    assert total < 90 * n_tot / 4, \
        f"spatial wire {total} B not clearly under the replicate " \
        f"column budget {90 * n_tot} B"


def test_scanstats_adds_no_collectives():
    """ISSUE-14 acceptance: turning ``SimConfig.scanstats`` on must add
    ZERO collectives to the compiled spatial chunk scan.  The scalar
    folds consume counts the kernels already reduce, and the [P]
    per-aircraft folds are a shard-aligned row split GSPMD keeps local
    — so the (op, dtype, shape) multiset of collectives in the ON
    program equals the OFF program exactly."""
    import jax.numpy as jnp
    from bluesky_tpu.core.step import SimConfig
    from bluesky_tpu.core.traffic import Traffic

    mesh = sharding.make_mesh(8)
    rng = np.random.default_rng(7)
    nmax, n = 4096, 1200
    traf = Traffic(nmax=nmax, dtype=jnp.float32, pair_matrix=False)
    traf.create(n, "B744", rng.uniform(3000, 11000, n),
                rng.uniform(130, 240, n), None,
                rng.uniform(35, 60, n), rng.uniform(-10, 30, n),
                rng.uniform(0, 360, n))
    traf.flush()
    cfg = SimConfig(cd_backend="sparse", cd_block=256,
                    cd_shard_mode="spatial")
    st, _, info = sharding.prepare_spatial(traf.state, mesh, cfg.asas)
    cfg = cfg._replace(cd_halo_blocks=info["halo_blocks"])

    def colls_for(c):
        # 21 steps: one full CD interval inside the scan at dtasas=1 s
        comp = sharding.sharded_step_fn(mesh, c, nsteps=21).lower(
            st).compile()
        return sorted((op, dtype, shape)
                      for op, dtype, shape, _ in _collectives(
                          comp.as_text()))

    off = colls_for(cfg)
    on = colls_for(cfg._replace(scanstats=True))
    assert off, "spatial chunk program must contain halo collectives"
    assert on == off, (
        "scanstats changed the collective set:\n"
        f"  off {off}\n  on  {on}")


def test_sharded_sparse_interval_collectives():
    mesh = sharding.make_mesh(8)
    st = sharding.shard_state(make_mixed_scene(), mesh)
    cfg = AsasConfig()

    def one_interval(s):
        s2, _ = asasmod.update_tiled(s, cfg, block=256, impl="sparse",
                                     mesh=mesh)
        return s2

    comp = jax.jit(one_interval).lower(st).compile()
    colls = _collectives(comp.as_text())
    assert colls, "sharded program must contain collectives"

    n = st.ac.lat.shape[0]
    n_tot = cd_sched.padded_size(n, 256)
    kk = st.asas.partners_s.shape[1]

    by_op = {}
    for op, dtype, shape, nbytes in colls:
        by_op.setdefault(op, []).append((dtype, shape, nbytes))

    # No stray collectives around the global stripe-sort/window-build:
    # those ops are recomputed per device, never resharded.
    for op in ("all-to-all", "reduce-scatter", "collective-permute"):
        assert op not in by_op, by_op.get(op)

    # Every all-gather is an O(N) column gather: its result holds at
    # most one padded column (n_tot elements, 2nd dim <= 1) — never a
    # slab, a tile, or anything O(N^2/D)-scaled.
    ags = by_op.get("all-gather", [])
    assert ags, "column gathers must exist"
    for dtype, shape, nbytes in ags:
        assert len(shape) <= 2, (dtype, shape)
        assert shape[0] <= n_tot, (dtype, shape)
        if len(shape) == 2:
            assert shape[1] <= 1, (dtype, shape)

    # The partner/accumulator back-permute is the only all-reduce
    # family, O(N*K) total; GSPMD fuses it into 1-2 ops — bound the
    # per-op and total SIZES, not the fusion count.
    ars = by_op.get("all-reduce", [])
    assert len(ars) <= 16, ars
    for dtype, shape, nbytes in ars:
        assert int(np.prod(shape)) <= 2 * n_tot * kk, (dtype, shape)

    # Total wire bytes per interval stay O(N): generously < 256 B per
    # padded slot (measured ~90), i.e. ~8 MB/interval at N=100k — vs
    # the ~2 GB the [N, N] pair space would cost.
    total = sum(nbytes for _, _, _, nbytes in colls)
    assert total < 256 * n_tot, total


def test_tiles_interval_collectives():
    """ISSUE 19 acceptance: the 2-D tile decomposition's per-interval
    communication is O(tile perimeter) — NO O(N) per-aircraft-column
    all-gathers, no all-to-alls, and the halo exchange is at most TWO
    collective-permutes per canonical edge/corner offset (slab + gid:
    2 x 5 = 10 on the 4x2 mesh, lon-wrap deduped), each bounded by its
    pinned per-offset budget's slab volume.  Wire total is
    O(N/D x perimeter) slabs plus the O(N/block) summary metadata."""
    import jax.numpy as jnp
    from bluesky_tpu.core.traffic import Traffic

    tiles = (4, 2)
    mesh = sharding.make_tile_mesh(tiles)
    rng = np.random.default_rng(7)
    nmax, n = 4096, 1200
    traf = Traffic(nmax=nmax, dtype=jnp.float32, pair_matrix=False)
    traf.create(n, "B744", rng.uniform(3000, 11000, n),
                rng.uniform(130, 240, n), None,
                rng.uniform(35, 60, n), rng.uniform(-10, 30, n),
                rng.uniform(0, 360, n))
    traf.flush()
    cfg = AsasConfig()
    st, _, info = sharding.prepare_tiles(traf.state, mesh, cfg,
                                         block=256)
    nb, block = info["nb"], 256
    budgets = tuple(info["budgets"])
    offs = tuple(info["offsets"])
    assert len(offs) == 5            # 4x2 canonical offset set

    def one_interval(s):
        s2, _ = asasmod.update_tiled(s, cfg, block=256, impl="sparse",
                                     mesh=mesh, shard_mode="tiles",
                                     tile_shape=tiles,
                                     tile_budgets=budgets)
        return s2

    comp = jax.jit(one_interval).lower(st).compile()
    colls = _collectives(comp.as_text())
    assert colls, "tiles program must contain halo collectives"

    by_op = {}
    for op, dtype, shape, nbytes in colls:
        by_op.setdefault(op, []).append((dtype, shape, nbytes))

    assert "all-to-all" not in by_op, by_op.get("all-to-all")

    # Every all-gather is block-summary metadata: O(nb) = O(N/block)
    # elements — the replicate scheme's O(N) column gathers must not
    # reappear in tiles mode.
    for dtype, shape, nbytes in by_op.get("all-gather", []):
        elems = int(np.prod(shape)) if shape else 1
        assert elems <= 16 * nb, \
            f"O(N)-scale all-gather leaked into tiles mode: " \
            f"{dtype}{list(shape)}"

    # Halo exchange: at most 2 permutes per canonical offset (the
    # summary slab + the gid row), each within its offset budget's
    # slab volume (16 f32 rows + 1 s32 gid row per block).
    perms = by_op.get("collective-permute", [])
    assert perms, "tile halo exchange must use collective-permute"
    assert len(perms) <= 2 * len(offs), \
        f"{len(perms)} permutes exceed the 2 x {len(offs)} " \
        f"slab+gid budget: {perms}"
    slab_budget = max(budgets) * 17 * block * 4
    for dtype, shape, nbytes in perms:
        assert nbytes <= slab_budget, (dtype, shape, nbytes)

    # All-reduces are scalar count psums.
    for dtype, shape, nbytes in by_op.get("all-reduce", []):
        assert int(np.prod(shape) if shape else 1) <= 64, (dtype, shape)

    # Per-interval wire total: the budgets' slab+gid volume (edge +
    # corner, O(N/D x perimeter)) plus O(nb) metadata — and clearly
    # under the O(N)-column budget replicate mode pays.
    wire_budget = sum(budgets) * 17 * block * 4
    total = sum(nbytes for _, _, _, nbytes in colls)
    assert total <= 2 * wire_budget + 64 * 16 * nb, total
    # at this toy scale the min-4 per-offset budget floor dominates, so
    # the margin is 2x rather than the ~10x a production N gives
    assert total < 90 * info["n_tot"] / 2, \
        f"tiles wire {total} B not clearly under the replicate " \
        f"column budget {90 * info['n_tot']} B"
