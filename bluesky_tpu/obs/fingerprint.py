"""Device-side state fingerprints: a bit-pattern fold for SDC defense.

Silent data corruption — a flipped HBM bit, a marginal ALU, a corrupted
completion payload — completes a piece *wrong* without tripping the
in-scan isfinite guard (a flipped mantissa bit in ``lat`` is still
finite).  The defense (docs/FAULT_TOLERANCE.md §SDC defense) is
comparison: two executions of the same piece on healthy workers produce
the same stepped state bit-for-bit, so a cheap order-sensitive fold of
the state's raw bit patterns is a complete-state witness the server can
compare across hedge duplicates, shadow audits and 2-of-3 votes.

``FingerprintPack`` rides the chunk-scan CARRY exactly like ScanStats
(obs/scanstats.py): folded once per step from the post-step state,
emitted once per chunk as an extra non-donated output next to the
telemetry pack, behind the jit-static ``SimConfig.fingerprint`` flag.

Contracts (tests/test_sdc.py, the obs_smoke parity hash):

* **Off path is free.**  With the flag False the chunk scan traces the
  exact pre-existing HLO; folding never writes state, so the stepped
  state is bit-identical either way.
* **Zero host syncs, zero in-scan collectives.**  The fold is pure
  bitwise arithmetic on the carry; per-aircraft words fold to ``[P]``
  PER-DEVICE PARTIALS via the same ``reshape(P, nmax // P)`` row split
  as ScanStats (GSPMD keeps it local), XOR-combined host-side at the
  chunk edge.
* **Deterministic and order-sensitive.**  XOR alone would miss a value
  swapped between steps or fields; each step's contribution rotates the
  running fold left by one bit, and each guarded field's word is
  rotated by its field index, so time- and field-transposed corruption
  changes the fingerprint.  Comparability across workers assumes the
  deployment invariant the serving layer already holds: the same piece
  dispatched with the same SimConfig and the same nmax bucket (content-
  addressed pieces + the pack compatibility key guarantee this).

The fold watches the ``GUARD_FIELDS`` kinematic outputs plus the live
mask — the same complete-coverage argument as the isfinite guard: any
upstream corruption reaches one of these within a step or two, and a
fold over six [N] f32 columns stays ≪1% of the step pipeline.
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .scanstats import n_partials

#: 32-bit mask for host-side chain arithmetic (Python ints are wide).
_M32 = 0xFFFFFFFF


class FingerprintPack(NamedTuple):
    """Per-chunk fingerprint accumulator (the scan-carry resident).

    ``fp`` keeps [P] per-device partial folds (P = mesh size when a
    device mesh divides nmax, else 1 — ``scanstats.n_partials``), XORed
    into one 32-bit word host-side; ``steps`` counts folds so the host
    can sanity-check chunk arity when comparing.
    """
    fp: jnp.ndarray      # [P] uint32 — per-device partial folds
    steps: jnp.ndarray   # [] int32 — steps folded


def _rotl(x, k: int):
    """Rotate a uint32 word left by a static k (bits)."""
    k %= 32
    if k == 0:
        return x
    return (x << k) | (x >> (32 - k))


def _words(x) -> jnp.ndarray:
    """Bitcast any state leaf to uint32 words, shape-preserving: bools
    widen, 64-bit leaves XOR their two words (x64 mode safe)."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize <= 4:
        return x.astype(jnp.uint32)
    v = jax.lax.bitcast_convert_type(x, jnp.uint32)
    if v.ndim > x.ndim:          # 64-bit leaf split into 2 words
        v = v[..., 0] ^ v[..., 1]
    return v


def init(state, cfg) -> FingerprintPack:
    """Fresh fold for one chunk (built INSIDE the jitted chunk program,
    so chunk packs chain host-side from a known zero)."""
    p = n_partials(cfg, int(state.ac.active.shape[-1]))
    return FingerprintPack(fp=jnp.zeros((p,), jnp.uint32),
                           steps=jnp.zeros((), jnp.int32))


def fold(pack: FingerprintPack, state, cfg) -> FingerprintPack:
    """One scan-body fold of the post-step state into the carry.

    ``fp' = rotl(fp, 1) XOR step_word`` where ``step_word[P]`` XORs the
    row split of every watched column, each column pre-rotated by its
    field index.  Pure bitwise ops — no reductions beyond the row XOR,
    which GSPMD keeps shard-local (rows align with 'ac' shards).
    """
    from ..core.step import GUARD_FIELDS
    p = pack.fp.shape[0]
    ac = state.ac
    acc = _words(ac.active).reshape(p, -1)
    for i, f in enumerate(GUARD_FIELDS):
        acc = acc ^ _rotl(_words(getattr(ac, f)).reshape(p, -1), i + 1)
    part = jnp.bitwise_xor.reduce(acc, axis=1)        # [P], shard-local
    return FingerprintPack(fp=_rotl(pack.fp, 1) ^ part,
                           steps=pack.steps + 1)


# ------------------------------------------------------------------ host side

def combine(pack) -> int:
    """XOR a (device_get) pack's [P] partials into one 32-bit int."""
    fp = np.asarray(pack.fp, dtype=np.uint64)
    return int(np.bitwise_xor.reduce(fp)) & _M32 if fp.size else 0


def chain(prev: int, chunk_fp: int) -> int:
    """Fold one chunk fingerprint into the running piece chain — the
    same rotate-XOR recurrence as the in-scan fold, so chunk order
    matters and re-chunked identical runs still disagree only when the
    stepped states disagree."""
    prev &= _M32
    return (((prev << 1) | (prev >> 31)) ^ chunk_fp) & _M32


def summarize(chain_fp: int, chunks: int, steps: int) -> dict:
    """The wire/heartbeat summary dict for a running piece chain."""
    return {"fp": format(chain_fp & _M32, "08x"),
            "chunks": int(chunks), "steps": int(steps)}
