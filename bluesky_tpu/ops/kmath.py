"""Inverse-trig built from VPU-supported primitives, for Pallas kernels.

Mosaic's TPU lowering has no atan/atan2/asin (only sin/cos/sqrt/exp/log —
probed on hardware), but the conflict-detection geometry needs bearing
(atan2) and the MVP erratum term (arcsin).  These are classic Cephes-style
float32 evaluations: an odd minimax polynomial on |z| <= tan(pi/8) with the
two standard range reductions (reciprocal for |z| > 1, the tan(pi/8)
rotation otherwise), accurate to ~1 ulp f32 — well inside the f32 noise of
the surrounding haversine math.

The shared geometry cores (``geo._haversine_qdr_dist``,
``cr_mvp.pair_contrib_core``) take these as injectable parameters defaulting
to the exact jnp versions, so only the Pallas kernel pays the approximation.
"""
import jax.numpy as jnp

_PI = 3.14159265358979323846
_PI_2 = 1.57079632679489661923
_PI_4 = 0.78539816339744830962
_TAN_PI_8 = 0.41421356237309503


def _atan_pos(z):
    """arctan for z >= 0 (Cephes atanf reduction + degree-7 odd poly)."""
    big = z > 1.0
    zr = jnp.where(big, 1.0 / jnp.maximum(z, 1e-30), z)
    red = zr > _TAN_PI_8
    z2 = jnp.where(red, (zr - 1.0) / (zr + 1.0), zr)
    zz = z2 * z2
    p = ((8.05374449538e-2 * zz - 1.38776856032e-1) * zz
         + 1.99777106478e-1) * zz - 3.33329491539e-1
    y = z2 + z2 * zz * p
    y = jnp.where(red, y + _PI_4, y)
    return jnp.where(big, _PI_2 - y, y)


def atan(x):
    return jnp.sign(x) * _atan_pos(jnp.abs(x))


def atan2(y, x):
    """Four-quadrant arctangent; matches jnp.arctan2 on finite inputs
    (including the axes: atan2(0, x>0)=0, atan2(0, x<0)=pi, atan2(0,0)=0)."""
    ax = jnp.abs(x)
    ay = jnp.abs(y)
    base = _atan_pos(ay / jnp.maximum(ax, 1e-30))
    ang = jnp.where(x >= 0.0, base, _PI - base)
    return jnp.where(y >= 0.0, ang, -ang)


def asin(x):
    """arcsin on [-1, 1] via atan2(x, sqrt(1-x^2))."""
    x = jnp.clip(x, -1.0, 1.0)
    return atan2(x, jnp.sqrt(jnp.maximum(0.0, 1.0 - x * x)))


def asin_taylor(s, s2):
    """Odd Taylor arcsin for the haversine arc length, |s| <= 1.

    ``s2`` is s squared, which the caller already holds (the haversine
    takes s as a root).

    Error bounds that matter for conflict detection (s = sin(d/2R)):
    < 1e-9 relative for d <= 400 km — and a pair beyond ~400 km can
    neither be in LoS (d >> rpz) nor enter conflict within the 300 s
    lookahead (closing speed would have to exceed 1.3 km/s), so every
    distance that can flip a conflict/LoS flag is evaluated to full f32
    precision.  For far pairs the polynomial *under*-estimates the arc
    (up to 16% at the antipode), which cannot create a false conflict:
    dcpa scales with dist, so shrinking a >400 km pair still leaves
    dcpa orders of magnitude above the protected zone.
    """
    return s * (1.0 + s2 * (1.0 / 6.0 + s2 * (3.0 / 40.0 + s2 * (
        15.0 / 336.0 + s2 * (105.0 / 3456.0)))))
